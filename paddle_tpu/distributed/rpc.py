"""distributed.rpc (reference: python/paddle/distributed/rpc/rpc.py:73 —
init_rpc / rpc_sync / rpc_async / get_worker_info / shutdown over a
master-rendezvous'd worker registry).

TPU-native re-design: the reference rides brpc+protobuf; here each worker
runs a small threaded TCP server executing pickled python callables, and
worker discovery rides the native TCPStore (distributed/store.py) — the
same rendezvous fabric the launcher and elastic manager use. RPC in this
framework is control-plane machinery (parameter-server-style coordination,
metrics aggregation); the data plane is XLA collectives, never RPC.

Trust model matches the reference: callables are pickled, so only run
inside a trusted cluster network (the reference's brpc endpoints are
likewise unauthenticated within the job).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import pickle
import socket
import struct
import threading
import time
from typing import Any, Dict, Optional

from ..resilience import faults as _faults

__all__ = ["init_rpc", "rpc_sync", "rpc_async", "get_worker_info",
           "get_all_worker_infos", "get_current_worker_info", "shutdown",
           "WorkerInfo"]

_MAX_FRAME = 64 << 20


@dataclasses.dataclass(frozen=True)
class WorkerInfo:
    name: str
    rank: int
    ip: str
    port: int


class _State:
    def __init__(self):
        self.store = None
        self.me: Optional[WorkerInfo] = None
        self.world_size = 0
        self.server: Optional[socket.socket] = None
        self.server_thread = None
        self.pool = None
        self.workers: Dict[str, WorkerInfo] = {}
        self.stopping = False


_state = _State()


def _garble(payload: bytes) -> bytes:
    """Deterministic frame corruption: flip the first byte and truncate
    to half — guaranteed to fail unpickling, same bytes every run.  The
    length header is built AFTER garbling so the frame stays
    self-consistent: the receiver reads exactly these corrupt bytes and
    fails at decode, not at the transport (the decode-rejection path is
    what chaos must prove)."""
    return bytes((payload[0] ^ 0xFF,)) + payload[1:max(1, len(payload) // 2)]


def _chaos(site, peer=None, kinds=_faults.NET_KINDS):
    """Consult the seeded net-fault plan at a transport choke point.
    Disabled path (PTPU_FAULTS unset): one global read inside
    ``net_fire``.  Raises for drop/partition, sleeps for a non-send
    delay, and returns the fired fault (or None) so the caller can act
    on send-side delay trickling and garbling."""
    f = _faults.net_fire(site=site, peer=peer, kinds=kinds)
    if f is None:
        return None
    if f.kind == "net_drop":
        exc = ConnectionRefusedError if site == "rpc.dial" \
            else ConnectionResetError
        raise exc(f"injected net_drop at {site} (peer={peer})")
    if f.kind == "net_partition":
        # one-directional blackhole: the caller learns nothing except
        # its own timeout; secs bounds how long the blackhole blocks
        # (tests should not pay real partition walls)
        time.sleep(f.secs)
        raise socket.timeout(f"injected net_partition at {site} "
                             f"(peer={peer})")
    if f.kind == "net_delay" and site != "rpc.send":
        time.sleep(f.secs)
    return f


def _send_frame(sock, payload: bytes, site="rpc.send", peer=None):
    f = _chaos(site, peer=peer)
    if f is not None and f.kind == "net_garble":
        payload = _garble(payload)
    hdr = struct.pack("<Q", len(payload))
    if f is not None and f.kind == "net_delay":
        # slow byte trickle: the frame arrives intact but takes ~secs,
        # spread over 8 chunks — exercises every partial-read path
        chunks = 8
        step = max(1, (len(payload) + chunks - 1) // chunks)
        sock.sendall(hdr)
        for i in range(0, len(payload), step):
            sock.sendall(payload[i:i + step])
            time.sleep(f.secs / chunks)
        return
    sock.sendall(hdr + payload)


def _recv_frame(sock, site="rpc.recv", peer=None) -> bytes:
    f = _chaos(site, peer=peer)
    hdr = _recv_exact(sock, 8)
    (n,) = struct.unpack("<Q", hdr)
    if n > _MAX_FRAME:
        raise RuntimeError(f"rpc frame too large: {n}")
    buf = _recv_exact(sock, n)
    if f is not None and f.kind == "net_garble":
        buf = _garble(buf)
    return buf


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("rpc peer closed")
        buf += chunk
    return buf


def _serve(server):
    while not _state.stopping:
        try:
            conn, _ = server.accept()
        except OSError:
            return
        threading.Thread(target=_handle, args=(conn,), daemon=True).start()


def _handle(conn):
    from ..monitor import trace as mtrace
    from ..monitor.wire import RPC_FRAME_MIN

    try:
        with conn:
            try:
                msg = pickle.loads(_recv_frame(conn))
                # frame arity is declared in monitor/wire.py (checked by
                # ptpu-check wire-compat): the first RPC_FRAME_MIN fields
                # are mandatory, everything beyond is optional — that
                # slice keeps a legacy 3-tuple client working mid-deploy
                fn, args, kwargs = msg[:RPC_FRAME_MIN]
                # optional 4th element: the caller's inject()-ed span
                # context — run the callable under a child span so one
                # trace_id spans both processes in export_chrome_trace()
                ctx = mtrace.extract(msg[RPC_FRAME_MIN]) \
                    if len(msg) > RPC_FRAME_MIN else None
            except (ConnectionError, OSError):
                raise               # transport death: nobody to reply to
            except Exception as e:
                # a garbled/truncated frame must error THIS request with
                # a structured reply, not kill the handler thread and
                # leave the caller blocked until its timeout — corrupted
                # pickles raise anything (UnpicklingError, EOFError,
                # AttributeError, ...), so the decode guard is broad
                _send_frame(conn, pickle.dumps(
                    (False, RuntimeError(f"garbled rpc frame: {e!r}"))))
                return
            try:
                if ctx is not None:
                    with mtrace.attach(ctx), mtrace.span(
                            "rpc/serve",
                            fn=getattr(fn, "__name__", repr(fn))):
                        result = (True, fn(*args, **kwargs))
                else:
                    result = (True, fn(*args, **kwargs))
            except Exception as e:  # ship the failure back to the caller
                result = (False, e)
            _send_frame(conn, pickle.dumps(result))
    except (ConnectionError, OSError):
        pass


def init_rpc(name: str, rank: int = None, world_size: int = None,
             master_endpoint: str = None):
    """Register this worker and wait for the full world (rpc.py:73).
    rank 0 hosts the rendezvous store at master_endpoint."""
    from .store import TCPStore

    if _state.me is not None:
        raise RuntimeError("init_rpc already called")
    rank = int(os.environ.get("PADDLE_TRAINER_ID", 0) if rank is None else rank)
    world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", 1)
                    if world_size is None else world_size)
    master_endpoint = master_endpoint or os.environ.get(
        "PADDLE_MASTER_ENDPOINT", "127.0.0.1:8711")
    host, port = master_endpoint.rsplit(":", 1)

    server = socket.create_server(("0.0.0.0", 0))
    my_port = server.getsockname()[1]
    _state.server = server
    _state.stopping = False
    _state.server_thread = threading.Thread(
        target=_serve, args=(server,), daemon=True)
    _state.server_thread.start()
    _state.pool = concurrent.futures.ThreadPoolExecutor(max_workers=8)

    store = TCPStore(host, int(port), is_master=(rank == 0),
                     world_size=world_size)
    _state.store = store
    my_ip = "127.0.0.1" if host in ("127.0.0.1", "localhost") else \
        socket.gethostbyname(socket.gethostname())
    me = WorkerInfo(name, rank, my_ip, my_port)
    store.set(f"rpc/worker/{name}",
              pickle.dumps(dataclasses.astuple(me)))
    store.set(f"rpc/rank/{rank}", name.encode())
    store.add("rpc/joined", 1)
    store.barrier("rpc_init", world_size)
    _state.me = me
    _state.world_size = world_size
    return me


def get_current_worker_info() -> WorkerInfo:
    _check_init()
    return _state.me


def get_worker_info(name: str) -> WorkerInfo:
    _check_init()
    if name not in _state.workers:
        raw = _state.store.get(f"rpc/worker/{name}")
        _state.workers[name] = WorkerInfo(*pickle.loads(raw))
    return _state.workers[name]


def get_all_worker_infos():
    _check_init()
    out = []
    for r in range(_state.world_size):
        name = _state.store.get(f"rpc/rank/{r}").decode()
        out.append(get_worker_info(name))
    return out


def rpc_sync(to: str, fn, args=None, kwargs=None, timeout: float = 60.0):
    """Run fn(*args, **kwargs) on worker `to`, blocking for the result."""
    return _call(to, fn, args or (), kwargs or {}, timeout)


def rpc_async(to: str, fn, args=None, kwargs=None, timeout: float = 60.0):
    """Like rpc_sync but returns a Future (reference returns a FutureWrapper
    with .wait(); concurrent.futures.Future has the same .result surface)."""
    _check_init()
    fut = _state.pool.submit(_call, to, fn, args or (), kwargs or {}, timeout)
    fut.wait = fut.result  # reference API spells it .wait()
    return fut


def _budget(timeout, deadline):
    """Per-socket-op bound: the Deadline's remaining budget, never the
    full timeout re-armed after earlier ops consumed part of it.
    Explicit None check: remaining() == 0.0 is falsy but means "out of
    budget", not "use the full timeout again"."""
    remaining = deadline.remaining()
    return timeout if remaining is None else max(remaining, 1e-3)


def _call(to, fn, args, kwargs, timeout):
    _check_init()
    from ..monitor import trace as mtrace
    from ..resilience.retry import Deadline, retry as _retry

    info = get_worker_info(to)
    deadline = Deadline(timeout)

    def dial():
        # retry ONLY the dial: once the frame is sent the call may have
        # executed on the peer, and blind re-issue would double-run a
        # non-idempotent fn — a dial failure is provably side-effect-free
        _faults.maybe_raise("conn_error", site="rpc.dial")
        _chaos("rpc.dial", peer=to,
               kinds=("net_drop", "net_delay", "net_partition"))
        return socket.create_connection(
            (info.ip, info.port), timeout=_budget(timeout, deadline))

    # retryable=(OSError,) covers the whole dial-failure family —
    # ConnectionError/ConnectionRefusedError/ConnectionResetError/
    # socket.timeout are all OSError subclasses; the deadline bounds total
    # time and a dial failure is always side-effect-free
    with mtrace.span("rpc/call", to=to):
        # the header parents the REMOTE rpc/serve span under this call
        # span; with tracing off span() is the no-op singleton and
        # inject() is one global read → None (tests/test_fleet.py;
        # tests/test_disabled_overhead.py times it).
        # No header → the LEGACY 3-tuple frame, so the DEFAULT
        # (PTPU_TRACE off) path is wire-identical to older servers
        # mid-deploy; a TRACED call sends the 4-tuple and therefore
        # requires the receiving worker to run this version too —
        # enable propagation only once the fleet is upgraded
        hdr = mtrace.inject()
        frame = (fn, args, kwargs) if hdr is None \
            else (fn, args, kwargs, hdr)
        with _retry(dial, retries=3, backoff=0.05, max_backoff=1.0,
                    deadline=deadline, site="rpc.dial",
                    retryable=(OSError,))() as s:
            # send/recv are bounded by the REMAINING Deadline budget,
            # not the full timeout re-armed — the dial (and its
            # retries) already spent part of it
            s.settimeout(_budget(timeout, deadline))
            _send_frame(s, pickle.dumps(frame), peer=to)
            raw = _recv_frame(s, peer=to)
            try:
                ok, payload = pickle.loads(raw)
            except Exception as e:
                # a garbled reply errors this one call — callers treat
                # RuntimeError as a transport-class failure (reroute /
                # resubmit), and the request is NOT blindly re-sent
                raise RuntimeError(
                    f"garbled rpc reply from {to!r}: {e!r}") from e
    if not ok:
        raise payload
    return payload


def shutdown():
    """Graceful stop: barrier so no peer is mid-call, then close."""
    if _state.me is None:
        return
    try:
        _state.store.barrier("rpc_shutdown", _state.world_size)
    except (ConnectionError, OSError, TimeoutError):
        pass   # ptpu-check[silent-except]: best-effort drain barrier — a peer that died
        # uncleanly must not wedge every surviving worker's shutdown
    _state.stopping = True
    try:
        _state.server.close()
    except OSError:
        pass
    if _state.pool is not None:
        _state.pool.shutdown(wait=False)
    try:
        _state.store.close()
    except (ConnectionError, OSError):
        pass   # ptpu-check[silent-except]: socket already dead — shutdown must finish
    _state.__init__()


def _check_init():
    if _state.me is None:
        raise RuntimeError("call paddle.distributed.rpc.init_rpc first")
