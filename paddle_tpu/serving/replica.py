"""Replica worker — the engine-owning half of the multi-replica tier.

`ReplicaWorker` wraps one `LLMEngine` behind the router protocol
(`serving/router.py` builds the frames; `monitor/wire.py` declares
them).  The split is thread-shaped: `distributed/rpc.py` delivers
`_remote_submit` / `_remote_adopt` / `_remote_poll` on its serve
threads, which only touch lock-guarded deques — admission, stepping,
export and harvest all happen in `pump()`, on whatever thread owns the
engine (jax programs are driven from exactly one place).  One `pump()`
is one cycle: drain check → admit inbox → `engine.step()` → harvest
(results, prefill handoffs, deadline expiries).

Roles (`RouterConfig.disaggregate` routes on them):

- ``both`` (default) — classic replica: prefill + decode locally.
- ``prefill`` — runs prompt prefills and samples the FIRST token, then
  exports the request (`LLMEngine.export_request`: evolved PRNG key +
  bit-exact `swap_out` KV snapshot) as a handoff frame the router
  forwards to a decode worker.  Absorbs the compile-heavy long-prompt
  program ladder.
- ``decode`` — only ever receives handoffs (`adopt_request` rides the
  scheduler's swap-resume path), so it dispatches exactly one
  fixed-shape ``ragged(max_num_seqs, 1)`` program, forever.

Drain (SIGTERM via `resilience.PreemptionHandler`, or `start_drain()`):
admission stops (`submit_local` returns False — the router re-routes),
never-computed WAITING requests are released with reason ``migrated``
and returned to the router as requeued submit frames, and the running
ones finish normally.  `serve_loop` exits once drained AND the router
has polled the last outbox — a drained worker never strands a result.

Fault hook: each pump crosses ``faults.maybe_crash(site="replica.step")``
so `PTPU_FAULTS="ckpt_crash@site=replica.step,hard=1"` kills a replica
mid-stream deterministically — the failover smoke's kill switch.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..monitor import trace as mtrace
from ..resilience import faults
from .router import (handoff_frame, params_from_wire, poll_frame,
                     result_frame, submit_frame)
from .scheduler import Request

__all__ = ["ReplicaWorker", "install", "current_worker",
           "_remote_submit", "_remote_adopt", "_remote_poll"]


class ReplicaWorker:
    """One engine behind the router protocol.  `handler` is an optional
    `PreemptionHandler` (or anything with a truthy ``triggered``) polled
    each pump; tests inject a stub, `serve_loop` installs the real
    one."""

    def __init__(self, engine, name: str = None, role: str = "both",
                 handler=None):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown replica role {role!r}")
        self.engine = engine
        self.name = name or os.environ.get("PTPU_REPLICA_ID") \
            or f"replica-{os.getpid()}"
        self.role = role
        self.handler = handler
        self._lock = threading.Lock()
        self._inbox: deque = deque()      # ("submit"|"adopt", frame)
        self._results: list = []          # result frames for the router
        self._handoffs: list = []         # handoff frames (prefill role)
        self._requeued: list = []         # submit frames (drain)
        self._owned: dict = {}            # engine rid -> original frame
        self._draining = False

    # -- rpc-thread surface (lock-guarded, never touches the engine) --------

    @staticmethod
    def _frame_ok(frame) -> bool:
        """Structural gate at the rpc boundary: a garbled frame that
        survived unpickling by luck must be refused HERE (the router
        re-routes on False), never enqueued where it would blow up
        `pump()` and wedge the one engine thread."""
        return (isinstance(frame, dict)
                and isinstance(frame.get("rid"), int)
                and isinstance(frame.get("prompt_ids"), (list, tuple)))

    def submit_local(self, frame) -> bool:
        """Accept a submit frame (False while draining — the router
        re-routes; no partial admission)."""
        if not self._frame_ok(frame):
            return False
        with self._lock:
            if self._draining:
                return False
            self._inbox.append(("submit", frame))
            return True

    def adopt_local(self, frame) -> bool:
        if not self._frame_ok(frame):
            return False
        with self._lock:
            if self._draining:
                return False
            self._inbox.append(("adopt", frame))
            return True

    def poll_local(self) -> dict:
        """Hand the router everything accumulated since its last poll
        (results, handoffs, drain requeues) in one frame."""
        with self._lock:
            doc = poll_frame(self.name, self._draining,
                             self._results, self._handoffs,
                             self._requeued)
            self._results = []
            self._handoffs = []
            self._requeued = []
        return doc

    # -- engine-thread pump --------------------------------------------------

    def pump(self) -> bool:
        """One worker cycle; returns True while there is (or may be)
        work.  Engine-owning thread only."""
        # deterministic mid-stream kill for the failover smoke
        faults.maybe_crash(site="replica.step")
        if not self._draining and self.handler is not None \
                and getattr(self.handler, "triggered", False):
            self.start_drain()
        self._admit()
        if self.engine.has_unfinished():
            self.engine.step()
            if self.role == "prefill":
                # what this worker hands off is a prompt's first token:
                # read it back now, not behind a decode step that the
                # engine would dispatch for a row about to leave
                self.engine.settle()
        else:
            mtrace.heartbeat()   # idle pump still feeds the watchdog
        self._harvest()
        with self._lock:
            backlog = bool(self._inbox)
        return backlog or self.engine.has_unfinished()

    def _admit(self) -> None:
        with self._lock:
            batch = list(self._inbox)
            self._inbox.clear()
        for kind, frame in batch:
            if self._draining:
                # raced into the inbox as drain fired: bounce straight
                # back to the router, nothing was admitted
                with self._lock:
                    self._requeued.append(self._as_submit(frame))
                continue
            self._admit_one(kind, frame)

    def _admit_one(self, kind: str, frame: dict) -> None:
        # join the router's trace: the admit span carries the router-side
        # trace_id, so one trace spans router dispatch -> replica admit
        ctx = mtrace.extract(frame.get("trace"))
        sp = None
        if ctx is not None:
            sp = mtrace.start_span("replica/admit", parent=ctx,
                                   rid=frame.get("rid"), kind=kind,
                                   replica=self.name)
        try:
            # params decode is INSIDE the guard: a structurally-valid
            # frame with garbled params (wrong field types, non-dict)
            # must error this one request, not kill the pump
            params = params_from_wire(frame.get("params"))
            if kind == "adopt":
                erid = self.engine.adopt_request(
                    frame["prompt_ids"], params, frame["output_ids"],
                    frame["key"], frame["kv"])
            else:
                erid = self.engine.add_request(frame["prompt_ids"],
                                               params)
        except (ValueError, KeyError, TypeError, AttributeError) as e:
            # malformed request (empty/over-long prompt, spent handoff,
            # garbled field): a clean error result, not a wedged stream
            with self._lock:
                self._results.append(result_frame(
                    frame.get("rid"), self.name, ok=False,
                    finish_reason="abort", error=repr(e)))
            return
        finally:
            if sp is not None:
                sp.end()
        self._owned[erid] = frame

    def _harvest(self) -> None:
        out_results, out_handoffs = [], []
        for erid in list(self._owned):
            frame = self._owned[erid]
            req = self.engine._requests.get(erid)
            if req is None:
                # the engine's deadline sweep released it inside step()
                # — the only internal release path for an owned request
                out_results.append(result_frame(
                    frame["rid"], self.name, ok=False,
                    finish_reason="deadline",
                    error="deadline_s expired on the replica"))
                del self._owned[erid]
                continue
            if req.finished:
                out_results.append(result_frame(
                    frame["rid"], self.name, ok=True,
                    token_ids=self.engine.request_output(erid),
                    finish_reason="stop"))
                self.engine.release_request(erid)
                del self._owned[erid]
                continue
            if self.role == "prefill" and req.prefill_done \
                    and req.output_ids \
                    and req in self.engine.scheduler.running:
                # prefill half done (first token sampled): export for a
                # decode worker, KV block-for-block
                h = self.engine.export_request(erid)
                out_handoffs.append(handoff_frame(
                    frame["rid"], h["prompt_ids"], h["output_ids"],
                    frame.get("params"), h["key"], h["kv"],
                    trace=frame.get("trace")))
                del self._owned[erid]
        if out_results or out_handoffs:
            with self._lock:
                self._results.extend(out_results)
                self._handoffs.extend(out_handoffs)

    # -- drain ---------------------------------------------------------------

    def start_drain(self) -> None:
        """Stop admission and return never-computed waiting requests to
        the router (released locally with reason "migrated" — their
        terminal state HERE is a success elsewhere).  Running requests
        finish normally; idempotent."""
        with self._lock:
            if self._draining:
                return
            self._draining = True
            bounced = [self._as_submit(f) for _, f in self._inbox]
            self._inbox.clear()
        requeue = []
        for erid in list(self._owned):
            req = self.engine._requests.get(erid)
            if req is None or req.state != Request.WAITING:
                continue   # running/preempted requests run to completion
            frame = self._owned.pop(erid)
            self.engine.release_request(erid, reason="migrated")
            requeue.append(self._as_submit(frame))
        with self._lock:
            self._requeued.extend(bounced + requeue)

    @staticmethod
    def _as_submit(frame: dict) -> dict:
        """A requeueable submit frame from either a submit or a handoff
        frame (a bounced handoff resubmits from-prompt: its KV snapshot
        is forfeit, the tokens are not — generation is deterministic)."""
        return submit_frame(frame["rid"], frame["prompt_ids"],
                            frame.get("params"), trace=frame.get("trace"))

    def drained(self) -> bool:
        """True once draining AND nothing is left to run or hand back."""
        if not self._draining or self.engine.has_unfinished():
            return False
        with self._lock:
            return not (self._inbox or self._results
                        or self._handoffs or self._requeued)

    # -- process loop --------------------------------------------------------

    def serve_loop(self, idle_sleep_s: float = 0.005) -> None:
        """Pump until drained (the production loop).  Installs a
        `PreemptionHandler` when none was injected, so SIGTERM = drain;
        returns only after the router has polled the last outbox."""
        if self.handler is None:
            from ..resilience.retry import PreemptionHandler

            self.handler = PreemptionHandler().install()
        while True:
            busy = self.pump()
            if self.drained():
                return
            if not busy:
                time.sleep(idle_sleep_s)


# -- rpc entrypoints ----------------------------------------------------------
# rpc_sync ships the FUNCTION by reference; these resolve against the
# process-global worker the replica main installed.

_worker: "ReplicaWorker | None" = None


def install(worker: ReplicaWorker) -> ReplicaWorker:
    """Register `worker` as this process's rpc target."""
    global _worker
    _worker = worker
    return worker


def current_worker() -> "ReplicaWorker | None":
    return _worker


def _require() -> ReplicaWorker:
    if _worker is None:
        raise RuntimeError("no ReplicaWorker installed in this process "
                           "(call serving.replica.install(worker) first)")
    return _worker


def _remote_submit(frame) -> bool:
    return _require().submit_local(frame)


def _remote_adopt(frame) -> bool:
    return _require().adopt_local(frame)


def _remote_poll() -> dict:
    return _require().poll_local()
