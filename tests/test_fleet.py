"""paddle_tpu.monitor v4 — fleet observability plane (ISSUE 11).

Subprocess-free fast tier: the exposition parser and the
merge-round-trip exactness pin (scrape → parse → merge → re-export ==
sum/union of the sources, histograms included), the trace
inject/extract propagation (incl. the rpc frame carrying it, and the
disabled pair returning None and recording nothing), the store-registration key format, the rollup
state machine driven by a fake scraper (healthy → stalled → down, with
flight-dump harvesting on transition), and the endpoint surface
(/healthz identity fields, /flight/latest).

The cross-PROCESS half — two real replicas + aggregator + a
PTPU_FAULTS-stalled replica — is scripts/fleet_smoke.py, run by the
slow-tier test at the bottom (fast-tier subprocess budget is spent,
per ROADMAP).
"""
import json
import os
import pathlib
import pickle
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

import paddle_tpu  # noqa: F401  (backend pinned by the suite env)
from paddle_tpu import monitor
from paddle_tpu.monitor import fleet, flight, serve, trace


@pytest.fixture(autouse=True)
def _fresh():
    monitor.reset()
    monitor.enable(True)
    trace.enable(True)
    trace.reset()
    yield
    trace.enable(False)
    trace.reset()
    monitor.reset()
    monitor.refresh()
    trace.refresh()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# trace context propagation
# ---------------------------------------------------------------------------

def test_inject_extract_roundtrip():
    with trace.span("t/root") as root:
        hdr = trace.inject()
        assert hdr is not None and ";" in hdr
        ctx = trace.extract(hdr)
        assert isinstance(ctx, trace.SpanContext)
        assert ctx.trace_id == root.trace_id
        assert ctx.span_id == root.span_id
        # a span parented on the extracted context joins the trace
        child = trace.start_span("t/from_wire", parent=ctx)
        child.end()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
    names = {s["name"] for s in trace.get_trace(root.trace_id)}
    assert names == {"t/root", "t/from_wire"}


def test_attach_accepts_span_context():
    root = trace.start_span("t/root")
    ctx = trace.extract(trace.inject(root))
    with trace.attach(ctx):
        with trace.span("t/attached") as c:
            pass
    root.end()
    assert c.trace_id == root.trace_id and c.parent_id == root.span_id


def test_extract_rejects_garbage():
    assert trace.extract(None) is None
    assert trace.extract("") is None
    assert trace.extract("garbage") is None
    assert trace.extract("other;x;y") is None
    assert trace.extract("ptpu1;;y") is None


def test_inject_outside_any_span_is_none():
    assert trace.current_span() is None
    assert trace.inject() is None


def test_inject_extract_disabled_and_under_budget():
    """Disabled propagation hooks return at the gate: the rpc hot path
    runs inject+extract per call, so with PTPU_TRACE=0 the pair hands
    back None, even inside a span opened before tracing went off, and
    records nothing.  The per-call time bound lives in
    tests/test_disabled_overhead.py (slow tier)."""
    open_span = trace.start_span("t/open")   # a live context to leak
    trace.enable(False)
    try:
        assert trace.inject() is None
        assert trace.inject(open_span) is None
        assert trace.extract("ptpu1;a;b") is None   # receiver-side gate
        assert trace.extract(trace.inject()) is None
    finally:
        trace.enable(True)
        open_span.end()
    assert trace.trace_ids() == [open_span.trace_id]   # only the span


def _rpc_probe():
    """Executed 'remotely' by rpc._handle: report the callee-side trace
    context and leave a child span."""
    cur = trace.current_span()
    with trace.span("t/remote_work"):
        pass
    return None if cur is None else cur.trace_id


def test_rpc_frame_carries_trace_context():
    """The rpc wire format's 4th element parents the callee's rpc/serve
    span under the caller's span — one trace_id, both sides (the
    in-process twin of the fleet smoke's cross-process assert)."""
    from paddle_tpu.distributed import rpc

    a, b = socket.socketpair()
    try:
        with trace.span("t/caller") as caller:
            hdr = trace.inject()
            rpc._send_frame(a, pickle.dumps((_rpc_probe, (), {}, hdr)))
            rpc._handle(b)
            ok, remote_tid = pickle.loads(rpc._recv_frame(a))
    finally:
        a.close()
    assert ok and remote_tid == caller.trace_id
    spans = {s["name"]: s for s in trace.get_trace(caller.trace_id)}
    assert "rpc/serve" in spans and "t/remote_work" in spans
    assert spans["rpc/serve"]["parent_id"] == caller.span_id
    assert spans["t/remote_work"]["parent_id"] == \
        spans["rpc/serve"]["span_id"]


def test_rpc_handle_accepts_legacy_three_tuple():
    from paddle_tpu.distributed import rpc

    a, b = socket.socketpair()
    try:
        rpc._send_frame(a, pickle.dumps((_rpc_probe, (), {})))
        rpc._handle(b)
        ok, remote_tid = pickle.loads(rpc._recv_frame(a))
    finally:
        a.close()
    assert ok and remote_tid is None   # no header → no adopted context


def test_rpc_frame_header_ignored_when_receiver_disabled():
    from paddle_tpu.distributed import rpc

    with trace.span("t/caller"):
        hdr = trace.inject()
    trace.enable(False)
    try:
        a, b = socket.socketpair()
        try:
            rpc._send_frame(a, pickle.dumps((_rpc_probe, (), {}, hdr)))
            rpc._handle(b)
            ok, remote_tid = pickle.loads(rpc._recv_frame(a))
        finally:
            a.close()
    finally:
        trace.enable(True)
    assert ok and remote_tid is None


# ---------------------------------------------------------------------------
# exposition parser + merge round-trip (the federation primitive)
# ---------------------------------------------------------------------------

def _fill(reg: "monitor.StatRegistry", scale: float):
    reg.counter("serving/decode_tokens", "new tokens").add(100 * scale)
    reg.counter("serving/compiles").labels(kind="decode").add(2 * scale)
    reg.counter("serving/compiles").labels(kind="prefill").add(scale)
    reg.gauge("serving/queue_depth", "queued").set(3 * scale)
    h = reg.histogram("serving/ttft", "s", buckets=(0.01, 0.1, 1.0))
    for v in (0.005 * scale, 0.05, 0.5, 2.0 * scale):   # incl. overflow
        h.observe(v)
    reg.histogram("serving/tpot").labels(replica_kind="x").observe(0.02)


def test_parse_prometheus_typed_roundtrip():
    reg = monitor.StatRegistry()
    _fill(reg, 1)
    parsed = fleet.parse_prometheus(reg.export_prometheus())
    assert parsed["serving_decode_tokens"]["kind"] == "counter"
    assert parsed["serving_decode_tokens"]["help"] == "new tokens"
    assert parsed["serving_decode_tokens"]["series"][()] == 100.0
    assert fleet.series_value(parsed, "serving_compiles",
                              kind="decode") == 2.0
    assert parsed["serving_queue_depth"]["kind"] == "gauge"
    h = fleet.series_value(parsed, "serving_ttft")
    assert h["buckets"] == (0.01, 0.1, 1.0)
    assert h["counts"] == [1, 1, 1, 1] and h["count"] == 4
    assert h["sum"] == 0.005 + 0.05 + 0.5 + 2.0   # repr round-trip: exact
    hx = fleet.series_value(parsed, "serving_tpot", replica_kind="x")
    assert hx["count"] == 1


def test_parse_skips_foreign_lines():
    parsed = fleet.parse_prometheus(
        "# random comment\n"
        "weird{ 1\n"
        "ok_metric 4\n"
        "nan_metric not_a_number\n")
    assert fleet.series_value(parsed, "ok_metric") == 4.0
    assert "weird" not in parsed and "nan_metric" not in parsed


def test_merge_roundtrip_equals_sum_of_sources():
    """ISSUE 11 satellite pin: scrape → parse → merge → re-export
    equals the sum/union of the source registries — counters sum (with
    replica labels present), gauges keep per-replica values, histogram
    buckets add elementwise, and p50/p95/p99 come back recomputed from
    the merged buckets."""
    a, b = monitor.StatRegistry(), monitor.StatRegistry()
    _fill(a, 1)
    _fill(b, 7)
    fl = monitor.StatRegistry()
    fl.merge_snapshot(fleet.parse_prometheus(a.export_prometheus()),
                      labels={"replica": "r0"})
    fl.merge_snapshot(fleet.parse_prometheus(b.export_prometheus()),
                      labels={"replica": "r1"})
    out = fleet.parse_prometheus(fl.export_prometheus())

    # counters: original series holds the exact sum, replicas labeled
    assert fleet.series_value(out, "serving_decode_tokens") == 800.0
    assert fleet.series_value(out, "serving_decode_tokens",
                              replica="r0") == 100.0
    assert fleet.series_value(out, "serving_decode_tokens",
                              replica="r1") == 700.0
    assert fleet.series_value(out, "serving_compiles",
                              kind="decode") == 16.0
    assert fleet.series_value(out, "serving_compiles", kind="prefill",
                              replica="r1") == 7.0
    # gauges: per-replica only, no fabricated sum series
    assert fleet.series_value(out, "serving_queue_depth") is None
    assert fleet.series_value(out, "serving_queue_depth",
                              replica="r0") == 3.0
    assert fleet.series_value(out, "serving_queue_depth",
                              replica="r1") == 21.0
    # histograms: buckets add elementwise, sums exactly
    pa = fleet.series_value(
        fleet.parse_prometheus(a.export_prometheus()), "serving_ttft")
    pb = fleet.series_value(
        fleet.parse_prometheus(b.export_prometheus()), "serving_ttft")
    hm = fleet.series_value(out, "serving_ttft")
    assert hm["counts"] == [ca + cb for ca, cb
                            in zip(pa["counts"], pb["counts"])]
    assert hm["count"] == pa["count"] + pb["count"]
    assert hm["sum"] == pa["sum"] + pb["sum"]
    hr0 = fleet.series_value(out, "serving_ttft", replica="r0")
    assert hr0["counts"] == pa["counts"] and hr0["sum"] == pa["sum"]

    # percentiles recomputed from the MERGED buckets, inside the
    # occupied range and monotone
    merged = fl.get("serving_ttft")
    p50, p95, p99 = (merged.percentile(q) for q in (50, 95, 99))
    assert 0.0 < p50 <= p95 <= p99
    snap = merged.snapshot()[""]
    assert snap["count"] == 8 and {"p50", "p95", "p99"} <= set(snap)

    # and the whole cycle is idempotent: re-parse(re-export) == itself
    again = fleet.parse_prometheus(fl.export_prometheus())
    assert again == out


def test_label_values_with_escapes_roundtrip():
    """Backslash-then-n label values must survive export → parse (a
    two-pass unescape would turn 'C:\\new' into 'C:' + newline + 'ew'
    and split the series key across the fleet)."""
    reg = monitor.StatRegistry()
    for val in ("C:\\new", 'say "hi"', "line\nbreak", "back\\\\slash"):
        reg.counter("t/paths").labels(p=val).add(1)
    parsed = fleet.parse_prometheus(reg.export_prometheus())
    keys = {dict(k)["p"] for k in parsed["t_paths"]["series"]}
    assert keys == {"C:\\new", 'say "hi"', "line\nbreak", "back\\\\slash"}
    # and a merged re-export parses back to the SAME series keys
    fl = monitor.StatRegistry()
    fl.merge_snapshot(parsed)
    again = fleet.parse_prometheus(fl.export_prometheus())
    assert again["t_paths"]["series"] == parsed["t_paths"]["series"]


def test_merge_rejects_mismatched_histogram_buckets():
    src = monitor.StatRegistry()
    src.histogram("t/h", buckets=(0.1, 1.0)).observe(0.5)
    parsed = fleet.parse_prometheus(src.export_prometheus())
    dst = monitor.StatRegistry()
    dst.histogram("t_h", buckets=(0.2, 2.0)).observe(0.5)
    with pytest.raises(ValueError, match="bucket bounds"):
        dst.merge_snapshot(parsed)


# ---------------------------------------------------------------------------
# store registration + discovery
# ---------------------------------------------------------------------------

class _FakeStore:
    def __init__(self):
        self.kv = {}
        self.counts = {}

    def add(self, key, n):
        self.counts[key] = self.counts.get(key, 0) + n
        return self.counts[key]

    def set(self, key, val):
        self.kv[key] = val

    def get(self, key, timeout_ms=0):
        return self.kv.get(key)

    def close(self):
        pass


class _FakeServer:
    url = "http://127.0.0.1:4242"


def test_registration_key_format(monkeypatch):
    """The slot-log contract the aggregator discovers through: ADD on
    fleet/replicas/next claims slot n, the JSON record lands at
    fleet/replicas/<n> with name/url/identity."""
    monkeypatch.setenv("PTPU_REPLICA_ID", "r9")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "9")
    fs = _FakeStore()
    rec = fleet.register_replica(_FakeServer(), store=fs)
    assert fs.counts == {fleet.REPLICA_COUNT_KEY: 1}
    assert list(fs.kv) == [f"{fleet.REPLICA_KEY_PREFIX}1"]
    doc = json.loads(fs.kv[f"{fleet.REPLICA_KEY_PREFIX}1"])
    assert doc == rec
    assert doc["name"] == "r9" and doc["url"] == _FakeServer.url
    assert doc["replica_id"] == "r9" and doc["rank"] == 9
    assert doc["pid"] == os.getpid() and "host" in doc and "ts" in doc
    # restart: a new slot, discovery keeps the newest record per name
    fleet.register_replica(_FakeServer(), store=fs, name="r9")
    assert fs.counts[fleet.REPLICA_COUNT_KEY] == 2
    recs = fleet.discover(store=fs)
    assert [r["name"] for r in recs] == ["r9"]


def test_store_client_against_real_store():
    """The stdlib wire client in fleet.py speaks the native TCPStore
    protocol — registration/discovery round-trips through a real store
    server, no paddle_tpu import needed on the monitor side."""
    from paddle_tpu.distributed.store import TCPStore

    port = _free_port()
    master = TCPStore("127.0.0.1", port, is_master=True, world_size=1)
    try:
        cli = fleet._StoreClient("127.0.0.1", port)
        assert cli.add("t/ctr", 5) == 5
        cli.set("t/key", b"payload")
        assert cli.get("t/key", timeout_ms=1000) == b"payload"
        assert cli.get("t/missing", timeout_ms=50) is None
        rec = fleet.register_replica(_FakeServer(), store=cli, name="rA")
        recs = fleet.discover(store=cli)
        assert [r["name"] for r in recs] == ["rA"]
        assert recs[0]["url"] == rec["url"]
        cli.close()
    finally:
        master.close()


def test_store_client_ops_bounded_against_wedged_store():
    """A store that ACCEPTS but never answers (SIGSTOPped/black-holed)
    must not hang registration or the aggregator poll thread: ops carry
    a socket timeout, surfacing as the OSError every caller contains."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)   # accept queue only — nobody ever replies
    try:
        cli = fleet._StoreClient("127.0.0.1", srv.getsockname()[1],
                                 timeout_s=1.0)
        cli._io_timeout = 0.3
        cli._sock.settimeout(0.3)
        t0 = time.monotonic()
        with pytest.raises(OSError):
            cli.add("t/never_answered", 1)
        assert time.monotonic() - t0 < 3.0
        cli.close()
    finally:
        srv.close()


def test_spawn_target_replica_id_composes(monkeypatch):
    """spawn() under a multi-host launch() must not collapse fleet
    names: an inherited PTPU_REPLICA_ID (per-host, from launch) becomes
    the PREFIX of the per-child id instead of being either kept
    verbatim (duplicates across ranks) or overwritten (duplicates
    across hosts)."""
    from paddle_tpu.distributed import launch_mod

    for key in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                "PADDLE_LOCAL_RANK"):
        monkeypatch.setenv(key, "sentinel")   # restored by monkeypatch
    seen = {}

    def probe():
        seen["rid"] = os.environ["PTPU_REPLICA_ID"]

    monkeypatch.delenv("PTPU_REPLICA_ID", raising=False)
    launch_mod._spawn_target(probe, (), rank=2, nprocs=4, backend=None)
    assert seen["rid"] == "r2"
    monkeypatch.setenv("PTPU_REPLICA_ID", "r1")   # "launched on host 1"
    launch_mod._spawn_target(probe, (), rank=0, nprocs=4, backend=None)
    assert seen["rid"] == "r1.0"


def test_advertised_url_handles_wildcard_binds():
    """A 0.0.0.0/:: bind is unroutable as written — the registration
    must advertise the hostname; an explicit (incl. loopback) bind is
    advertised as bound, which is the truth about its reachability."""
    class _Srv:
        def __init__(self, host, port=1234):
            self.host, self.port = host, port
            self.url = f"http://{host}:{port}"

    hn = socket.gethostname()
    assert fleet.advertised_url(_Srv("0.0.0.0")) == f"http://{hn}:1234"
    assert fleet.advertised_url(_Srv("::")) == f"http://{hn}:1234"
    assert fleet.advertised_url(_Srv("127.0.0.1")) == \
        "http://127.0.0.1:1234"
    assert fleet.advertised_url(_Srv("10.1.2.3")) == "http://10.1.2.3:1234"


def test_split_addr_rejects_garbage():
    with pytest.raises(ValueError):
        fleet._split_addr("no-port")
    assert fleet._split_addr("127.0.0.1:8711") == ("127.0.0.1", 8711)


# ---------------------------------------------------------------------------
# rollup state machine (fake scraper — no sockets, no subprocesses)
# ---------------------------------------------------------------------------

class _FakeFleet:
    """Two scripted replicas behind an injectable fetch()."""

    def __init__(self):
        self.metrics = {
            "r0": "# TYPE serving_decode_tokens counter\n"
                  "serving_decode_tokens 5\n"
                  "# TYPE serving_queue_depth gauge\n"
                  "serving_queue_depth 2\n"
                  "# TYPE serving_goodput_tokens_per_s gauge\n"
                  "serving_goodput_tokens_per_s 42.5\n"
                  "# TYPE serving_padding_waste gauge\n"
                  'serving_padding_waste{kind="rows"} 0.375\n'
                  "# TYPE serving_kernels_per_step gauge\n"
                  "serving_kernels_per_step 2\n"
                  "# TYPE train_step_time gauge\n"
                  "train_step_time 0.25\n"
                  "# TYPE train_goodput_examples_per_s gauge\n"
                  "train_goodput_examples_per_s 64\n"
                  "# TYPE train_data_wait_frac gauge\n"
                  "train_data_wait_frac 0.125\n"
                  "# TYPE serving_spec_accept_rate gauge\n"
                  "serving_spec_accept_rate 0.75\n"
                  "# TYPE serving_prefix_hit_tokens counter\n"
                  "serving_prefix_hit_tokens 96\n",
            "r1": "# TYPE serving_decode_tokens counter\n"
                  "serving_decode_tokens 7\n",
        }
        self.healthz = {
            "r0": {"last_activity_age_s": 0.1, "host": "hA", "pid": 11,
                   "rss_bytes": 123456, "open_fds": 17,
                   "uptime_s": 9.5},
            "r1": {"last_activity_age_s": 0.2, "host": "hB", "pid": 22},
        }
        self.down = set()
        self.fetches = []

    def endpoints(self):
        return [{"name": "r0", "url": "http://fake-r0"},
                {"name": "r1", "url": "http://fake-r1"}]

    def fetch(self, url):
        self.fetches.append(url)
        name = "r0" if "fake-r0" in url else "r1"
        if name in self.down:
            raise ConnectionError("injected: replica gone")
        if url.endswith("/metrics"):
            return self.metrics[name]
        if url.endswith("/healthz"):
            return json.dumps(self.healthz[name])
        if url.endswith("/flight/latest"):
            return json.dumps({"reason": "stall", "pid": 11,
                               "ring": []})
        raise ValueError(url)


@pytest.fixture()
def fake():
    return _FakeFleet()


def _agg(fake, tmp_path, **kw):
    kw.setdefault("stall_after_s", 1.0)
    kw.setdefault("down_after", 2)
    return fleet.FleetAggregator(
        endpoints=fake.endpoints(), store=None,
        harvest_dir=str(tmp_path), fetch=fake.fetch, **kw)


def test_rollup_healthy_fleet_merges_counters(fake, tmp_path):
    agg = _agg(fake, tmp_path)
    states = agg.poll_once()
    assert states == {"r0": "healthy", "r1": "healthy"}
    txt = agg.registry.export_prometheus()
    assert "serving_decode_tokens 12" in txt          # exact sum
    assert 'serving_decode_tokens{replica="r0"} 5' in txt
    assert 'serving_decode_tokens{replica="r1"} 7' in txt
    assert 'serving_queue_depth{replica="r0"} 2' in txt
    assert 'fleet_replicas{state="healthy"} 2' in txt
    assert 'fleet_replicas{state="down"} 0' in txt
    assert 'fleet_scrape_age_s{replica="r0"} 0' in txt
    hz = agg.healthz()
    assert hz["status"] == "ok" and hz["counts"]["healthy"] == 2


def test_rollup_stall_transition_harvests_once(fake, tmp_path):
    agg = _agg(fake, tmp_path)
    agg.poll_once()
    fake.healthz["r0"]["last_activity_age_s"] = 9.9   # > stall_after_s
    states = agg.poll_once()
    assert states["r0"] == "stalled" and states["r1"] == "healthy"
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 1 and files[0].startswith("harvest_r0_stalled")
    assert json.load(open(tmp_path / files[0]))["reason"] == "stall"
    # still stalled: no duplicate harvest; recovery re-arms
    agg.poll_once()
    assert sorted(os.listdir(tmp_path)) == files
    fake.healthz["r0"]["last_activity_age_s"] = 0.1
    assert agg.poll_once()["r0"] == "healthy"
    fake.healthz["r0"]["last_activity_age_s"] = 9.9
    agg.poll_once()
    assert len(os.listdir(tmp_path)) == 2   # NEW stall → new harvest
    snap = agg.snapshot()
    assert len(snap["r0"]["harvested"]) == 2


def test_wedged_scrape_is_bounded_and_not_restacked(fake, tmp_path):
    """ISSUE 14: a black-holed endpoint (wedged resolver — urllib's
    timeout does not bound DNS) must cost ONE bounded cycle budget and
    ONE pool worker total, not hang poll_once or leak a worker per
    cycle until the 16-slot pool is exhausted."""
    import threading as _threading
    gate = _threading.Event()
    wedged_entries = []
    real_fetch = fake.fetch

    def fetch(url):
        if "fake-r1" in url:
            wedged_entries.append(url)
            gate.wait(30)
            raise ConnectionError("unwedged late")
        return real_fetch(url)

    agg = fleet.FleetAggregator(
        endpoints=fake.endpoints(), store=None,
        harvest_dir=str(tmp_path), fetch=fetch,
        scrape_timeout=0.05, stall_after_s=1.0, down_after=99)
    t0 = time.monotonic()
    s1 = agg.poll_once()
    s2 = agg.poll_once()
    wall = time.monotonic() - t0
    assert wall < 10.0, f"poll cycles not bounded: {wall:.1f}s"
    # the healthy replica keeps scraping while r1 is wedged
    assert s1["r0"] == "healthy" and s2["r0"] == "healthy"
    # ONE worker total on the black hole — cycle 2 did not stack
    assert len(wedged_entries) == 1
    snap = agg.snapshot()
    assert "wedged" in (snap["r1"]["last_err"] or "")
    # unwedge: the orphaned worker finishes, the next cycle resubmits
    gate.set()
    time.sleep(0.2)
    agg.poll_once()
    assert len(wedged_entries) == 2


def test_rollup_down_after_failure_streak(fake, tmp_path):
    agg = _agg(fake, tmp_path)
    agg.poll_once()
    fake.down.add("r1")
    assert agg.poll_once()["r1"] == "healthy"   # one failure: not yet
    assert agg.poll_once()["r1"] == "down"      # streak hits down_after
    snap = agg.snapshot()
    assert snap["r1"]["fail_streak"] == 2
    assert snap["r1"]["scrape_errors"] == 2
    assert "injected" in snap["r1"]["last_err"]
    # the harvest ATTEMPT happened (endpoint dead → recorded, not raised)
    assert any(u.endswith("/flight/latest") and "fake-r1" in u
               for u in fake.fetches)
    assert not any(f.startswith("harvest_r1") for f
                   in os.listdir(tmp_path))
    hz = agg.healthz()
    assert hz["status"] == "degraded" and hz["counts"]["down"] == 1
    txt = agg.registry.export_prometheus()
    assert 'fleet_scrape_errors{replica="r1"} 2' in txt
    # recovery: the endpoint answering again clears the streak
    fake.down.discard("r1")
    assert agg.poll_once()["r1"] == "healthy"
    assert agg.snapshot()["r1"]["fail_streak"] == 0


def test_snapshot_is_the_router_feed(fake, tmp_path):
    agg = _agg(fake, tmp_path)
    agg.poll_once()
    fake.metrics["r0"] = fake.metrics["r0"].replace(
        "serving_decode_tokens 5", "serving_decode_tokens 25")
    agg.poll_once()
    snap = agg.snapshot()
    assert snap["r0"]["queue_depth"] == 2.0
    assert snap["r0"]["host"] == "hA" and snap["r0"]["pid"] == 11
    assert snap["r0"]["decode_tokens_per_s"] > 0   # 20 tokens / cycle dt
    assert snap["r0"]["state"] == "healthy"
    assert snap["r0"]["last_activity_age_s"] == 0.1
    assert snap["r1"]["decode_tokens_per_s"] == 0.0
    # ISSUE 12: goodput/padding/launch + process-identity signals ride
    # the router feed; a replica predating them reads None, never KeyError
    assert snap["r0"]["goodput_tokens_per_s"] == 42.5
    assert snap["r0"]["padding_waste_rows"] == 0.375
    assert snap["r0"]["kernels_per_step"] == 2.0
    assert snap["r0"]["rss_bytes"] == 123456
    assert snap["r0"]["open_fds"] == 17 and snap["r0"]["uptime_s"] == 9.5
    # ISSUE 13: the training keys ride the same feed (straggler_skew is
    # None here — r1 publishes no step time, so there is no fleet median
    # to ratio against; the rollup itself is pinned in test_train_stats)
    assert snap["r0"]["step_time"] == 0.25
    assert snap["r0"]["goodput_examples_per_s"] == 64.0
    assert snap["r0"]["data_wait_frac"] == 0.125
    # ISSUE 15: spec-accept + prefix-cache heat ride the feed too
    assert snap["r0"]["spec_accept_rate"] == 0.75
    assert snap["r0"]["prefix_hit_tokens"] == 96.0
    for k in ("goodput_tokens_per_s", "padding_waste_rows",
              "kernels_per_step", "rss_bytes", "open_fds",
              "step_time", "goodput_examples_per_s", "data_wait_frac",
              "straggler_skew", "spec_accept_rate", "prefix_hit_tokens"):
        assert snap["r1"][k] is None, (k, snap["r1"][k])


def test_unmergeable_replica_does_not_stall_fleet_view(fake, tmp_path):
    """A version-skewed replica whose histogram buckets can't merge must
    not keep the WHOLE fleet registry stale: the others still merge and
    the failure is exported as fleet/merge_errors + last_err."""
    fake.metrics["r0"] += ("# TYPE t_h histogram\n"
                           't_h_bucket{le="0.1"} 1\n'
                           't_h_bucket{le="+Inf"} 1\n'
                           "t_h_sum 0.05\nt_h_count 1\n")
    fake.metrics["r1"] += ("# TYPE t_h histogram\n"
                           't_h_bucket{le="0.5"} 1\n'   # different bounds
                           't_h_bucket{le="+Inf"} 1\n'
                           "t_h_sum 0.2\nt_h_count 1\n")
    agg = _agg(fake, tmp_path)
    states = agg.poll_once()
    assert states == {"r0": "healthy", "r1": "healthy"}
    txt = agg.registry.export_prometheus()
    # r0 merged fully (its histogram set the fleet bounds), r1's OTHER
    # metrics still landed, and the merge failure is visible
    assert 'serving_decode_tokens{replica="r1"} 7' in txt
    assert 'fleet_merge_errors{replica="r1"} 1' in txt
    assert "fleet_merge_errors{replica=\"r0\"}" not in txt
    assert "bucket bounds" in agg.snapshot()["r1"]["last_err"]


def test_serve_before_first_poll_is_empty_not_process_metrics(fake,
                                                              tmp_path):
    monitor.counter("t/own_process_metric").inc(5)
    agg = _agg(fake, tmp_path)
    srv = agg.serve(port=0)
    try:
        txt = urllib.request.urlopen(srv.url + "/metrics",
                                     timeout=10).read().decode()
        assert "t_own_process_metric" not in txt   # truthfully empty
        agg.poll_once()
        txt = urllib.request.urlopen(srv.url + "/metrics",
                                     timeout=10).read().decode()
        assert "serving_decode_tokens 12" in txt   # then the real view
    finally:
        agg.stop()


def test_discovery_slot_holes_stop_being_polled(monkeypatch, tmp_path,
                                                fake):
    """A registrant that died between ADD and SET leaves a hole slot;
    the aggregator must give up on it after a few misses instead of
    paying a blocking GET every cycle forever."""
    calls = []

    class _HoleStore:
        def __init__(self, host, port, timeout_s=10.0):
            pass

        def add(self, key, n):
            return 2   # two claimed slots

        def get(self, key, timeout_ms=0):
            calls.append(key)
            if key.endswith("/1"):
                return json.dumps({"name": "r0",
                                   "url": "http://fake-r0"}).encode()
            return None   # slot 2: the permanent hole

        def close(self):
            pass

    monkeypatch.setattr(fleet, "_StoreClient", _HoleStore)
    agg = fleet.FleetAggregator(store="127.0.0.1:1", harvest_dir=str(
        tmp_path), fetch=fake.fetch, stall_after_s=1.0, down_after=2)
    for _ in range(6):
        agg.poll_once()
    hole_polls = [c for c in calls if c.endswith("/2")]
    assert len(hole_polls) == agg._SLOT_GIVE_UP   # gave up, stayed up
    # the resolved slot was fetched ONCE, then served from cache
    assert len([c for c in calls if c.endswith("/1")]) == 1
    assert agg.states() == {"r0": "healthy"}


def test_fleet_server_serves_merged_view(fake, tmp_path):
    agg = _agg(fake, tmp_path)
    agg.poll_once()
    srv = agg.serve(port=0)
    try:
        txt = urllib.request.urlopen(srv.url + "/metrics",
                                     timeout=10).read().decode()
        assert "serving_decode_tokens 12" in txt
        assert 'replica="r1"' in txt
        hz = json.loads(urllib.request.urlopen(
            srv.url + "/fleet/healthz", timeout=10).read())
        assert hz["status"] == "ok"
        assert hz["replicas"]["r0"]["state"] == "healthy"
    finally:
        agg.stop()


# ---------------------------------------------------------------------------
# endpoint surface: /healthz identity + /flight/latest
# ---------------------------------------------------------------------------

def test_healthz_identity_fields(monkeypatch):
    monkeypatch.setenv("PTPU_REPLICA_ID", "r3")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    srv = serve.MonitorServer(port=0)
    try:
        hz = json.loads(urllib.request.urlopen(srv.url + "/healthz",
                                               timeout=10).read())
    finally:
        srv.stop()
    # PR-5 keys stay byte-compatible...
    for key in ("status", "pid", "uptime_s", "last_activity_age_s",
                "monitor_enabled", "trace_enabled"):
        assert key in hz, key
    assert hz["status"] == "ok" and hz["pid"] == os.getpid()
    # ...and the v4 identity rides alongside
    assert hz["schema_version"] == serve.SCHEMA_VERSION
    assert hz["host"] == socket.gethostname()
    assert hz["rank"] == 3 and hz["replica_id"] == "r3"


def test_flight_latest_endpoint(tmp_path, monkeypatch):
    monkeypatch.setenv("PTPU_FLIGHT_DIR", str(tmp_path))
    srv = serve.MonitorServer(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/flight/latest", timeout=10)
        assert ei.value.code == 404
        p1 = flight.dump("first", dir=str(tmp_path))
        p2 = flight.dump("second", dir=str(tmp_path))
        os.utime(p1, (1, 1))   # force a deterministic mtime order
        doc = json.loads(urllib.request.urlopen(
            srv.url + "/flight/latest", timeout=10).read())
        assert doc["reason"] == "second" and doc["pid"] == os.getpid()
        assert flight.latest_dump() == p2
    finally:
        srv.stop()


def test_latest_dump_none_without_dir(monkeypatch):
    monkeypatch.delenv("PTPU_FLIGHT_DIR", raising=False)
    assert flight.latest_dump() is None
    assert flight.latest_dump("/nonexistent/ptpu_nowhere") is None


# ---------------------------------------------------------------------------
# the cross-process acceptance (slow tier: 2 replicas + aggregator)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_smoke_script():
    """ISSUE 11 acceptance end-to-end: merged counters exactly equal the
    per-replica sums, one trace_id spans the rpc caller and a replica's
    spans in the chrome export, and a PTPU_FAULTS-stalled replica is
    rolled up as stalled with its flight dump harvested."""
    script = pathlib.Path(__file__).resolve().parent.parent / \
        "scripts" / "fleet_smoke.py"
    env = dict(os.environ, PTPU_FORCE_PLATFORM="cpu", JAX_PLATFORMS="cpu",
               PTPU_MONITOR="1")
    env.pop("PTPU_FAULTS", None)
    env.pop("PTPU_FLEET_STORE", None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=900)
    tail = proc.stdout[-4000:] + "\n--- stderr ---\n" + proc.stderr[-4000:]
    assert proc.returncode == 0, tail
    assert "FLEET SMOKE OK" in proc.stdout, tail
