"""monitor.hlo + the ISSUE-12 program-microscope surface.

Covers the HLO text parser across dialects (golden fixtures: the jax
0.4.x `%`-sigil form with inline operand types, the newer bare-name
form, a fuzz/garbage line inside a valid module, and outright garbage),
the flops/bytes shape algebra pins, the capture → gauges → hlo_report
path on a LIVE compiled program, the recompile explainer
(`jit._signature_delta` + the `jit/recompile_cause{fn,axis}` counter and
flight-ring breadcrumb), the `/profile` endpoint contract (zip artifact
/ 409 single-flight / 501 unavailable), and the /healthz process-
identity fields (schema v3).  Fast tier, subprocess-free.
"""
import io
import json
import threading
import urllib.error
import urllib.request
import zipfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import flight, hlo, perf, serve


@pytest.fixture(autouse=True)
def _fresh():
    monitor.reset()
    monitor.enable(True)
    perf.reset()
    yield
    perf.enable(False)
    perf.reset()
    perf.refresh()
    monitor.reset()
    monitor.refresh()


# The jax 0.4.x dialect: % sigils, inline operand types, metadata.
# (Captured from compiled.as_text() on this host's jax 0.4.37, trimmed.)
GOLDEN_OLD = """\
HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[8,16]{1,0}, f32[16,4]{1,0})->f32[]}

%region_0.8 (Arg_0.9: f32[], Arg_1.10: f32[]) -> f32[] {
  %Arg_0.9 = f32[] parameter(0), metadata={op_name="jit(f)/jit(main)/reduce_sum"}
  %Arg_1.10 = f32[] parameter(1)
  ROOT %add.11 = f32[] add(f32[] %Arg_0.9, f32[] %Arg_1.10)
}

%fused_computation (param_0.2: f32[8,4]) -> f32[] {
  %param_0.2 = f32[8,4]{1,0} parameter(0)
  %constant.1 = f32[] constant(1)
  %broadcast.0 = f32[8,4]{1,0} broadcast(f32[] %constant.1), dimensions={}
  %add.0 = f32[8,4]{1,0} add(f32[8,4]{1,0} %param_0.2, f32[8,4]{1,0} %broadcast.0), metadata={op_name="jit(f)/jit(main)/add"}
  %constant.0 = f32[] constant(0)
  ROOT %reduce.0 = f32[] reduce(f32[8,4]{1,0} %add.0, f32[] %constant.0), dimensions={0,1}, to_apply=%region_0.8
}

ENTRY %main.13 (Arg_0.1: f32[8,16], Arg_1.2: f32[16,4]) -> f32[] {
  %Arg_0.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="a"}
  %Arg_1.2 = f32[16,4]{1,0} parameter(1), metadata={op_name="b"}
  %dot.6 = f32[8,4]{1,0} dot(f32[8,16]{1,0} %Arg_0.1, f32[16,4]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/dot_general"}
  ROOT %add_reduce_fusion = f32[] fusion(f32[8,4]{1,0} %dot.6), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jit(main)/reduce_sum"}
}
"""

# The newer dialect: no % sigils, bare operand names (no inline types),
# a signature-less ENTRY header — the SAME program, so every estimated
# number must round-trip identically.
GOLDEN_NEW = """\
HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[8,16], f32[16,4])->f32[]}, frontend_attributes={fingerprint_before_lhs="abc"}

region_0.8 (Arg_0.9: f32[], Arg_1.10: f32[]) -> f32[] {
  Arg_0.9 = f32[] parameter(0)
  Arg_1.10 = f32[] parameter(1)
  ROOT add.11 = f32[] add(Arg_0.9, Arg_1.10)
}

fused_computation (param_0.2: f32[8,4]) -> f32[] {
  param_0.2 = f32[8,4]{1,0} parameter(0)
  constant.1 = f32[] constant(1)
  broadcast.0 = f32[8,4]{1,0} broadcast(constant.1), dimensions={}
  add.0 = f32[8,4]{1,0} add(param_0.2, broadcast.0)
  constant.0 = f32[] constant(0)
  ROOT reduce.0 = f32[] reduce(add.0, constant.0), dimensions={0,1}, to_apply=region_0.8
}

ENTRY main.13 {
  Arg_0.1 = f32[8,16]{1,0} parameter(0), metadata={op_name="a"}
  Arg_1.2 = f32[16,4]{1,0} parameter(1)
  dot.6 = f32[8,4]{1,0} dot(Arg_0.1, Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/dot_general"}
  ROOT add_reduce_fusion = f32[] fusion(dot.6), kind=kLoop, calls=fused_computation
}
"""

# expected numbers for BOTH goldens (one program, two dialects):
#   dot: 2 * |8x4| * K=16              = 1024 flops
#        bytes 8*16*4 + 16*4*4 + 8*4*4 = 896
#   fusion: add |8x4|=32 + reduce |8x4|=32 = 64 flops
#           bytes (boundary) 8*4*4 + 4     = 132
_DOT = dict(flops=1024.0, bytes=896.0)
_FUSION = dict(flops=64.0, bytes=132.0)


class TestParser:
    @pytest.mark.parametrize("text", [GOLDEN_OLD, GOLDEN_NEW],
                             ids=["jax04x", "newer"])
    def test_golden_dialects_same_numbers(self, text):
        res = hlo.analyze(text)
        assert res["available"]
        assert res["module"] == "jit_f"
        assert res["ops"] == 2 and res["fusions"] == 1
        assert res["computations"] == 3
        rows = {r["name"]: r for r in res["table"]}
        assert rows["dot.6"]["flops"] == _DOT["flops"]
        assert rows["dot.6"]["bytes"] == _DOT["bytes"]
        assert rows["dot.6"]["estimated"]
        assert rows["add_reduce_fusion"]["opcode"] == "fusion"
        assert rows["add_reduce_fusion"]["flops"] == _FUSION["flops"]
        assert rows["add_reduce_fusion"]["bytes"] == _FUSION["bytes"]
        assert res["flops"] == _DOT["flops"] + _FUSION["flops"]
        # op_name metadata survives where present (the human label)
        assert rows["dot.6"]["op_name"].endswith("dot_general")

    def test_fuzz_line_inside_valid_module_is_skipped(self):
        # forward compat: one line of an unknown future syntax inside a
        # recognized module must not kill the whole analysis
        fuzzed = GOLDEN_NEW.replace(
            "  dot.6 = ",
            "  !!some @future [syntax] 100%% garbage\n  dot.6 = ")
        res = hlo.analyze(fuzzed)
        assert res["available"] and res["ops"] == 2
        assert res["flops"] == _DOT["flops"] + _FUSION["flops"]

    def test_garbage_raises_and_capture_degrades(self):
        with pytest.raises(hlo.HloParseError):
            hlo.parse_hlo("not HLO at all\x00\xff")
        with pytest.raises(hlo.HloParseError):
            # module header but no ENTRY — MLIR-ish / truncated text
            hlo.parse_hlo("HloModule jit_x\nfunc.func @main() {}\n")
        # capture NEVER raises: unavailable record + counted error
        rec = hlo.capture("deg:garbage", "totally not hlo")
        assert rec["available"] is False
        assert hlo.get("deg:garbage")["available"] is False
        rep = hlo.report("deg:garbage")
        assert "unavailable" in rep
        snap = monitor.snapshot()
        errs = snap.get("perf/capture_errors") or {}
        assert any("hlo_parse" in k for k in errs), errs
        # no gauges for an unavailable program
        assert "fn=deg:garbage" not in (snap.get("perf/hlo_ops") or {})

    def test_bare_module_header_and_cycles_never_raise(self):
        # review round: a bare "HloModule" line (no name) used to escape
        # capture as IndexError, and a cyclic fusion call graph as
        # RecursionError — both must degrade, the never-raises contract
        bare = "HloModule\nENTRY %m (p: f32[2]) -> f32[2] {\n" \
               "  %p = f32[2]{0} parameter(0)\n" \
               "  ROOT %n = f32[2]{0} negate(f32[2]{0} %p)\n}\n"
        res = hlo.analyze(bare)
        assert res["available"] and res["module"] == "<unnamed>"
        cyclic = """\
HloModule jit_cyc

%comp_a (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %fa = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, calls=%comp_b
}

%comp_b (q: f32[2]) -> f32[2] {
  %q = f32[2]{0} parameter(0)
  ROOT %fb = f32[2]{0} fusion(f32[2]{0} %q), kind=kLoop, calls=%comp_a
}

ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2]{0} parameter(0)
  ROOT %f = f32[2]{0} fusion(f32[2]{0} %x), kind=kLoop, calls=%comp_a
}
"""
        res = hlo.analyze(cyclic)       # bails at the cycle, no blowup
        assert res["available"] and res["ops"] == 1
        assert res["table"][0]["estimated"] is False
        # and capture() absorbs even unforeseen parser exceptions
        assert hlo.capture("deg:bare", bare)["available"]

    def test_oversized_text_degrades(self, monkeypatch):
        monkeypatch.setenv("PTPU_HLO_MAX_BYTES", "64")
        rec = hlo.capture("deg:huge", GOLDEN_OLD)
        assert rec["available"] is False and "MAX_BYTES" in rec["error"]

    def test_dtype_bytes_and_tuple_shapes(self):
        text = """\
HloModule jit_t, entry_computation_layout={()->(bf16[4,8], s8[16])}

ENTRY %main (p0: bf16[4,8], p1: s8[16]) -> (bf16[4,8], s8[16]) {
  %p0 = bf16[4,8]{1,0} parameter(0)
  %p1 = s8[16]{0} parameter(1)
  %neg = bf16[4,8]{1,0} negate(bf16[4,8]{1,0} %p0)
  %dus = s8[16]{0} dynamic-update-slice(s8[16]{0} %p1, s8[16]{0} %p1, s8[16]{0} %p1)
  ROOT %t = (bf16[4,8]{1,0}, s8[16]{0}) tuple(bf16[4,8]{1,0} %neg, s8[16]{0} %dus)
}
"""
        res = hlo.analyze(text)
        rows = {r["name"]: r for r in res["table"]}
        # negate: 32 elems; bf16 = 2 B/elem, operand + result
        assert rows["neg"]["flops"] == 32.0
        assert rows["neg"]["bytes"] == 64.0 + 64.0
        # dynamic-update-slice: data movement, zero flops, bytes counted
        assert rows["dus"]["flops"] == 0.0
        assert rows["dus"]["bytes"] == 16 * 4  # 3 operands + result, 1B
        # tuple is plumbing: not in the ops table
        assert "t" not in rows and res["ops"] == 2

    def test_unknown_cost_opcodes_flagged_not_invented(self):
        text = """\
HloModule jit_c, entry_computation_layout={(f32[8])->f32[8]}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %cc = f32[8]{0} custom-call(f32[8]{0} %p0), custom_call_target="do_magic"
}
"""
        res = hlo.analyze(text)
        row = res["table"][0]
        assert row["opcode"] == "custom-call"
        assert row["flops"] == 0.0 and row["estimated"] is False
        # the report marks the unknowable row instead of claiming zero
        hlo.capture("deg:cc", text)
        assert "?" in hlo.report("deg:cc")


class TestLiveCapture:
    def test_capture_exports_gauges_and_report(self):
        hlo.capture("live:golden", GOLDEN_OLD)
        snap = monitor.snapshot()
        assert snap["perf/hlo_ops"]["fn=live:golden"] == 2.0
        assert snap["perf/fusions"]["fn=live:golden"] == 1.0
        rep = hlo.report("live:golden")
        assert "add_reduce_fusion" in rep and "fusion" in rep
        assert "dot.6" in rep
        # perf.hlo_report resolves labels / callables / None
        assert perf.hlo_report("live:golden") == rep
        assert "live:golden" in perf.hlo_report()
        assert perf.hlo_report("never:captured") == ""

    def test_real_compiled_program_roundtrip(self):
        # the acceptance shape: a jitted program on THIS host — XLA-CPU
        # as_text parses, fusions are named with flops/bytes, and the
        # gauges ride the registry (perf.measure is the same AOT capture
        # path the jit hook and CostModel.profile_measure use)
        import jax.numpy as jnp

        perf.enable(True)

        def step(a, b):
            return (a @ b + 1.0).sum()

        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 4), jnp.float32)
        perf.measure(step, a, b, label="live:step", reps=1)
        an = hlo.get("live:step")
        assert an is not None and an["available"]
        assert an["ops"] >= 2 and an["flops"] >= 1024.0
        rep = perf.hlo_report("live:step")
        assert "hlo[live:step]" in rep and "dot" in rep
        if an["fusions"]:
            assert "fusion" in rep
        # perf.reset clears the microscope store too
        perf.reset()
        assert hlo.get("live:step") is None


class TestRecompileExplainer:
    def test_signature_delta_axes(self):
        from paddle_tpu.jit import _signature_delta as delta

        base = "nstate=2;(4, 32):int32;(4,):float32"
        assert delta(set(), base) is None
        assert delta({base}, base.replace("(4, 32)", "(4, 64)")) == \
            ("dim1", "arg0 dim1: 32→64")
        assert delta({base}, base.replace("(4,):float32",
                                          "(4,):int64")) == \
            ("dtype", "arg1: float32→int64")
        axis, det = delta({base},
                          base.replace("(4, 32):int32", "(8, 64):int32"))
        assert axis == "shape" and "arg0" in det
        axis, det = delta({base}, base + ";(2,):int32")
        assert axis == "nargs"
        axis, det = delta({"nstate=0;'a'"}, "nstate=0;'b'")
        assert axis == "static"
        # closest-match: the cached sig sharing more parts wins the diff
        cached = {base, "nstate=2;(9, 9):int32;(9,):float32"}
        assert delta(cached, base.replace("(4, 32)", "(4, 16)")) == \
            ("dim1", "arg0 dim1: 32→16")

    @staticmethod
    def _my_causes(snap, fn):
        """Nonzero cause series for ONE fn — other suites leave zeroed
        series of other fns registered in the process-global registry."""
        cause = snap.get("jit/recompile_cause") or {}
        return {k: v for k, v in sorted(cause.items())
                if f"fn={fn}" in k and v > 0}

    def test_compiled_function_names_the_axis(self):
        from paddle_tpu import jit

        flight.get_recorder().clear()

        def microscope_step(x):
            return x.sum()

        c = jit.compile(microscope_step, train=False)
        c(paddle.to_tensor(np.ones((4, 8), np.float32)))
        # first compile: a compile, not a RE-compile — nothing to explain
        assert self._my_causes(monitor.snapshot(),
                               "microscope_step") == {}
        c(paddle.to_tensor(np.ones((4, 16), np.float32)))
        mine = self._my_causes(monitor.snapshot(), "microscope_step")
        assert mine == {"axis=dim1,fn=microscope_step": 1.0}, mine
        # the breadcrumb is in the flight ring for post-mortem dumps
        notes = [r for r in flight.get_recorder().records()
                 if r.get("kind") == "note"
                 and r.get("event") == "jit/recompile"]
        assert notes and notes[-1]["axis"] == "dim1"
        assert "32→64" in notes[-1]["detail"] or \
            "8→16" in notes[-1]["detail"], notes[-1]

    def test_same_signature_never_explains(self):
        from paddle_tpu import jit

        def steady_step(x):
            return x * 2

        c = jit.compile(steady_step, train=False)
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        for _ in range(3):
            c(x)
        # reset() keeps previously registered (zeroed) series: absence
        # of INCREMENTS for THIS fn is the invariant
        assert self._my_causes(monitor.snapshot(), "steady_step") == {}


class TestProfileEndpoint:
    @pytest.fixture()
    def server(self):
        srv = serve.MonitorServer(0)
        yield srv
        srv.stop()

    def test_profile_returns_loadable_zip_or_clean_501(self, server):
        # the acceptance contract on any host: a perfetto-loadable zip,
        # or an honest 501 where this backend has no profiler
        try:
            body = urllib.request.urlopen(
                server.url + "/profile?secs=0.1", timeout=60).read()
        except urllib.error.HTTPError as e:
            assert e.code == 501, e.code
            assert "error" in json.loads(e.read())
            return
        z = zipfile.ZipFile(io.BytesIO(body))
        assert z.namelist(), "empty profile artifact"
        assert z.testzip() is None

    def test_single_flight_409(self, server, monkeypatch):
        started = threading.Event()
        release = threading.Event()

        def slow_capture(secs):
            started.set()
            release.wait(10)
            return b"PK\x05\x06" + b"\x00" * 18   # empty-but-valid zip

        monkeypatch.setattr(serve, "_capture_profile", slow_capture)
        out = {}

        def first():
            out["first"] = urllib.request.urlopen(
                server.url + "/profile?secs=9", timeout=30).read()

        t = threading.Thread(target=first, daemon=True)
        t.start()
        assert started.wait(5)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/profile", timeout=10)
        assert ei.value.code == 409
        assert "in flight" in json.loads(ei.value.read())["error"]
        release.set()
        t.join(10)
        assert out["first"].startswith(b"PK")

    def test_unavailable_501_and_bad_query_400(self, server,
                                               monkeypatch):
        def broken(secs):
            raise serve.ProfilerUnavailable("no profiler here")

        monkeypatch.setattr(serve, "_capture_profile", broken)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/profile", timeout=10)
        assert ei.value.code == 501
        assert "no profiler" in json.loads(ei.value.read())["error"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/profile?secs=banana",
                                   timeout=10)
        assert ei.value.code == 400

    def test_healthz_process_identity_v3(self, server):
        hz = json.loads(urllib.request.urlopen(
            server.url + "/healthz", timeout=10).read())
        assert hz["schema_version"] == 3
        # prior keys stay byte-compatible
        for k in ("status", "pid", "uptime_s", "last_activity_age_s",
                  "monitor_enabled", "trace_enabled", "host"):
            assert k in hz, k
        # the v3 identity gauges (linux /proc on this host)
        assert hz["rss_bytes"] > 0
        assert hz["open_fds"] > 0
