"""Nothing on the chip path may hide the device (ISSUE 21): the compile
cache is placed from outside, interpret mode is a CPU-only switch, a dead
backend raises, unknown chips have no peaks, `set_device("tpu")` needs a
TPU, `chip_smoke.py` refuses to run without one, and a Pallas call on an
installed mesh runs per shard.  Fast tier: fakes, two short subprocesses
that compile nothing, and one interpret-mode flash call.
"""
import os
import shutil
import subprocess
import sys
import time
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_devices(monkeypatch, platform, kind=""):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind, id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


# -- compile cache ----------------------------------------------------------

@pytest.fixture
def cache_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_compile_cache_env_var_wins(monkeypatch, cache_updates, tmp_path):
    from paddle_tpu.jit import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert cache_updates == []          # JAX reads the variable itself


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       cache_updates):
    from paddle_tpu.jit import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want     # fixed: no pid, no tempdir
    assert cache_updates == [("jax_compilation_cache_dir", want)] * 2


# -- kernel gates -----------------------------------------------------------

def test_interpret_is_a_cpu_only_switch(monkeypatch):
    from paddle_tpu.ops import pallas_ops

    monkeypatch.delenv("PTPU_PALLAS_INTERPRET", raising=False)
    assert pallas_ops._interpret() is False
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    assert pallas_ops._interpret() is True          # the suite's platform
    _fake_devices(monkeypatch, "tpu")
    with pytest.raises(RuntimeError, match="'tpu'"):
        pallas_ops._interpret()


def test_on_tpu_propagates_backend_failure(monkeypatch):
    from paddle_tpu.ops import pallas_ops

    def dead(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas_ops._on_tpu()


def test_flash_kernel_runs_per_shard_on_an_installed_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic call: on an init_mesh() mesh the
    kernel goes through shard_map (batch over dp, heads over mp), matches
    the reference forward and backward, and refuses shapes that do not
    divide; with no mesh installed the call is direct whatever the host's
    device count."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu import parallel
    from paddle_tpu.ops import pallas_ops as po
    from paddle_tpu.parallel import mesh as mesh_mod

    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 128, 4, 64), jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    def flash(q, k, v):
        return po.flash_attention_arrays(q, k, v, is_causal=True)

    def ref(q, k, v):
        return po.mha_reference(q, k, v, None, True)

    prev = mesh_mod._current()
    try:
        mesh_mod._state.mesh = None
        parallel.get_mesh()             # the default mesh is not "installed"
        direct = jax.make_jaxpr(flash)(q[:1], k[:1], v[:1])
        assert "shard_map" not in str(direct)

        mesh = parallel.init_mesh(dp=2, mp=2)
        spec = NamedSharding(mesh, P("dp", None, "mp", None))
        qs, ks, vs = (jax.device_put(a, spec) for a in (q, k, v))
        assert "shard_map" in str(jax.make_jaxpr(flash)(qs, ks, vs))
        out = jax.jit(flash)(qs, ks, vs)
        assert len(out.sharding.device_set) == 4
        np.testing.assert_allclose(out, ref(q, k, v), rtol=1e-5, atol=1e-5)
        got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(qs, ks, vs)
        want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
        with pytest.raises(ValueError, match="must divide"):
            jax.jit(flash)(q[:3], k[:3], v[:3])
    finally:
        mesh_mod._state.mesh = prev


# -- peaks and placement ----------------------------------------------------

def test_chip_spec_refuses_unknown_tpu(monkeypatch):
    from paddle_tpu.monitor import perf

    try:
        with monkeypatch.context() as m:
            _fake_devices(m, "tpu", "TPU v5 lite")
            assert perf.chip_spec(refresh_probe=True).name == "tpu-v5e"
            _fake_devices(m, "tpu", "TPU v99 mystery")
            with pytest.raises(ValueError, match="v99 mystery"):
                perf.chip_spec(refresh_probe=True)
    finally:
        perf.chip_spec(refresh_probe=True)     # re-probe the real backend


def test_set_device_tpu_needs_an_accelerator():
    import paddle_tpu as paddle
    from paddle_tpu import device

    before = device._current
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("tpu")
    assert device._current is before
    try:
        assert paddle.set_device("cpu").platform == "cpu"
    finally:
        device._current = before
        jax.config.update("jax_default_device", None)


# -- entry points -----------------------------------------------------------

def test_bench_ladder_exits_nonzero_on_a_failed_config(monkeypatch,
                                                        tmp_path, capsys):
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)

    def fake_run(cmd, **kw):
        ok = cmd[-1] == "good"
        return types.SimpleNamespace(
            returncode=0 if ok else 1, stderr="boom",
            stdout='{"metric": "good", "value": 1.0}\n' if ok else "")

    monkeypatch.setattr(bench, "LADDER", {"good": None, "bad": None})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--ladder"])
    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "bad" in str(exc.value.code)
    assert '"error": "rc=1"' in capsys.readouterr().out


def _smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.monotonic() - t0


def test_chip_smoke_fails_fast_without_a_tpu():
    proc, took = _smoke(ROOT, os.path.join(ROOT, "chip_smoke.py"))
    assert proc.returncode != 0
    assert proc.stdout == ""                  # no summary line, no result
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert took < 60, f"took {took:.0f}s — it must not reach a compile"


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc, _ = _smoke(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "paddle_tpu" in proc.stderr


@pytest.mark.parametrize("serve_ok", [True, False])
def test_chip_smoke_last_stdout_line_is_the_verdict(monkeypatch, capsys,
                                                    serve_ok):
    """The accelerator check parses the last stdout line and accepts
    exactly {"ok", "device": {"platform", "kind", "count"}}; the per-leg
    report is the line before it."""
    import json

    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def fake_leg(leg, args, deadline):
        return {"leg": leg, "ok": serve_ok or leg == "train", "rc": 0,
                "wall_s": 1.0, "device": dict(device),
                "versions": {"jax": "0.9.0"}, "cache_dir": "/x",
                "attention_paths": {"attn_kernel": 1}}

    monkeypatch.setattr(chip_smoke, "_run_leg", fake_leg)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code == (0 if serve_ok else 1)
    report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": serve_ok, "device": device}
    # a failed leg ends the run: the families leg follows a good serve leg
    assert set(report["legs"]) == {"train", "serve"} | (
        {"families"} if serve_ok else set())
    assert report["versions"] == {"jax": "0.9.0"}
