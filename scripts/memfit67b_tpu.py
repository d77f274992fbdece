"""6.7B hybrid capacity check against the REAL TPU compiler (VERDICT r3
item 4): AOT-compile the full-shape GPT-3 6.7B hybrid training step for
an 8-device v5e topology and report XLA:TPU's per-device memory analysis
as one JSON line — no 8 physical chips needed (the XLA-CPU pass trips an
internal check at these shapes; the TPU target is the real question
anyway).

Needs only the TPU compiler (local libtpu), no attached chip: the target
is an explicit v5e 2x4 topology description.
  python scripts/memfit67b_tpu.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("PTPU_SCAN_UNROLL", "1")  # rolled layer scan


def main():
    if os.environ.get("PTPU_FORCE_PLATFORM") == "cpu":
        # this script is only meaningful against the real TPU compiler
        print(json.dumps({"metric": "gpt3_6p7b_hybrid8_hbm_headroom",
                          "error": "cpu-pinned environment"}))
        return 1
    import numpy as np
    import jax
    import jax.numpy as jnp

    topo = None
    err = {}
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x4")
    except Exception as e:  # no local libtpu topology support
        err["v5e_2x4"] = str(e)[:200]
    if topo is None:
        print(json.dumps({"metric": "gpt3_6p7b_hybrid8_hbm_headroom",
                          "error": "no TPU topology available",
                          "detail": err}))
        return 1

    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt3_6p7b_config)
    from paddle_tpu.core.dtype import convert_dtype
    from paddle_tpu.nn import initializer as _init

    # zero-init EVERY initializer as HOST (cpu-device) arrays: 6.7B of
    # on-chip zeros (plus Adam moments at _ensure_state) would
    # RESOURCE_EXHAUST the single attached 16 GiB chip before the AOT
    # lower ever runs
    cpu0 = jax.devices("cpu")[0]
    for _cls in vars(_init).values():
        if isinstance(_cls, type) and issubclass(_cls, _init.Initializer):
            _cls.__call__ = lambda self, shape, dtype: jax.device_put(
                np.zeros(shape, convert_dtype(dtype)), cpu0)
    paddle.set_default_dtype("bfloat16")
    cfg = gpt3_6p7b_config(stacked_blocks=True, pp_num_microbatches=4,
                           recompute=True)
    from jax.sharding import Mesh

    devs = np.array(topo.devices).reshape(1, 2, 2, 1, 1, 2)
    mesh = Mesh(devs, ("dp", "sharding", "pp", "ep", "sp", "mp"))
    parallel.set_mesh(mesh)

    model = parallel.place_model(GPTForCausalLM(cfg))
    crit = GPTPretrainingCriterion(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=False)

    def step(x, y):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    batch, seq = 8, 2048
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    lab = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    print("lowering + compiling for v5e:2x4...", file=sys.stderr, flush=True)
    mem = compiled.memory_analysis(ids, lab)
    per_dev_gb = mem["peak_bytes_estimate"] / 2**30
    hbm_gb = 16.0
    print(json.dumps({
        "metric": "gpt3_6p7b_hybrid8_hbm_headroom",
        "value": round(hbm_gb / max(per_dev_gb, 1e-9), 4),
        "unit": "x (16GiB/use)",
        "vs_baseline": round(hbm_gb / max(per_dev_gb, 1e-9), 4),
        "per_device_gb": round(per_dev_gb, 3),
    }))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # always emit a parseable line
        print(json.dumps({"metric": "gpt3_6p7b_hybrid8_hbm_headroom",
                          "error": type(e).__name__,
                          "detail": str(e)[:300]}))
        sys.exit(1)
