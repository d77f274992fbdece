"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip.  Everything is found by name:

  cell           the entry of `workloads` in BENCHMARK.json
  configuration  the `file` of its `configs` entry
  traffic mix    benchmark/traffic/<traffic>.json; its "kind" names
  runner         benchmark/kinds/<kind>.py, `run(spec) -> dict`
  layer metric   benchmark/layer_metrics/<name up to the first dot>.py,
                 `compute(ctx) -> number or None`

Progress goes to stderr.  The last line of stdout is the result object;
with --trace 0 its metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics.  Without a TPU, or with another number of
chips than the cell names, it exits non-zero and prints nothing on stdout.
"""
import time

T0 = time.perf_counter()       # set-up is counted from here

import argparse                # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory, name):
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(manifest, name):
    """(cell, configuration file's content, traffic file's content)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        sys.exit(f"no workload {name!r} in BENCHMARK.json; it has "
                 f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT, entry["file"]),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def layer_metrics(manifest, cell_name, ctx):
    """Every per-layer metric of the cell that its reader can compute."""
    out = {}
    for metric in manifest["per_layer"]:
        if not applies(metric, cell_name):
            continue
        reader = load_module("layer_metrics", metric["name"].split(".")[0])
        value = reader.compute(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = resolve_cell(manifest, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit("no paddle_tpu package beside benchmark/: run from a "
                 "checkout of the repository")
    sys.path.insert(0, ROOT)
    # the attention gates count which path each program took only under
    # this variable; the runners assert on the counts
    os.environ["PTPU_ATTN_DEBUG"] = "1"

    import jax

    try:
        devices = jax.devices()
        found = f"{len(devices)} {devices[0].platform} device(s)"
    except RuntimeError as e:
        devices, found = [], f"no device ({e})"
    if (not devices or devices[0].platform != "tpu"
            or len(devices) != cell["chips"]):
        sys.exit(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); "
                 f"JAX found {found}")

    from paddle_tpu.jit import enable_compile_cache

    from benchmark.lib import trace as trace_lib
    from benchmark.lib.common import CompileCounter, device_info, log
    from benchmark.lib.peaks import peaks_for

    cache_dir = enable_compile_cache()
    # small programs too: after a cell's first run nothing compiles again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = peaks_for(devices[0].device_kind)
    compiles = CompileCounter()
    log(f"{cell['name']}: kind {traffic['kind']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}, compile cache {cache_dir}")

    out = load_module("kinds", traffic["kind"]).run({
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "t0": T0, "compiles": compiles})
    log(f"checks {out['checks']}; {compiles.report()}")

    result = {"correct": all(out["checks"].values()),
              "attempted": out["attempted"], "failed": out["failed"],
              "device": device_info()}
    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if applies(m, cell["name"]) and m["name"] in out["end_to_end"]}
    else:
        traced = out["traced"]
        events = summary = None
        if traced.get("xplane"):
            events = trace_lib.read_xplane(traced["xplane"])
            summary = trace_lib.summarize(events)
            dump = os.environ.get("BENCH_TRACE_DUMP")
            if dump:                 # for cutting a test fixture by hand
                with open(dump, "w") as f:
                    json.dump(events, f)
        traced["cleanup"]()
        if summary is None:
            sys.exit("the traced slice holds no device operation")
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "peaks": peaks, "chips": len(devices), "events": events,
               "trace": summary, "counters": out["counters"],
               "timings": out["timings"], "end_to_end": out["end_to_end"]}
        result["metrics"] = layer_metrics(manifest, cell["name"], ctx)
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        log(f"traced slice {summary['window_s']:.3f} s, busy per chip "
            f"{summary['busy_s_per_chip']}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
