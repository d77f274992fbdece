"""Bench regression gate (reference capability:
tools/check_op_benchmark_result.py + tools/ci_op_benchmark.sh — relative
regression checks against a prior run, no absolute thresholds).

Pairwise mode — compare two bench artifacts:

    python tools/check_bench_regression.py BENCH_r01.json BENCH_r02.json
    python tools/check_bench_regression.py --ladder OLD_LADDER.json BENCH_LADDER.json

History mode (ISSUE 6) — gate the newest run in the persistent ledger
(`BENCH_HISTORY.jsonl`, appended by every bench.py emit) against the
trailing median of comparable prior runs:

    python tools/check_bench_regression.py --history BENCH_HISTORY.jsonl
    python tools/check_bench_regression.py --history BENCH_HISTORY.jsonl \
        --current BENCH_LADDER.json --gate-smoke --tolerance 0.5

"Comparable" means same metric, same host, same backend, backend alive —
a host or backend change starts a fresh lane and NEVER gates (outage and
hardware churn are not regressions).  Fewer than --min-samples priors in
the lane: reported, passes.  Metrics whose name contains "overhead" are
lower-is-better and gate in the opposite direction (the pairwise mode
skips them for exactly that reason).

Exit 0 = no metric regressed more than --tolerance (default 7%, chosen
above the ~±5% run-to-run variance observed on chip in round 2); exit 1 otherwise.
CPU-smoke lines gate only with --gate-smoke (the fast-CI lane, where the
CPU host IS the lane) — without it they are reported but never gate.
"""
import argparse
import json
import statistics
import sys


def _entries(path):
    """Yield {metric, value, ...} dicts from either artifact shape:
    driver BENCH_r*.json ({"parsed": {...}}) or BENCH_LADDER.json lists."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        doc = [doc.get("parsed", doc)]
    for entry in doc:
        if entry and "metric" in entry:
            yield entry


def _ledger_entries(path):
    """Yield ledger records from a BENCH_HISTORY.jsonl file, skipping
    truncated/corrupt lines (a killed bench can leave a partial tail)."""
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                yield rec


def _is_smoke(rec):
    name = rec.get("metric", "")
    return bool(rec.get("cpu_smoke")) or "smoke" in name \
        or "skipped_cpu" in name


def _usable(rec):
    return ("error" not in rec and rec.get("value", 0) > 0
            and not rec.get("backend_unavailable"))


def _age_hours(rec):
    """Hours since the record's ledger timestamp; None when untagged
    (bench artifacts and hand-built test ledgers carry no ts → treated
    as fresh)."""
    ts = rec.get("ts")
    if not ts:
        return None
    import datetime

    try:
        then = datetime.datetime.fromisoformat(ts)
    except ValueError:
        return None
    if then.tzinfo is None:
        # naive ISO stamp (other tooling / hand-built ledgers): assume
        # UTC — bench.py's own stamps always carry an offset
        then = then.replace(tzinfo=datetime.timezone.utc)
    now = datetime.datetime.now(datetime.timezone.utc)
    return (now - then).total_seconds() / 3600.0


def check_history(args):
    history = list(_ledger_entries(args.history))
    if not history:
        print(f"history gate: {args.history} is empty — nothing to gate")
        return 0

    if args.current:
        current = [e for e in _entries(args.current) if _usable(e)]
        prior = history
        # bench artifacts (BENCH_LADDER.json / BENCH_r*.json) carry no
        # host/backend tags, but bench.py ledgers every emit — so the
        # artifact's run IS the newest ledger entry for its metric;
        # inherit that entry's lane tags
        newest = {}
        for rec in history:
            newest[rec["metric"]] = rec
        for e in current:
            src = newest.get(e["metric"])
            if src is not None:
                e.setdefault("host", src.get("host"))
                e.setdefault("backend", src.get("backend"))
                e.setdefault("cpu_smoke", src.get("cpu_smoke"))
                # the artifact's run is that newest ledger entry: keep it
                # out of its own comparison lane
                e["_self"] = src
    else:
        # newest ledger entry per metric is "the current run"; everything
        # before it is history
        last_idx = {}
        for i, rec in enumerate(history):
            last_idx[rec["metric"]] = i
        current = [history[i] for i in sorted(last_idx.values())
                   if _usable(history[i])]
        prior = [rec for i, rec in enumerate(history)
                 if i < last_idx.get(rec["metric"], len(history))]

    failures = []
    for cur in current:
        name = cur["metric"]
        age_h = _age_hours(cur)
        if age_h is not None and age_h > args.max_age_hours:
            # the newest ledger entry for this metric was NOT produced by
            # the invocation being gated (a metric last benched days ago
            # must not fail today's unrelated CI run forever)
            print(f"stale {name}: newest run is {age_h:.1f}h old "
                  f"(> {args.max_age_hours:g}h) — not this invocation, "
                  "skipped")
            continue
        if _is_smoke(cur) and not args.gate_smoke:
            print(f"skip {name}: cpu-smoke lane (pass --gate-smoke to "
                  "gate it)")
            continue
        lane = [p for p in prior
                if p["metric"] == name and _usable(p)
                and p is not cur.get("_self")
                and p.get("host") == cur.get("host")
                and p.get("backend") == cur.get("backend")]
        if len(lane) < args.min_samples:
            print(f"new  {name}: {len(lane)} comparable prior run(s) "
                  f"(< {args.min_samples}) — lane too young to gate")
            continue
        window = [p["value"] for p in lane[-args.window:]]
        med = statistics.median(window)
        ratio = cur["value"] / med
        lower_is_better = "overhead" in name
        if lower_is_better:
            bad = ratio > 1.0 + args.tolerance
            arrow = "<=" if not bad else ">"
        else:
            bad = ratio < 1.0 - args.tolerance
            arrow = ">=" if not bad else "<"
        status = "FAIL" if bad else "ok"
        print(f"{status:4s} {name}: {cur['value']:.2f} vs trailing median "
              f"{med:.2f} over {len(window)} run(s) "
              f"({(ratio - 1) * 100:+.1f}% {arrow} "
              f"{'+' if lower_is_better else '-'}{args.tolerance:.0%})")
        if bad:
            failures.append(name)
    if failures:
        print(f"\n{len(failures)} regression(s) vs trailing median beyond "
              f"{args.tolerance:.0%}: {', '.join(failures)}")
        return 1
    print("\nno regressions vs trailing median")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline", nargs="?",
                    help="pairwise mode: baseline artifact")
    ap.add_argument("current", nargs="?",
                    help="pairwise mode: current artifact; history mode: "
                    "optional current artifact (default: newest ledger "
                    "entry per metric)", metavar="current")
    ap.add_argument("--current", dest="current_opt", metavar="ARTIFACT",
                    help="history mode: explicit current-run artifact")
    ap.add_argument("--ladder", action="store_true",
                    help="compat no-op; both artifact shapes auto-detected")
    ap.add_argument("--history", metavar="LEDGER",
                    help="gate against the trailing median of this "
                    "BENCH_HISTORY.jsonl instead of a pairwise baseline")
    ap.add_argument("--window", type=int, default=5,
                    help="trailing runs in the median (default 5)")
    ap.add_argument("--min-samples", type=int, default=3,
                    help="comparable priors required before a lane gates "
                    "(default 3)")
    ap.add_argument("--gate-smoke", action="store_true",
                    help="gate cpu-smoke lanes too (fast-CI on a CPU host)")
    ap.add_argument("--max-age-hours", type=float, default=6.0,
                    help="history mode: skip metrics whose newest ledger "
                    "entry is older than this — only runs the current "
                    "invocation produced should gate it (default 6)")
    ap.add_argument("--tolerance", type=float, default=0.07,
                    help="allowed fractional drop per metric (default 7%%)")
    args = ap.parse_args(argv)

    if args.history:
        if args.current_opt:
            args.current = args.current_opt
        elif args.baseline and not args.current:
            # `--history L CUR.json` reads naturally; the lone positional
            # lands in `baseline`
            args.current = args.baseline
        return check_history(args)
    if not args.baseline or not args.current:
        ap.error("pairwise mode needs BASELINE and CURRENT artifacts "
                 "(or use --history LEDGER)")

    base = {e["metric"]: e for e in _entries(args.baseline)}
    cur = {e["metric"]: e for e in _entries(args.current)}

    failures = []
    for name, b in sorted(base.items()):
        c = cur.get(name)
        if "error" in b or b.get("value", 0) <= 0:
            continue                    # baseline itself failed: nothing to gate
        if "smoke" in name:
            continue                    # CPU fallback line: outage, not perf
        if "overhead" in name:
            continue                    # lower-is-better: history mode gates it
        if c is None or "error" in c:
            msg = c.get("error", "missing") if c else "missing"
            print(f"FAIL {name}: current run has no number ({msg})")
            failures.append(name)
            continue
        ratio = c["value"] / b["value"]
        status = "ok" if ratio >= 1.0 - args.tolerance else "FAIL"
        print(f"{status:4s} {name}: {b['value']:.2f} -> {c['value']:.2f} "
              f"({(ratio - 1) * 100:+.1f}%)")
        if status == "FAIL":
            failures.append(name)
    for name in sorted(set(cur) - set(base)):
        print(f"new  {name}: {cur[name].get('value', cur[name].get('error'))}")

    if failures:
        print(f"\n{len(failures)} regression(s) beyond "
              f"{args.tolerance:.0%}: {', '.join(failures)}")
        return 1
    print("\nno regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
