#!/usr/bin/env bash
# CI entry point (reference capability: paddle_build.sh test stages +
# tools/gen_ut_cmakelists.py tier metadata — here: pytest tiers + the
# driver-shaped gates).
#
#   tools/run_ci.sh fast    — "not slow" tier on the virtual 8-device CPU mesh
#                             (includes the resilience suite + ptpu_check)
#   tools/run_ci.sh full    — everything incl. subprocess/example suites
#   tools/run_ci.sh lint    — unified static analyzer only (ptpu_check,
#                             all 12 rules: silent-except, metric-hygiene,
#                             host-sync, donation, lock-discipline,
#                             determinism, wall-clock, resource-leak,
#                             blocking-in-handler, recompile-hazard,
#                             wire-compat, env-flag-drift over
#                             paddle_tpu/ tools/ scripts/; JSON artifact
#                             at /tmp/ptpu_check_report.json)
#   tools/run_ci.sh chaos   — the deterministic network-fault schedule
#                             (ISSUE 18): scripts/chaos_smoke.py under a
#                             fixed PTPU_CHAOS_SEED — router + 4 replica
#                             processes through drop/delay/partition/
#                             garble/stall/SIGKILL, asserting no-hang,
#                             token-identity and zero KV leaks
#   tools/run_ci.sh gates   — driver gates: compile-check entry() + the
#                             8-device multichip dryrun + CPU bench smoke
#   tools/run_ci.sh bench-check OLD.json NEW.json — perf regression gate
#   tools/run_ci.sh bench-history [args] — gate the newest BENCH_HISTORY
#                             ledger entries against their trailing median
set -euo pipefail
cd "$(dirname "$0")/.."

export PTPU_FORCE_PLATFORM="${PTPU_FORCE_PLATFORM:-cpu}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

case "${1:-fast}" in
  fast)
    # unified static analyzer, INCREMENTAL (ISSUE 14): rules run only
    # on files changed vs ${PTPU_CHECK_BASE:-HEAD} plus their
    # call-graph closure — the fast lane pays ~2 s of parse+graph for a
    # clean tree and seconds for a working diff, instead of the
    # whole-tree rule wall.  `full` and `lint` keep the whole-tree run
    # (all 12 rules), so nothing lands unanalyzed.
    python -m tools.ptpu_check --changed "${PTPU_CHECK_BASE:-HEAD}" \
      --json-out /tmp/ptpu_check_report.json
    # "not slow" includes tests/test_train_stats.py (ISSUE 13: loss-spike
    # EWMA, goodput math, straggler rollup, forensics — subprocess-free)
    # and the serve_smoke --slo leg (ISSUE 16: deadline request ->
    # reqlog event -> kept trace -> exemplar -> fleet-merged burn rate),
    # which rides the EXISTING test_serving.py smoke subprocess — no
    # second engine-compiling process in the fast lane
    python -m pytest tests/ -m "not slow" -q --ignore=tests/test_examples.py
    # perf-history gate, CPU-smoke lane: the headline bench appends this
    # host's run to BENCH_HISTORY.jsonl, then gates against the trailing
    # median of SAME-host same-backend runs (a host change starts a fresh
    # lane — reported, never failed).  Loose 50% tolerance: the CPU smoke
    # config is tiny and shared-host noisy; it catches cliffs, the real
    # lane in `gates` catches percent-level drift on chip hosts.
    python bench.py
    # router fan-out (ISSUE 17): host-side dispatch throughput over fake
    # in-process replicas — backend-free, so the CPU lane IS the lane;
    # self-asserts sticky routing actually engaged before emitting
    python bench.py --config router_fanout
    python tools/check_bench_regression.py --history BENCH_HISTORY.jsonl \
      --gate-smoke --tolerance 0.50
    ;;
  full)
    python -m tools.ptpu_check --json-out /tmp/ptpu_check_report.json
    # includes the slow tier: tests/test_fleet.py::test_fleet_smoke_script
    # runs scripts/fleet_smoke.py (ISSUE 11 acceptance — 2 engine
    # replicas + aggregator; the fleet fast-tier unit tests ride the
    # "not slow" selection above like every other suite) and
    # tests/test_router.py::test_router_smoke_script runs
    # scripts/router_smoke.py (ISSUE 17 acceptance — router + 4 replica
    # processes: sticky prefix routing, disaggregated prefill/decode
    # handoff, mid-stream SIGKILL failover, all token-identical) and
    # tests/test_chaos.py::test_chaos_smoke_script runs
    # scripts/chaos_smoke.py (ISSUE 18 acceptance — the seeded
    # network-fault schedule, same as the `chaos` lane below) and
    # tests/test_api.py::test_api_smoke_script runs scripts/api_smoke.py
    # (ISSUE 19 acceptance — replica stall behind the API -> 504 inside
    # the deadline, and mid-stream SIGKILL -> failover with the stream
    # finishing token-identical; streams never hang)
    python -m pytest tests/ -q
    ;;
  chaos)
    # seed pinned so the fault schedule's p= rolls replay bit-identically
    # run-to-run (the replay contract itself is unit-pinned in
    # tests/test_chaos.py); override with PTPU_CHAOS_SEED=<n>
    PTPU_CHAOS_SEED="${PTPU_CHAOS_SEED:-7}" JAX_PLATFORMS=cpu \
      python scripts/chaos_smoke.py
    ;;
  lint)
    # whole-tree, all 12 rules (the 5 ISSUE-14 interprocedural rules —
    # resource-leak, blocking-in-handler, recompile-hazard, wire-compat,
    # env-flag-drift — ride the same one-parse-per-file core)
    python -m tools.ptpu_check --json-out /tmp/ptpu_check_report.json
    echo "ptpu_check: JSON artifact at /tmp/ptpu_check_report.json"
    ;;
  gates)
    python - <<'EOF'
import __graft_entry__ as g
fn, args = g.entry()
import jax
print("entry() abstract eval:", jax.eval_shape(fn, *args))
g.dryrun_multichip(8)
print("gates OK")
EOF
    python bench.py
    # ISSUE 12 launch-accounting lane: programs-per-decode-step +
    # padding-waste, self-asserting the 3→5 crossing stays FLAT (lives
    # here, NOT in fast — tier-1 room is scarce at ~790s of 870s)
    python bench.py --config kernel_count
    # ISSUE 15 serving-throughput lanes (same tier-placement logic):
    # cold-vs-hot TTFT for a shared-prefix batch, and steady-state
    # decode-step tokens/s spec-on vs spec-off (min/best-over-steps —
    # whole-generate walls drift >50% on shared hosts)
    python bench.py --config prefix_prefill
    python bench.py --config spec_decode
    # ISSUE 19 API front-door lane: seeded open-loop arrivals at rising
    # QPS through a live ApiServer socket — goodput gates higher-is-
    # better, the *_overhead_* TTFT/TPOT percentiles gate lower-is-better
    python bench.py --config serving_load
    # real-lane history gate: default 7% tolerance, smoke lines skipped
    # (on a chip host the headline is the non-smoke metric and gates;
    # after an outage fallback the smoke line is reported only)
    python tools/check_bench_regression.py --history BENCH_HISTORY.jsonl
    ;;
  bench-check)
    shift
    python tools/check_bench_regression.py "$@"
    ;;
  bench-history)
    shift
    python tools/check_bench_regression.py --history BENCH_HISTORY.jsonl "$@"
    ;;
  *)
    echo "usage: $0 {fast|full|lint|chaos|gates|bench-check OLD NEW|bench-history}" >&2
    exit 2
    ;;
esac
