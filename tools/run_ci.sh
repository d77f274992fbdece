#!/usr/bin/env bash
# CI entry point (reference capability: Paddle's paddle_build.sh test
# stages + gen_ut_cmakelists.py tier metadata — here: pytest tiers + the
# entry() and multichip gates).  Performance is measured on the chip by
# benchmark/run.py, one cell of BENCHMARK.json at a time.
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
#                             8-device multichip dryrun
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
    ;;
  *)
    echo "usage: $0 {fast|full|lint|chaos|gates}" >&2
    exit 2
    ;;
esac
