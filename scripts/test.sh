#!/bin/bash
# CPU test harness: JAX_PLATFORMS=cpu and an 8-device virtual CPU mesh.
#
# Two tiers:
#   scripts/test.sh           full tier — everything except @slow (the
#                             judged configuration; includes the @heavy
#                             golden-trajectory/e2e/subprocess tests)
#   scripts/test.sh core      core tier — additionally skips @heavy, for
#                             quick iteration; stays green without a warm
#                             compile cache on a 1-core host
# Any other arguments pass through to pytest unchanged.
#
# Duration audit (fault-tolerance PR satellite): every run appends
# --durations, and any single non-slow test over the per-test budget
# (COMMEFFICIENT_DURATION_BUDGET seconds; default 120; 0 disables) fails
# the harness with rc=4 even when pytest itself passed. This is the
# tripwire for the round-3 class of regression where one test
# (test_host_offload, ~20 min) silently ate the whole 870 s tier-1 wall.
cd "$(dirname "$0")/.."
# The compile cache is the persistent per-user one tests/conftest.py
# places (JAX_COMPILATION_CACHE_DIR wins). The per-run throwaway cache
# this script once forced guarded a jax 0.4.37 donation-from-cache bug;
# re-tested on jax 0.9.0 (tests/test_moe.py TestEPRound, donation on,
# warm cache) it is gone.
BUDGET="${COMMEFFICIENT_DURATION_BUDGET:-120}"
if [ "$1" = "core" ]; then
  shift
  set -- tests/ -x -q -m "not slow and not heavy" "$@"
elif [ $# -eq 0 ]; then
  # the judged tier-1 configuration: everything except @slow
  set -- tests/ -x -q -m "not slow"
fi
LOG="${TMPDIR:-/tmp}/commefficient_test_$$.log"
set -o pipefail
env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest "$@" --durations=15 --durations-min=1 2>&1 | tee "$LOG"
rc=$?
if [ "$rc" -eq 0 ] && [ "$BUDGET" != "0" ]; then
  # pytest duration lines look like "  123.45s call  tests/test_x.py::t";
  # only 'call' phases count (setup/teardown share fixtures across tests)
  over=$(awk -v b="$BUDGET" \
    '$2 == "call" { t = $1; sub(/s$/, "", t); if (t + 0 > b) print }' "$LOG")
  if [ -n "$over" ]; then
    echo ""
    echo "DURATION BUDGET EXCEEDED: test(s) over ${BUDGET}s" \
         "(COMMEFFICIENT_DURATION_BUDGET; 0 disables):"
    echo "$over"
    rm -f "$LOG"
    exit 4
  fi
fi
rm -f "$LOG"
exit $rc
