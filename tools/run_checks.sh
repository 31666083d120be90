#!/usr/bin/env bash
# The repo's check entrypoint: lint gates + analyzer self-checks + the
# shardcheck compiled-program contracts + smoke gates + tier-1 tests.
# Exits nonzero on ANY failure. This is what a PR must pass.
#
#   tools/run_checks.sh            # everything (tests take ~20 min)
#   tools/run_checks.sh --fast     # static checks only (seconds)
#
# Every stage is timed and the run ends with a summary table
# (stage -> pass/fail -> seconds) so the slowest gates stay visible and
# check-time regressions get noticed.

set -uo pipefail
cd "$(dirname "$0")/.."

fail=0
declare -a ST_NAME=() ST_RC=() ST_SEC=()

stage() {
    local name="$1"; shift
    echo "== $name =="
    local t0=$SECONDS
    "$@"
    local rc=$?
    ST_NAME+=("$name"); ST_RC+=("$rc"); ST_SEC+=($((SECONDS - t0)))
    [ "$rc" -ne 0 ] && fail=1
    return 0
}

tier1() {
    rm -f /tmp/_t1.log
    timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
        -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
        | tee /tmp/_t1.log
    local rc=${PIPESTATUS[0]}
    echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
        | tr -cd . | wc -c)
    return "$rc"
}

# the static-analysis layers route through the umbrella CLI
# (tools/analyze.py): per-layer sweep + self-check, unified exit codes
# (1 = findings, 2 = the analyzer itself is broken)
stage "analyze: jaxlint (sweep + self-check)" \
    python tools/analyze.py --layer jaxlint
stage "analyze: lockcheck (sweep + self-check)" \
    python tools/analyze.py --layer lockcheck
stage "analyze: postmortem (self-check)" \
    python tools/analyze.py --layer postmortem
stage "analyze: graphcheck (self-check)" env JAX_PLATFORMS=cpu \
    python tools/analyze.py --layer graphcheck

if [ "${1:-}" != "--fast" ]; then
    # shardcheck FIRST: the compiled-program contracts (reduce-scatter
    # layout, ga-scan anchor, bf16 boundary, fp32 identity, donation)
    # fail in seconds here instead of minutes in the bitwise smokes
    stage "analyze: shardcheck (self-check)" env JAX_PLATFORMS=cpu \
        python tools/analyze.py --layer shardcheck
    stage "shardcheck --contracts"  env JAX_PLATFORMS=cpu \
        python tools/shardcheck.py --contracts

    stage "profiling smoke"  env JAX_PLATFORMS=cpu python tools/profiling_smoke.py
    stage "chaos smoke"      env JAX_PLATFORMS=cpu python tools/chaos_smoke.py
    stage "serve smoke"      env JAX_PLATFORMS=cpu python tools/serve_smoke.py
    stage "lm serve smoke (token-level + shared-prefix + page chaos)" \
        env JAX_PLATFORMS=cpu python tools/lm_serve_smoke.py
    stage "fleet smoke (kill/failover/rolling drain)" env JAX_PLATFORMS=cpu \
        python tools/fleet_smoke.py
    stage "autoscale smoke (ramp/brownout/quarantine)" env JAX_PLATFORMS=cpu \
        python tools/autoscale_smoke.py
    stage "zero1 smoke"      env JAX_PLATFORMS=cpu python tools/zero1_smoke.py
    stage "zero2 smoke"      env JAX_PLATFORMS=cpu python tools/zero2_smoke.py
    stage "lm composition smoke" env JAX_PLATFORMS=cpu \
        python tools/lm_smoke.py
    stage "autotune smoke"   env JAX_PLATFORMS=cpu python tools/autotune_smoke.py
    stage "input smoke (+shuffle resume)" env JAX_PLATFORMS=cpu \
        python tools/input_smoke.py
    stage "elastic smoke (3 phases)" env JAX_PLATFORMS=cpu \
        python tools/elastic_smoke.py
    stage "tier-1 tests"     tier1
fi

echo
echo "== run_checks summary =="
printf '%-40s %-6s %8s\n' "stage" "result" "seconds"
total=0
for i in "${!ST_NAME[@]}"; do
    res=PASS; [ "${ST_RC[$i]}" -ne 0 ] && res=FAIL
    printf '%-40s %-6s %8s\n' "${ST_NAME[$i]}" "$res" "${ST_SEC[$i]}"
    total=$((total + ST_SEC[i]))
done
printf '%-40s %-6s %8s\n' "total" "" "$total"

if [ "$fail" -eq 0 ]; then
    echo "run_checks: ALL CHECKS PASSED"
else
    echo "run_checks: FAILURES (see above)" >&2
fi
exit $fail
