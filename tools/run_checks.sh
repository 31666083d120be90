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

bench_smoke() {
    rm -f /tmp/_bench_smoke.jsonl
    JAX_PLATFORMS=cpu BENCH_SMOKE=1 \
        BENCH_RUNGS=lenet,input,serve,lm,lm_serve,fleet \
        BENCH_AUTOTUNE=1 \
        python bench.py | tee /tmp/_bench_smoke.jsonl || return 1
    # every successful rung record must carry the ISSUE-10 precision
    # fields, the ISSUE-11 comm_bytes_hlo calibration field, and the
    # ISSUE-13 autotune fields; the autotuned lenet rung must land a
    # finite measured-vs-predicted calibration gap
    python - <<'PY'
import json, math
recs = []
for line in open("/tmp/_bench_smoke.jsonl"):
    line = line.strip()
    if line.startswith("{"):
        recs.append(json.loads(line))
# failure/timeout records (_failure_record / _RungWatchdog) carry no
# schema fields by design — only successful rung records must
recs = [r for r in recs if not r.get("failed")]
assert recs, "bench smoke emitted no successful records"
missing = [r.get("metric") for r in recs
           if "compute_dtype" not in r or "params_dtype" not in r]
assert not missing, f"records missing compute_dtype/params_dtype: {missing}"
missing = [r.get("metric") for r in recs if "comm_bytes_hlo" not in r]
assert not missing, f"records missing comm_bytes_hlo: {missing}"
missing = [r.get("metric") for r in recs
           if not {"autotuned", "predicted_step_s",
                   "measured_vs_predicted_gap"} <= set(r)]
assert not missing, f"records missing autotune fields: {missing}"
tuned = [r for r in recs if r.get("autotuned")]
assert tuned, "BENCH_AUTOTUNE=1 but no record ran autotuned"
bad = [r["metric"] for r in tuned
       if not (r.get("predicted_step_s") and r.get(
           "measured_vs_predicted_gap") is not None
           and math.isfinite(r["measured_vs_predicted_gap"]))]
assert not bad, f"autotuned records without a finite calibration gap: {bad}"
# ISSUE 14: the lm rung's record must carry the token-throughput schema
# with the compiled step's FLOP count; a CPU run has no MFU
lm = [r for r in recs if r.get("rung") == "lm"]
assert lm, "no lm rung record emitted"
for r in lm:
    for fld in ("tokens_per_sec_per_chip", "seq_len", "flops_per_step"):
        v = r.get(fld)
        assert v is not None and math.isfinite(float(v)), \
            f"lm record {fld} missing or non-finite: {v!r}"
    assert r["analytic_mfu"] is None, \
        f"CPU smoke record reports an MFU: {r['analytic_mfu']!r}"
# ISSUE 15: the lm_serve rung must carry the token-level serving
# schema (tokens/sec-at-SLO + TTFT p50/p99), run its timed wave with
# zero decode recompiles, and BEAT the whole-predict baseline on the
# same mixed-length workload
ls_ = [r for r in recs if r.get("rung") == "lm_serve"]
assert ls_, "no lm_serve rung record emitted"
for r in ls_:
    for fld in ("tokens_per_sec_at_slo", "ttft_p50_ms", "ttft_p99_ms",
                "whole_predict_tokens_per_sec", "vs_whole_predict",
                # ISSUE 20: block-paged KV pool + prefix-cache census
                "prefix_cache_hit_rate", "kv_pages_total",
                "kv_pages_shared"):
        v = r.get(fld)
        assert v is not None and math.isfinite(float(v)), \
            f"lm_serve record {fld} missing or non-finite: {v!r}"
    assert r["decode_recompiles_timed_wave"] == 0, \
        f"lm_serve timed wave recompiled: {r['decode_recompiles_timed_wave']}"
    assert r["vs_whole_predict"] > 1.0, \
        f"token-level serving did not beat whole-predict: {r['vs_whole_predict']}"
# ISSUE 18: the fleet rung must carry the multi-replica serving schema
# (aggregate rps-at-SLO + the single-server ratio measured on the same
# workload) with R >= 2 replicas and zero request errors.
# vs_single_server itself is not gated in smoke: R replicas share one
# CPU there, so the ratio only means something on real parallel hardware
fl = [r for r in recs if r.get("rung") == "fleet"]
assert fl, "no fleet rung record emitted"
for r in fl:
    for fld in ("value", "single_server_rps", "vs_single_server",
                "p50_ms", "p99_ms", "slo_attained"):
        v = r.get(fld)
        assert v is not None and math.isfinite(float(v)), \
            f"fleet record {fld} missing or non-finite: {v!r}"
    assert r.get("replicas", 0) >= 2, \
        f"fleet rung ran with {r.get('replicas')} replica(s)"
    assert r.get("comm_bytes_hlo", "MISSING") is None, \
        "fleet record comm_bytes_hlo convention broken"
    assert not r.get("request_errors"), \
        f"fleet rung dropped requests: {r['request_errors']}"
print(f"bench record schema: {len(recs)} records OK "
      f"({len(tuned)} autotuned, lm tokens/sec/chip "
      f"{lm[0]['tokens_per_sec_per_chip']} @ seq {lm[0]['seq_len']}, "
      f"lm_serve {ls_[0]['tokens_per_sec_at_slo']} tok/s@SLO = "
      f"{ls_[0]['vs_whole_predict']}x whole-predict, ttft p50 "
      f"{ls_[0]['ttft_p50_ms']}ms)")
PY
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
    stage "bench smoke (autotuned lenet + input + serve + lm + lm_serve + fleet)" \
        bench_smoke
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
