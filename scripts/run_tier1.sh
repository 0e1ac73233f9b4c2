#!/usr/bin/env bash
# Canonical tier-1 verify entrypoint (ROADMAP.md "Tier-1 verify").
#
# Runs the fast test suite on the CPU backend exactly the way the driver
# does — builders and CI should invoke THIS script rather than hand-rolling
# the pytest line, so the marker filter, plugin set, and DOTS_PASSED
# accounting stay in one place.
#
# Env overrides:
#   T1_TIMEOUT  seconds before the run is killed (default 870)
#   T1_LOG      log path (default /tmp/_t1.log)
set -o pipefail
cd "$(dirname "$0")/.."

LOG="${T1_LOG:-/tmp/_t1.log}"
rm -f "$LOG"
timeout -k 10 "${T1_TIMEOUT:-870}" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
# progress-line chars: . pass, F fail, E error, s skip, x xfail, X xpass
echo DOTS_PASSED=$(grep -aE '^[.FEsxX]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
# name the failures so a red run is triageable from the tail alone
# (pytest -q prints "FAILED tests/..::id" / "ERROR tests/..::id" summary lines)
fails=$(grep -aE '^(FAILED|ERROR) ' "$LOG" | awk '{print $2}' | sort -u)
echo "DOTS_FAILED=$(printf '%s\n' "$fails" | grep -c . )"
if [ -n "$fails" ]; then
    printf 'DOTS_FAILED_ID=%s\n' $fails
fi
# per-plane snapshot lines (TRANSFER_PLANE= / CKPT_PLANE= /
# SHARDING_PLANE= / RESILIENCE= / SERVING_PLANE= / FLEET= / STREAMING= /
# SHM= / ANALYSIS= / OBS=): tiny CPU workloads through each plane's
# production path, all through the ONE zoo-metrics snapshot codepath
# (analytics_zoo_tpu/obs/snapshots.py — previously five bespoke heredocs
# here). One process per plane: the sharding/analysis snapshots configure the
# 8-device simulated mesh themselves, which must happen before the JAX
# backend first initializes. The streaming snapshot carries the PR-19
# fleet block ("fleet": consumers/windows_total/freshness_p99_ratio/
# guard_rejected/rejected_never_adopted — a 2-consumer sharded run plus
# one guardrail-rejected poisoned commit). Never affects the exit code.
for plane in transfer ckpt sharding resilience serving fleet streaming shm analysis obs; do
    env JAX_PLATFORMS=cpu \
        python -m analytics_zoo_tpu.obs snapshot "$plane" \
        2>/dev/null | grep -aE '^[A-Z_]+=' || true
done
# serving-scale smoke (SERVING_SCALE= line): the continuous batch former +
# multi-model multiplexer under an open-loop 1x/3x/10x Poisson load on the
# CPU backend — seconds, not minutes; like the plane snapshots it never
# affects the exit code (the BENCH_DETAIL_SMOKE.json entry keeps the full
# per-leg detail).
env JAX_PLATFORMS=cpu BENCH_SMOKE=1 BENCH_ONLY=serving_scale \
    python bench.py 2>/dev/null | grep -a '^{' | tail -1 \
    | sed 's/^/SERVING_SCALE=/' || true
exit $rc
