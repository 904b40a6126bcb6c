#!/bin/bash
# Per-file pytest isolation: this box's XLA CPU backend segfaults
# sporadically inside compile/serialize on long single-process runs
# (see tests/conftest.py).  One process per test file bounds the blast
# radius and makes the suite resumable: completed files are marked in
# $SUITE_STATE (default /tmp/suite_logs) and skipped on rerun.
set -u
STATE=${SUITE_STATE:-/tmp/suite_logs}
mkdir -p "$STATE"
status=0
# graph-contract gate (oversim_tpu/analysis/): every compiled entry
# point checked against its declarative contract + trace-time + AST
# lint, BEFORE the test tiers.  The JSON verdict is exported so
# run_manifest embeds it in every artifact (telemetry.analysis_verdict).
an_marker="$STATE/analyze.ok"
export OVERSIM_ANALYSIS_VERDICT="$STATE/analysis.json"
if [ -f "$an_marker" ]; then
  echo "skip  analyze (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/analyze.py --all --fast --compile-budget 600 \
      --json "$OVERSIM_ANALYSIS_VERDICT" \
      > "$STATE/analyze.log" 2>&1; then
  touch "$an_marker"
  echo "PASS  analyze  $(tail -1 "$STATE/analyze.log")"
else
  status=1
  echo "FAIL  analyze  $(tail -1 "$STATE/analyze.log")"
fi
for f in tests/test_*.py; do
  name=$(basename "$f" .py)
  marker="$STATE/$name.ok"
  if [ -f "$marker" ]; then
    echo "skip  $name (done)"
    continue
  fi
  # hard per-module ceiling: one runaway module must not eat the
  # whole suite budget (round-3 lost a third of the suite that way)
  if timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
      python -m pytest "$f" -q > "$STATE/$name.log" 2>&1; then
    touch "$marker"
    echo "PASS  $name  $(tail -1 "$STATE/$name.log")"
  else
    status=1
    echo "FAIL  $name  $(tail -1 "$STATE/$name.log")"
  fi
done
# service-plane kill-and-resume smoke (scripts/service_smoke.py): a
# real SIGKILL mid-run, resume from the atomic checkpoint, final state
# bit-identical — same marker/timeout discipline as the test modules
smoke_marker="$STATE/service_smoke.ok"
if [ -f "$smoke_marker" ]; then
  echo "skip  service_smoke (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/service_smoke.py > "$STATE/service_smoke.log" 2>&1; then
  touch "$smoke_marker"
  echo "PASS  service_smoke  $(tail -1 "$STATE/service_smoke.log")"
else
  status=1
  echo "FAIL  service_smoke  $(tail -1 "$STATE/service_smoke.log")"
fi
# elastic-fleet chaos smoke (scripts/fleet_run.py): 2 workers sharding a
# 4-replica campaign, 3 seeded SIGKILLs + reschedule-from-checkpoint,
# then --verify pins the merged ensemble EXACTLY equal (counter leaves
# and summary) to an uninterrupted single-process run
fleet_marker="$STATE/fleet_smoke.ok"
if [ -f "$fleet_marker" ]; then
  echo "skip  fleet_smoke (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/fleet_run.py --workers 2 --replicas 4 --ticks 64 \
      --chunk 16 --n 8 --overlay chord --chaos --kills 3 \
      --chaos-span 25 --verify --out "$STATE/fleet_smoke.out" \
      > "$STATE/fleet_smoke.log" 2>&1; then
  touch "$fleet_marker"
  echo "PASS  fleet_smoke  $(tail -1 "$STATE/fleet_smoke.log")"
else
  status=1
  echo "FAIL  fleet_smoke  $(tail -1 "$STATE/fleet_smoke.log")"
fi
# sparse-tick gate (scripts/sparse_gate.py): 64 churned chord ticks
# under tick_impl="sparse" must be bit-identical to the dense oracle,
# and the compiled sparse tick must REPLACE the
# full-width payload gathers with [A]-lane ones (wide-gather drop >= 1,
# no new sorts)
sparse_marker="$STATE/sparse_gate.ok"
if [ -f "$sparse_marker" ]; then
  echo "skip  sparse_gate (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/sparse_gate.py > "$STATE/sparse_gate.log" 2>&1; then
  touch "$sparse_marker"
  echo "PASS  sparse_gate  $(tail -1 "$STATE/sparse_gate.log")"
else
  status=1
  echo "FAIL  sparse_gate  $(tail -1 "$STATE/sparse_gate.log")"
fi
# 2D-mesh sharded-tick gate (scripts/shard_gate.py): 64 churned chord
# ticks through ShardedSim on the (1, 8) mesh must be bit-identical to
# the unsharded oracle, the compiled sharded step
# may carry ONLY all-reduce:min collectives (no sorts), and on the
# (2, 4) campaign mesh no replica_groups set may span replica rows
shard_marker="$STATE/shard_gate.ok"
if [ -f "$shard_marker" ]; then
  echo "skip  shard_gate (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/shard_gate.py > "$STATE/shard_gate.log" 2>&1; then
  touch "$shard_marker"
  echo "PASS  shard_gate  $(tail -1 "$STATE/shard_gate.log")"
else
  status=1
  echo "FAIL  shard_gate  $(tail -1 "$STATE/shard_gate.log")"
fi
# AOT compile-plane smoke (scripts/aot_smoke.py): the same tiny scenario
# in TWO processes sharing one artifact store — the second must pre-warm
# every registered entry from exported artifacts with ZERO fresh
# compilations (per-entry compile_seconds 0.0 in its run manifest)
aot_marker="$STATE/aot_smoke.ok"
if [ -f "$aot_marker" ]; then
  echo "skip  aot_smoke (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/aot_smoke.py --store "$STATE/aot_store" \
      > "$STATE/aot_smoke.log" 2>&1; then
  touch "$aot_marker"
  echo "PASS  aot_smoke  $(tail -1 "$STATE/aot_smoke.log")"
else
  status=1
  echo "FAIL  aot_smoke  $(tail -1 "$STATE/aot_smoke.log")"
fi
# live-observability smoke (scripts/obs_smoke.py): a real service_run
# with ephemeral /metrics + synthetic ingest — counters monotone across
# two scrapes, SIGTERM flips /healthz to draining, flight JSONL + tail
# dump parse; loadgen prints the p50/p99 table; and the analyzer verdict
# with the obs plane armed in-process is identical to the obs-off one
# (reuses $OVERSIM_ANALYSIS_VERDICT from the analyze gate as baseline)
obs_marker="$STATE/obs_smoke.ok"
if [ -f "$obs_marker" ]; then
  echo "skip  obs_smoke (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/obs_smoke.py > "$STATE/obs_smoke.log" 2>&1; then
  touch "$obs_marker"
  echo "PASS  obs_smoke  $(tail -1 "$STATE/obs_smoke.log")"
else
  status=1
  echo "FAIL  obs_smoke  $(tail -1 "$STATE/obs_smoke.log")"
fi
# autoscale/admission smoke (scripts/autoscale_smoke.py): fleet_run
# --autoscale must record >= 1 live scale-up AND scale-down (re-split +
# reshard mid-campaign) with the merged ensemble exactly equal to an
# uninterrupted run, and loadgen --ramp under a small --max-pending must
# shed with explicit NACKs (zero lost sessions), flip /healthz to
# "overloaded", and keep the settled-latency p99 plateaued
autoscale_marker="$STATE/autoscale_smoke.ok"
if [ -f "$autoscale_marker" ]; then
  echo "skip  autoscale_smoke (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/autoscale_smoke.py > "$STATE/autoscale_smoke.log" 2>&1; then
  touch "$autoscale_marker"
  echo "PASS  autoscale_smoke  $(tail -1 "$STATE/autoscale_smoke.log")"
else
  status=1
  echo "FAIL  autoscale_smoke  $(tail -1 "$STATE/autoscale_smoke.log")"
fi
# SLO soak gate (scripts/slo_soak.py): the overlay-as-a-service daemon
# serves 100 concurrent TCP clients across 2 tenants — one host sync
# per serving window (fake-timer pin), sustained soak rounds drained
# in-deadline, tenant-0 overload sheds with EXT_NACK while tenant 1
# stays un-nacked with settled p99 under the window budget (scraped
# from per-tenant /metrics), and the final accounting identity
# minted == settled + nacked holds with zero lost sessions
slo_marker="$STATE/slo_soak.ok"
if [ -f "$slo_marker" ]; then
  echo "skip  slo_soak (done)"
elif timeout "${SUITE_MODULE_TIMEOUT:-3000}" \
    python scripts/slo_soak.py --out "$STATE/slo_soak.json" \
      --workdir "$STATE/slo_soak" > "$STATE/slo_soak.log" 2>&1; then
  touch "$slo_marker"
  echo "PASS  slo_soak  $(tail -1 "$STATE/slo_soak.log")"
else
  status=1
  echo "FAIL  slo_soak  $(tail -1 "$STATE/slo_soak.log")"
fi
exit $status
