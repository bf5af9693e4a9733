#!/bin/bash
# Run every bench on the chip, one process after another (a chip belongs
# to one process at a time). Operator tool; see docs/OPERATIONS.md
# "Benchmarks".
set -u
cd "$(dirname "$0")"
fails=0
for b in bench.py bench_gpt_large.py bench_bert.py bench_inference.py \
         bench_longseq.py bench_offload.py; do
  echo "=== $b $(date -u +%H:%M:%SZ) ==="
  python "$b" || { echo "[bench_all] $b failed (continuing)"; fails=$((fails+1)); }
done
echo "=== probes ==="
python bench_params_ceiling.py || { echo "[bench_all] params ceiling failed"; fails=$((fails+1)); }
python bench_tpu_smokes.py || { echo "[bench_all] tpu smokes failed"; fails=$((fails+1)); }
python bench_woq_probe.py || { echo "[bench_all] woq probe failed"; fails=$((fails+1)); }
python bench_decompose.py || { echo "[bench_all] decompose failed"; fails=$((fails+1)); }
python bench_act_offload.py || { echo "[bench_all] act-offload failed"; fails=$((fails+1)); }
# Communication observatory: exposed-collective anatomy + achieved
# bus-bandwidth rows into COMMSCOPE_BENCH.json (perf_ledger tracks them
# across PRs).
python bench_commscope.py || { echo "[bench_all] commscope failed"; fails=$((fails+1)); }
# KV residency observatory: forced-eviction regret exactness, session
# heat, and the measured tiered_kv advisor row into
# KV_RESIDENCY_BENCH.json (perf_ledger tracks regret/resume-TTFT
# trajectories across PRs — the host-tier PR lands against them).
python bench_kv_residency.py || { echo "[bench_all] kv residency failed"; fails=$((fails+1)); }
# Tiered host KV: demote-on-evict / restore-on-resume at 10x+ session
# oversubscription — host-restore resume TTFT vs prefill recompute,
# zero-regret A/B, achieved advisor rows merged into
# KV_RESIDENCY_BENCH.json (must run AFTER bench_kv_residency: it
# amends that artifact's host_tier section in place).
python bench_host_kv.py || { echo "[bench_all] host kv failed"; fails=$((fails+1)); }
# Quantized + overlapped collectives: bucketed-overlap int8 grad wire
# vs the fused fp spelling (step time + exposed fraction + wire ratio)
# and the int8 TP decode collective (tokens/s + greedy parity) into
# OVERLAP_BENCH.json, plus on/off commscope rows amended into
# COMMSCOPE_BENCH.json (must run AFTER bench_commscope: it annotates
# that artifact in place).
python bench_overlap.py || { echo "[bench_all] overlap failed"; fails=$((fails+1)); }
# Serving engine: static-vs-continuous goodput, multi-turn prefix
# sharing, and the self-speculative decoding rows (spec-on vs spec-off
# accepted-tokens/step, verify-step overhead, wall goodput speedup,
# greedy parity) into SERVING_BENCH.json.
python bench_serving.py || { echo "[bench_all] serving failed"; fails=$((fails+1)); }
# Replay observatory: capture/replay parity and the advisor backtest —
# incl. the speculative_decoding lever (predicted vs achieved
# first-draft acceptance, +-10 pt band) — into REPLAY_BENCH.json and
# BACKTEST_REPORT.json.
python bench_replay.py || { echo "[bench_all] replay failed"; fails=$((fails+1)); }
# Load & scaling observatory: arrival analytics, service-rate / rho
# estimation, SLO-burn TTV, and the replay-backtested scaling advisor
# (predicted vs achieved queue-wait and goodput deltas, +-10 pt band
# at two fleet sizes) into LOADSCOPE_BENCH.json; also refreshes
# CAPACITY_REPORT.json with the scaling lever + achieved block.
python bench_loadscope.py || { echo "[bench_all] loadscope failed"; fails=$((fails+1)); }
# Elastic autoscaler chaos bench: fake-clock scale-up (warm join),
# drain-before-remove (zero loss, bit parity), mid-traffic kill with
# the incident latch, flap-bait self-freeze, SLO-green gauges through
# every scale event, doctor [autoscale] gates, and a capture->replay
# round-trip of the autoscaled run — into AUTOSCALE_BENCH.json
# (perf_ledger tracks scale-event latency and stranded work).
python bench_autoscale.py || { echo "[bench_all] autoscale failed"; fails=$((fails+1)); }
# NVMe aio tier microbench: threads x block x O_DIRECT sweep feeding
# the serving NVMe KV rung and optimizer-offload sizing (read/write
# MB/s rates are up-is-good; perf_ledger direction-infers *_mb_s).
# Local-disk only — does not touch the chip.
python -m deepspeed_tpu.ops.aio_bench --size-mb 64 --json AIO_BENCH.json \
  || { echo "[bench_all] aio bench failed"; fails=$((fails+1)); }
# Tenant attribution observatory: exact-conservation checks (tokens,
# page-seconds, tier bytes vs the fleet's own meters), fairness index
# on even vs skewed multi-tenant traffic, and the injected
# noisy-neighbor round-trip — into TENANT_BENCH.json (the fairness
# rows are up-is-good in the perf ledger).
python bench_tenantscope.py || { echo "[bench_all] tenantscope failed"; fails=$((fails+1)); }
echo "=== perf ledger ==="
# Fold every bench JSON this chain just rewrote into the cross-PR
# trajectory and gate on regressions vs each series' rolling best
# (observability/perf_ledger.py; report-only here — the chain's own
# failures already count, and a wall-noise trip should not mask them).
python -m deepspeed_tpu.observability.perf_ledger --root . --out PERF_LEDGER.json --no-gate \
  || { echo "[bench_all] perf ledger failed"; fails=$((fails+1)); }
echo "=== bench_all done, $fails failures $(date -u +%H:%M:%SZ) ==="
exit $((fails > 0))
