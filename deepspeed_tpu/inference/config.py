"""Inference config (reference ``deepspeed/inference/config.py:128-304``).

The knobs that survive the TPU translation: dtype, tensor parallel size,
max output tokens, weight-only quantization. ``enable_cuda_graph`` and
``replace_with_kernel_inject`` have no analog — XLA compilation subsumes
graph capture, and the model is functional so there is nothing to inject.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
           "float32": jnp.float32, "fp32": jnp.float32,
           "float16": jnp.float16, "fp16": jnp.float16}


@dataclasses.dataclass
class ServingConfig:
    """Continuous-batching knobs (``deepspeed_tpu/serving/``).

    The compiled-program budget is a direct function of these: steady-state
    serving runs one slot decode program, one slot-insert program, and one
    prefill-chunk program per chunk bucket (powers of two from 8 up to
    ``prefill_chunk``) — see docs/SERVING.md for bucket-tuning guidance.
    """

    slots: int = 8                  # persistent KV slots (decode batch)
    max_len: int = 256              # per-slot cache capacity (prompt + new);
                                    # serving admits only P + max_new <= max_len
    prefill_chunk: int = 32         # SplitFuse-style chunk size: long prompts
                                    # prefill in chunks of this many tokens,
                                    # one chunk per scheduler iteration,
                                    # interleaved with the slot decode step
    max_queue: int = 0              # submit() backpressure; 0 = unbounded
    # ---- paged KV cache (serving/pages.py, docs/SERVING.md) ----
    # page_size > 0 replaces the contiguous per-slot cache with a pooled
    # (L, pages, KV, page_size, hd) page cache: per-slot integer page
    # tables indexed inside the attention read, a host-side radix prefix
    # tree sharing identical prompt prefixes copy-free across slots
    # (refcounted pages, copy-on-write at the first divergent page), and
    # typed PagePoolExhausted admission control instead of mid-decode
    # OOM. 0 (default) keeps the contiguous cache — bit-for-bit the
    # pre-paging engine, same program set.
    page_size: int = 0              # tokens per KV page; must divide max_len
    pool_pages: int = 0             # pool size incl. the reserved scratch
                                    # page; 0 = auto (1 + slots * pages/slot)
    prefix_sharing: bool = True     # radix-tree prefix reuse (paged only)
    # int8 quantized KV: pool stored int8 with per-token per-head scales,
    # quantized on append, dequantized at the attention read (the WOQ
    # point-of-use discipline applied to the cache). 0 = fp pool at the
    # engine compute dtype (the bit-parity path).
    kv_quant_bits: int = 0
    # ---- tiered KV: pinned-host page store (serving/hostkv.py) ----
    # host_pool_bytes > 0 (paged only) bounds a host-memory tier that
    # keeps evicted tree-held pages instead of dropping them: eviction
    # demotes full-block entries (data + int8 scale planes + the token
    # prefix that keys them), admission consults the tier right after
    # the radix-tree match, and matched cold prefixes restore by async
    # H2D copy into the prefill cache — resume pays copy bandwidth, not
    # recompute FLOPs. fp restore is bit-identical to recompute; lost/
    # corrupt/pruned host copies degrade to recompute, never crash.
    # 0 (default) builds no tier: one `is not None` per admission and
    # per eviction pass, zero new programs (docs/SERVING.md).
    host_pool_bytes: int = 0
    # ---- NVMe rung below the host tier (serving/tiering.py) ----
    # nvme_pool_bytes > 0 (requires host_pool_bytes) adds a disk rung:
    # host-tier prune victims SPILL to swap files via ops/aio.py async
    # writes instead of vanishing, and admission matches promote
    # NVMe→host→HBM through the same restore path with the same
    # CRC/fallback-to-recompute contract — session residency bounded by
    # disk, not DRAM. nvme_path picks the mount (default $TMPDIR/
    # dstpu_kv_nvme; each engine gets a private subdirectory).
    nvme_pool_bytes: int = 0
    nvme_path: "str | None" = None
    # demote_ahead_idle_s > 0 (requires host_pool_bytes) turns on the
    # background demotion lane: tree-held pages idle past this many
    # seconds are proactively staged into the tier OFF the admission
    # path, so a later eviction under pressure frees pages already
    # copied (a refcount drop, not a blocking gather+device_get —
    # measured in Serve/host_tier_demote_wait_s). 0 = off.
    demote_ahead_idle_s: float = 0.0
    # engine-wide sampling policy (per-request RNG still makes every
    # request's draws independent of batch composition)
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    greedy: bool = False
    # ---- request guards (resilience layer, docs/RESILIENCE.md) ----
    # Default per-request deadlines on the serving clock, in seconds
    # (0 = none; submit() accepts per-request overrides). TTFT is measured
    # submit → first token (queue wait included); total is submit → retire.
    # Expired requests finish with RequestStatus.TIMEOUT.
    ttft_deadline_s: float = 0.0
    total_deadline_s: float = 0.0
    # Decode-step watchdog: a serving decode step whose wall time exceeds
    # this logs + counts Serve/watchdog_stalls and flips health() to
    # degraded (0 = off). Measured around the step's EXISTING host
    # read-back — the watchdog adds no syncs.
    watchdog_s: float = 0.0
    # Deterministic fault injection (resilience.chaos.ChaosConfig | dict).
    # None/disabled = the engine builds no chaos machinery at all.
    chaos: "object | None" = None
    # ---- observability: spans / flight recorder / SLOs ----
    # Lifecycle span events (observability/spans.py): queued → prefill
    # chunks → slot placement → decode residency → retired(status), plus
    # per-step and occupancy events. Host-side ring only — zero added
    # device syncs and zero new compiled programs (the bench compile
    # freeze stays the acceptance gate). Off by default.
    spans: bool = False
    spans_ring: int = 4096
    # Flight recorder (observability/flight.py): when set, the engine
    # keeps a black box (span ring + metric snapshots + recent request
    # records) and dumps it to this directory on a watchdog stall or on
    # flight.dump(). None = no recorder built.
    flight_dir: "str | None" = None
    flight_max_dumps: int = 8
    # Declarative SLO targets + anomaly detection
    # (observability.slo.SLOConfig | dict): TTFT/TPOT p99 targets and
    # error budget scored into Serve/slo_*_burn gauges, a median+MAD
    # decode-step regression detector, and a compile-storm detector.
    # None = no scoring machinery built.
    slo: "object | None" = None
    # Traffic analytics on the admission path
    # (observability.workload.WorkloadConfig | dict): prefix-overlap /
    # self-speculation estimators + shape histograms into
    # Serve/workload_*, feeding the capacity advisor
    # (observability/capacity.py). Host-side only — zero new compiled
    # programs, zero device syncs. None = no analyzer built.
    workload: "object | None" = None
    # KV residency observatory (observability/kvscope.py |
    # observability.kvscope.KVScopeConfig | dict): ghost-tree
    # eviction-regret ledger on the page pool (every prefill token
    # re-paid because of a past eviction counted and attributed),
    # per-session lifecycle heat tracking (idle/resume histograms, HBM
    # byte-seconds-held-while-idle), and the measured inputs of the
    # tiered_kv capacity-advisor lever. Host-side only — zero new
    # compiled programs, zero device syncs (the copy-bandwidth probe
    # runs only when a capacity report asks). None (default) builds
    # nothing: one `is not None` per admission/retirement/eviction.
    kvscope: "object | None" = None
    # Draft-free self-speculative decoding
    # (inference.speculation.SpeculationConfig | dict): per-slot n-gram
    # prompt-lookup drafting + one fixed-shape length-(max_draft+1)
    # verify forward per decode step, with page-table-aware rollback of
    # rejected tokens. Requires greedy sampling (the serving engine
    # enforces it — greedy spec-on is bit-identical to greedy spec-off).
    # None (default) builds nothing: the decode lane stays the plain
    # one-token step.
    speculation: "object | None" = None
    # Goodput/badput wall-time attribution (observability/goodput.py):
    # decomposes elapsed wall time into productive decode/prefill vs
    # badput buckets (compile, queue-empty idle, watchdog stall, drain,
    # ...) as Serve/goodput_* gauges + the /goodput endpoint. Costs two
    # host clock reads per iteration when on; False (default) builds no
    # ledger — zero clock reads, zero programs.
    goodput: bool = False
    # Traffic capture (observability/replay.py): record every admitted
    # submit (relative time, prompt ids, seed, session, deadline
    # overrides), terminal result (the parity oracle's reference
    # tokens), and fleet chaos event into a bounded host ring — the
    # record half of record→replay. Flight/incident dumps bundle the
    # ring's tail as traffic_trace.jsonl. False (default) builds no
    # capture at all — one `is not None` per submit/retire, zero
    # programs, zero syncs.
    capture: bool = False
    capture_ring: int = 4096
    # Arrival & scaling observatory
    # (observability.loadscope.LoadScopeConfig | dict): rolling arrival
    # rate / burstiness / token-demand / trend estimators on the submit
    # path, queueing-model utilization from span-measured service rates,
    # SLO time-to-violation forecasting, and the scaling what-ifs the
    # capacity advisor's `scaling` lever + GET /scaling report. Host-side
    # only — zero new compiled programs; readout math runs at scrape
    # cadence, never per token. None (default) builds nothing: one
    # `is not None` per submit.
    loadscope: "object | None" = None
    # Per-tenant cost attribution, fairness & noisy-neighbor observatory
    # (observability.tenantscope.TenantScopeConfig | dict): a ledger
    # keyed by Request.tenant_id on the injectable clock — tokens,
    # queue-wait/TTFT/TPOT reservoirs, KV page-seconds (PagePool hook),
    # resident tier bytes (TierStore owner accounting), per-tenant
    # prefix overlap, Jain fairness, and an edge-triggered
    # noisy-neighbor detector that marks the flight ring and dumps a
    # per-tenant breakdown into incident dirs. Host-side only — zero
    # new compiled programs; per-tenant sums conserve the fleet totals
    # exactly. None (default) builds nothing: one `is not None` per
    # submit/admission/retirement.
    tenantscope: "object | None" = None
    # Elastic fleet autoscaler (serving.autoscaler.AutoscaleConfig |
    # dict): the actuation loop over the loadscope scaling report —
    # hysteresis-guarded add/drain-then-remove/rebalance with a flap
    # budget, incident cooldown latch, drain-before-remove, and a typed
    # decision audit ring (GET/POST /autoscale). Fleet-level: a solo
    # ServingEngine ignores it. None (default) builds nothing — the
    # fleet pays one `is not None` per step, zero threads/programs.
    autoscale: "object | None" = None
    # Live telemetry & control plane
    # (observability.server.TelemetryConfig | dict): an HTTP ops surface
    # (/metrics /healthz /readyz /requests /capacity /goodput /flight +
    # token-gated POST /drain /flight/dump /slo/reload) on a daemon
    # thread, loopback-bound by default. None / enabled=False (default)
    # builds nothing — zero threads, zero programs, zero syncs; the
    # bench_serving --smoke compile freeze is the oracle. Engines can
    # also start it explicitly via engine.serve_telemetry(port=0).
    telemetry: "object | None" = None

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"serving needs >= 1 slot, got {self.slots}")
        c = self.prefill_chunk
        if c < 8 or (c & (c - 1)) != 0:
            raise ValueError(
                f"prefill_chunk must be a power of two >= 8 (the chunk "
                f"bucket set), got {c}")
        if self.max_len < c:
            raise ValueError(f"max_len={self.max_len} < prefill_chunk={c}")
        if self.page_size:
            if self.page_size < 8 or self.max_len % self.page_size != 0:
                raise ValueError(
                    f"page_size must be >= 8 and divide max_len="
                    f"{self.max_len}, got {self.page_size}")
            per_slot = self.max_len // self.page_size
            if self.pool_pages == 0:
                # auto: every slot coverable with zero sharing, + scratch
                self.pool_pages = 1 + self.slots * per_slot
            elif self.pool_pages < 2:
                # smaller-than-worst-case pools are LEGAL (overcommit:
                # admission defers on transient pressure and sheds typed
                # PagePoolExhausted for requests that can never fit) —
                # but there must be at least one usable page + scratch
                raise ValueError(
                    f"pool_pages={self.pool_pages} < 2 (one usable page "
                    "+ the reserved scratch page)")
        if self.kv_quant_bits not in (0, 8):
            raise ValueError(f"kv_quant_bits must be 0 (off) or 8, "
                             f"got {self.kv_quant_bits}")
        if self.kv_quant_bits and not self.page_size:
            raise ValueError("kv_quant_bits requires the paged KV cache "
                             "(set serving.page_size)")
        if self.host_pool_bytes < 0:
            raise ValueError(f"host_pool_bytes must be >= 0, "
                             f"got {self.host_pool_bytes}")
        if self.host_pool_bytes and not self.page_size:
            raise ValueError("host_pool_bytes (the tiered host KV store) "
                             "requires the paged KV cache (set "
                             "serving.page_size)")
        if self.nvme_pool_bytes < 0:
            raise ValueError(f"nvme_pool_bytes must be >= 0, "
                             f"got {self.nvme_pool_bytes}")
        if self.nvme_pool_bytes and not self.host_pool_bytes:
            raise ValueError("nvme_pool_bytes (the NVMe KV rung) requires "
                             "the host tier above it (set "
                             "serving.host_pool_bytes)")
        if self.demote_ahead_idle_s < 0:
            raise ValueError(f"demote_ahead_idle_s must be >= 0, "
                             f"got {self.demote_ahead_idle_s}")
        if self.demote_ahead_idle_s and not self.host_pool_bytes:
            raise ValueError("demote_ahead_idle_s (background demotion) "
                             "requires the tiered host KV store (set "
                             "serving.host_pool_bytes)")
        for knob in ("ttft_deadline_s", "total_deadline_s", "watchdog_s"):
            if getattr(self, knob) < 0:
                raise ValueError(f"{knob} must be >= 0, "
                                 f"got {getattr(self, knob)}")
        if self.chaos is not None:
            from ..resilience.chaos import ChaosConfig

            self.chaos = ChaosConfig.from_any(self.chaos)
        if self.spans_ring < 1:
            raise ValueError(f"spans_ring must be >= 1, "
                             f"got {self.spans_ring}")
        if self.capture_ring < 1:
            raise ValueError(f"capture_ring must be >= 1, "
                             f"got {self.capture_ring}")
        if self.slo is not None:
            from ..observability.slo import SLOConfig

            self.slo = SLOConfig.from_any(self.slo)
        if self.workload is not None:
            from ..observability.workload import WorkloadConfig

            self.workload = WorkloadConfig.from_any(self.workload)
        if self.kvscope is not None:
            from ..observability.kvscope import KVScopeConfig

            self.kvscope = KVScopeConfig.from_any(self.kvscope)
        if self.speculation is not None:
            from .speculation import SpeculationConfig

            self.speculation = SpeculationConfig.from_any(self.speculation)
        if self.loadscope is not None:
            from ..observability.loadscope import LoadScopeConfig

            self.loadscope = LoadScopeConfig.from_any(self.loadscope)
        if self.tenantscope is not None:
            from ..observability.tenantscope import TenantScopeConfig

            self.tenantscope = TenantScopeConfig.from_any(self.tenantscope)
        if self.telemetry is not None:
            from ..observability.server import TelemetryConfig

            self.telemetry = TelemetryConfig.from_any(self.telemetry)
        if self.autoscale is not None:
            from ..serving.autoscaler import AutoscaleConfig

            self.autoscale = AutoscaleConfig.from_any(self.autoscale)

    @classmethod
    def from_any(cls, cfg: "ServingConfig | dict | None") -> "ServingConfig":
        if cfg is None:
            return cls()
        if isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown serving config keys: {sorted(unknown)}")
        return cls(**cfg)


@dataclasses.dataclass
class InferenceConfig:
    dtype: str = "bfloat16"            # compute dtype for decode
    tensor_parallel: int = 1           # reference tensor_parallel.tp_size
    expert_parallel: int = 1           # reference moe.ep_size: experts served
                                       # sharded over the mesh 'expert' axis
    max_out_tokens: int = 256          # reference max_out_tokens
    quantize: bool = False             # weight-only quant (WOQ)
    quant_group_size: int = 128
    quant_bits: int = 8                # 8 or 4 (nibble-packed)
    eos_token_id: Optional[int] = None
    seed: int = 0
    # Pallas streaming cache-attention for the 1-token decode step
    # (ops/decode_attention.py). None = auto: on for TPU, off elsewhere
    # (interpret-mode Pallas inside the decode scan is test-only slow).
    flash_decode: Optional[bool] = None
    # WOQ only: route eligible quantized projections through the fused
    # Pallas dequant-in-VMEM GEMM (ops/woq_matmul.py) so decode reads
    # int8/int4 bytes from HBM by construction. None = auto: on for TPU,
    # off elsewhere (the XLA per-use dequant is the portable fallback).
    woq_kernel: Optional[bool] = None
    # Subsumed knob, accepted for config compat: decode now keeps weights
    # quantized end-to-end and dispatches the dequant at each consumption
    # site, so there is no hoisted whole-tree dequant to toggle anymore
    # (XLA hoisted it out of the scan either way: docs/WOQ_DECODE.md).
    dequant_per_step: bool = False
    # Request tracing (observability/tracing.py): every generate() records
    # TTFT, per-token decode latency, tokens/s, and roofline MBU into a
    # ring buffer surfaced by InferenceEngine.metrics_snapshot(). When on,
    # generation compiles as two programs (prefill / decode scan) and pays
    # ONE extra host sync per request — never one per token. When off
    # (default), generate() keeps the single fused program and adds no
    # host synchronization at all.
    observability: bool = False
    trace_ring_size: int = 256
    # Quantized TP decode collective (EQuARX-style two-sided int8): spell
    # the T=1 decode step's model-axis partial-sum reductions — the
    # attention output (wo) and dense-MLP output (w_out) row-sharded
    # matmuls — as explicit blockwise-int8 all-reduces (both hops int8 +
    # fp32 block scales, comm/compressed.py int8_psum) instead of the
    # fp psum GSPMD inserts. ~4x fewer wire bytes per decode step on the
    # dominant TP collectives; greedy short-context decode stays exactly
    # token-parity with the fp default (the serving tests' oracle). 0
    # (default) keeps the GSPMD fp psum — bit-frozen, zero new programs;
    # TP=1 meshes are a no-op either way. Logits (the sampler's input)
    # are never quantized.
    tp_comm_quant: int = 0             # 0 = off, 8 = int8
    # Decode in host-checked chunks of this many steps instead of one fused
    # scan: between chunks the engine reads the (B,) done flags and stops
    # as soon as every row hit eos, so a batch that finishes early stops
    # paying for the dead tail of max_new_tokens. 0 (default) keeps the
    # zero-sync fused path; the chunked path costs one host sync per chunk
    # and is bit-identical (the tail is eos-filled either way).
    decode_chunk: int = 0
    # Continuous-batching knobs for serving.ServingEngine (ignored by the
    # plain generate() path). Accepts a nested dict in from_any.
    serving: "ServingConfig | None" = None

    def flash_decode_resolved(self) -> bool:
        if self.flash_decode is not None:
            return self.flash_decode
        import jax

        return jax.default_backend() == "tpu"

    def woq_kernel_resolved(self) -> bool:
        if self.woq_kernel is not None:
            return self.woq_kernel
        import jax

        return jax.default_backend() == "tpu"

    @classmethod
    def from_any(cls, cfg: "InferenceConfig | dict | None") -> "InferenceConfig":
        if cfg is None:
            return cls()
        if isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        flat = dict(cfg)
        # accept the reference's nested {"tensor_parallel": {"tp_size": N}}
        tp = flat.get("tensor_parallel")
        if isinstance(tp, dict):
            flat["tensor_parallel"] = int(tp.get("tp_size", 1))
        # accept the reference's {"moe": {"ep_size": N}} nesting — with the
        # same strictness as top-level keys (a typo'd sub-key must raise,
        # not silently serve with expert_parallel=1)
        moe = flat.pop("moe", None)
        if moe is not None:
            if not isinstance(moe, dict):
                raise ValueError("inference config 'moe' must be a dict "
                                 f"like {{'ep_size': N}}, got {moe!r}")
            unknown_moe = set(moe) - {"ep_size"}
            if unknown_moe:
                raise ValueError(f"unknown moe config keys: {sorted(unknown_moe)}")
            flat.setdefault("expert_parallel", int(moe.get("ep_size", 1)))
        srv = flat.get("serving")
        if srv is not None:
            flat["serving"] = ServingConfig.from_any(srv)
        unknown = set(flat) - known
        if unknown:
            raise ValueError(f"unknown inference config keys: {sorted(unknown)}")
        return cls(**flat)

    @property
    def compute_dtype(self) -> Any:
        return _DTYPES[self.dtype]
