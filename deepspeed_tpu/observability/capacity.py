"""Capacity attribution: HBM ledger, per-program cost census, advisor.

ZeRO-Infinity's memory-wall analysis starts from an explicit
per-component byte ledger, and EQuARX motivates quantized collectives
from measured per-program collective-byte attribution; this module is
that measurement substrate for the serving/training stack, composed from
three pieces:

- :func:`hbm_ledger` — the live HBM budget decomposed into weights
  (WOQ/dtype-aware), KV cache (from the cache layout the slot engine
  actually allocates), and per-program temp/peak (from the compiler's own
  ``memory_analysis``), with projected headroom (max slots / max context
  at the current config) as ``Memory/ledger_*`` gauges.
- :class:`ProgramCensus` — a registry over the engines' bounded compiled
  program set: static FLOPs / HBM bytes (``compiled_cost_analysis``) and
  collective bytes (``comm.hlo_analysis``) per program, joined against
  achieved per-program wall time from the PR-5 span ring to produce
  achieved-vs-roofline MBU/MFU attribution per program.
- :func:`capacity_report` — the advisor: composes workload analytics
  (``workload.py``), the ledger, and the census into what-if estimates on
  the *observed* traffic (prefill tokens prefix sharing would have saved,
  the decode-step speedup bound from int8 KV bytes, the collective-byte
  share of the step) and ranks the roadmap levers by measured payoff.
  Emitted as ``CAPACITY_REPORT.json`` and a ``doctor`` section.

Degradation contract (pinned by tier-1 tests): every compiler analysis
(``cost_analysis`` / ``memory_analysis``) is best-effort per backend —
on a backend that doesn't implement one, the census and ledger keep
every field PRESENT with ``None`` values and warn once; they never
raise. A capacity report from a CPU smoke run is partial, not absent.
"""

from __future__ import annotations

import json
import math
import os
from pathlib import Path
from typing import Any, Optional

from ..utils.logging import warning_once
from .metrics import MetricsRegistry, Reservoir

CAPACITY_SCHEMA = "dstpu-capacity-report/v1"

# Advisor lever names, in the order the smoke bench asserts on.
LEVER_PREFIX = "prefix_sharing"
LEVER_KV_QUANT = "kv_quantization"
LEVER_COLLECTIVES = "quantized_collectives"
LEVER_SPECULATION = "speculative_decoding"
LEVER_TIERED_KV = "tiered_kv"
LEVER_SCALING = "scaling"
LEVER_TENANT = "tenant_affinity"


def roofline_peaks(device=None) -> tuple:
    """``(peak_flops, peak_hbm_bw)`` for ``device`` (default: device 0),
    ``None`` where the chip is unknown to the peak tables — census rows
    then degrade their MFU/MBU fields to null. The one shared probe both
    engines' census entry points use."""
    import jax

    from ..utils.timer import peak_flops_for, peak_hbm_bw_for

    if device is None:
        device = jax.devices()[0]
    out = []
    for fn in (peak_flops_for, peak_hbm_bw_for):
        try:
            out.append(fn(device))
        except ValueError:
            out.append(None)
    return tuple(out)


# ------------------------------------------------------------------ ledger
def kv_cache_bytes(model_cfg, slots: int, max_len: int, dtype, *,
                   page_size: int = 0, pool_pages: int = 0,
                   kv_quant_bits: int = 0) -> dict:
    """KV-cache byte breakdown for the slot engine's ONE persistent cache,
    summed over the buffers the cache's kind declares (``inference/kinds``,
    the allocator's own source): each buffer that grows with the position
    at its own width (``per_token_bytes``), and what a slot holds whatever
    its length — a recurrent state, window rings, conv tails — as
    ``state_bytes``, which ``total_bytes`` and ``per_slot_bytes`` include.

    ``page_size > 0`` accounts the pooled page layout instead: the
    resident total is the pool (+ the fp32 scale planes when the pool is
    int8), ``per_token_bytes`` is what one cached token actually costs —
    the figure the int8-KV lever halves — and ``page_bytes`` is the unit
    the operator sizes the pool in (docs/OPERATIONS.md)."""
    import jax.numpy as jnp

    from ..inference.decode import cache_layout
    from ..inference.kinds import kind_of

    if page_size > 0:
        shape, dt = cache_layout(model_cfg, slots, max_len, dtype,
                                 page_size=page_size, pages=pool_pages)
        if kv_quant_bits == 8:
            itemsize = 1
            scale_bytes = 2 * int(math.prod(shape[:-1])) * 4   # f32 scales
        else:
            itemsize = jnp.dtype(dt).itemsize
            scale_bytes = 0
        pool_bytes = 2 * int(math.prod(shape)) * itemsize
        total = pool_bytes + scale_bytes
        page_bytes = total // max(1, pool_pages)
        per_slot = page_bytes * (max_len // page_size)
        return {"total_bytes": total, "per_slot_bytes": per_slot,
                "per_token_bytes": page_bytes // page_size,
                "itemsize": itemsize, "slots": slots, "max_len": max_len,
                "shape": list(shape),
                "dtype": "int8" if kv_quant_bits == 8 else
                str(jnp.dtype(dt)),
                "page_size": page_size, "pool_pages": pool_pages,
                "page_bytes": page_bytes, "scale_bytes": scale_bytes,
                "kv_quant_bits": kv_quant_bits}
    kind = kind_of(model_cfg)
    shape, dt = next(iter(kind.buffers(slots, max_len, dtype).values()))
    per_token = kind.bytes_per_token(dtype)
    state = kind.state_bytes_per_slot(dtype)
    per_slot = max_len * per_token + state
    return {"total_bytes": slots * per_slot, "per_slot_bytes": per_slot,
            "per_token_bytes": per_token, "state_bytes": slots * state,
            "itemsize": jnp.dtype(dt).itemsize, "slots": slots,
            "max_len": max_len,
            "shape": list(shape), "dtype": str(jnp.dtype(dt)),
            "page_size": 0, "pool_pages": 0, "page_bytes": 0,
            "scale_bytes": 0, "kv_quant_bits": 0}


def hbm_ledger(*, params: Any, model_cfg, slots: int, max_len: int,
               cache_dtype, temp_bytes: Optional[int] = None,
               limit_bytes: Optional[int] = None,
               registry: Optional[MetricsRegistry] = None,
               page_size: int = 0, pool_pages: int = 0,
               kv_quant_bits: int = 0,
               pages_used: Optional[int] = None,
               pages_free: Optional[int] = None,
               idle_kv_bytes: Optional[int] = None,
               host_tier_bytes: Optional[int] = None) -> dict:
    """Decompose the HBM budget of a serving config into its components.

    ``params`` is the engine's (possibly WOQ-quantized) tree — weights
    count their *resident* bytes (int8/int4 + scales for quantized
    leaves) plus the per-decode-step streamed-bytes model the MBU gauges
    already use. ``temp_bytes`` is the largest per-program temp
    allocation the census measured (None = unknown on this backend).
    ``limit_bytes`` defaults to the accelerator's reported HBM limit
    (None when the platform doesn't report one, e.g. CPU). Every field is
    always present; unknown values are None."""
    from ..inference.quantization import decode_weight_bytes, quantized_bytes

    weights = int(quantized_bytes(params))
    stream = int(decode_weight_bytes(params))
    kv = kv_cache_bytes(model_cfg, slots, max_len, cache_dtype,
                        page_size=page_size, pool_pages=pool_pages,
                        kv_quant_bits=kv_quant_bits)
    if limit_bytes is None:
        from ..platform.accelerator import get_accelerator

        limit_bytes = int(get_accelerator().memory_stats().bytes_limit) \
            or None
    known = weights + kv["total_bytes"] + (temp_bytes or 0)
    out = {
        "weights_bytes": weights,
        "weights_stream_bytes_per_step": stream,
        "kv_bytes": kv["total_bytes"],
        "kv_per_slot_bytes": kv["per_slot_bytes"],
        "kv_per_token_bytes": kv["per_token_bytes"],
        "cache_itemsize": kv["itemsize"],
        "cache_dtype": kv["dtype"],
        "slots": slots,
        "max_len": max_len,
        "temp_bytes": temp_bytes,
        "total_bytes": known,
        "limit_bytes": limit_bytes,
        "headroom_bytes": None,
        "projected_max_slots": None,
        "projected_max_context": None,
        # paged decomposition: pool pages used/free at their byte cost —
        # the live occupancy truth replacing the contiguous estimate
        # (all zero/None on the contiguous path)
        "kv_page_size": kv["page_size"],
        "kv_pool_pages": kv["pool_pages"],
        "kv_page_bytes": kv["page_bytes"],
        "kv_scale_bytes": kv["scale_bytes"],
        "kv_quant_bits": kv["kv_quant_bits"],
        "kv_pool_used_pages": pages_used,
        "kv_pool_free_pages": pages_free,
        "kv_pool_used_bytes": (pages_used * kv["page_bytes"]
                               if pages_used is not None else None),
        "kv_pool_free_bytes": (pages_free * kv["page_bytes"]
                               if pages_free is not None else None),
        # the host-tier row (kvscope): HBM currently held by IDLE
        # sessions' tree-retained pages — what demoting them to pinned
        # host memory would reclaim at the measured idle distribution.
        # None when the residency observatory isn't running (older
        # reports simply lack the figure; null is the contract).
        "kv_idle_resident_bytes": idle_kv_bytes,
        # ACHIEVED host tier (serving/hostkv.py): bytes of demoted KV
        # the pinned-host store holds right now — the projected
        # kv_idle_resident_bytes reclaim, realized. None when no tier
        # is attached (serving.host_pool_bytes=0).
        "kv_host_tier_bytes": host_tier_bytes,
    }
    if limit_bytes:
        free_for_kv = limit_bytes - weights - (temp_bytes or 0)
        out["headroom_bytes"] = limit_bytes - known
        if page_size > 0 and kv["page_bytes"] > 0:
            per_slot_pages = max_len // page_size
            out["projected_max_slots"] = max(
                0, free_for_kv // (kv["page_bytes"] * per_slot_pages))
        elif kv["per_slot_bytes"] > 0:
            out["projected_max_slots"] = max(
                0, free_for_kv // kv["per_slot_bytes"])
        if kv["per_token_bytes"] > 0 and slots > 0:
            out["projected_max_context"] = max(
                0, free_for_kv // (kv["per_token_bytes"] * slots))
    if registry is not None:
        for k, v in out.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                registry.gauge(f"Memory/ledger_{k}").set(float(v))
    return out


# ------------------------------------------------------------------ census
_CENSUS_STATIC_FIELDS = ("flops", "bytes_accessed", "collective_mbytes",
                         "collective_count", "collectives", "temp_bytes",
                         "peak_bytes")


class ProgramCensus:
    """Static cost + achieved wall time per compiled program.

    ``measure(name, jitted, *args)`` AOT-lowers/compiles the program
    (ShapeDtypeStruct args make this device-memory-free) and records the
    compiler's FLOPs / bytes-accessed, the HLO collective census, and the
    buffer-assignment temp/peak. ``observe_wall`` / ``attach_spans`` feed
    achieved per-call wall times (the serving span ring's ``decode_step``
    and ``prefill_chunk`` spans, the training ``train_step`` spans), and
    ``report()`` joins the two into per-program achieved-vs-roofline
    MBU/MFU. Analyses that a backend doesn't support leave their fields
    None (one warning, never a raise)."""

    def __init__(self, peak_flops: Optional[float] = None,
                 peak_bw: Optional[float] = None):
        self.peak_flops = peak_flops
        self.peak_bw = peak_bw
        self._static: dict[str, dict] = {}
        self._wall: dict[str, Reservoir] = {}
        self._calls: dict[str, int] = {}

    # ----------------------------------------------------------- static side
    def measure(self, name: str, jitted, *args, mesh=None, **kwargs) -> dict:
        """Record the static costs of one program; returns the row."""
        from ..comm.hlo_analysis import collective_totals
        from ..profiling.flops_profiler import (compiled_cost_analysis,
                                                compiled_memory_analysis)

        row: dict[str, Any] = {k: None for k in _CENSUS_STATIC_FIELDS}
        compiled = None
        try:
            lowered = jitted
            if hasattr(lowered, "lower"):
                if mesh is not None:
                    with mesh:
                        lowered = lowered.lower(*args, **kwargs)
                else:
                    lowered = lowered.lower(*args, **kwargs)
            compiled = lowered.compile() if hasattr(lowered, "compile") \
                else lowered
        except Exception as e:
            warning_once(f"capacity census: lowering {name!r} for analysis "
                         f"failed on this backend ({e!r}) — census row "
                         "kept with null values")
        if compiled is not None:
            try:
                cost = compiled_cost_analysis(compiled)
                row["flops"] = _maybe_num(cost.get("flops"))
                row["bytes_accessed"] = _maybe_num(cost.get("bytes accessed"))
            except Exception as e:
                warning_once("capacity census: cost_analysis unavailable on "
                             f"this backend ({e!r}) — FLOPs/bytes fields "
                             "stay null")
            try:
                mem = compiled_memory_analysis(compiled)
                row["temp_bytes"] = mem.get("temp_size_in_bytes")
                row["peak_bytes"] = mem.get(
                    "peak_memory_in_bytes",
                    _sum_or_none(mem, ("argument_size_in_bytes",
                                       "output_size_in_bytes",
                                       "temp_size_in_bytes")))
            except Exception as e:
                warning_once("capacity census: memory_analysis unavailable "
                             f"on this backend ({e!r}) — temp/peak fields "
                             "stay null")
            try:
                coll = collective_totals(compiled)
                row["collective_mbytes"] = coll["mbytes"]
                row["collective_count"] = int(coll["count"])
                row["collectives"] = coll["by_kind"]
            except Exception as e:
                warning_once("capacity census: HLO text unavailable on this "
                             f"backend ({e!r}) — collective fields stay "
                             "null")
        self._static[name] = row
        return row

    # --------------------------------------------------------- achieved side
    def observe_wall(self, name: str, seconds: float) -> None:
        r = self._wall.get(name)
        if r is None:
            r = self._wall[name] = Reservoir(1024)
        r.add(float(seconds))
        self._calls[name] = self._calls.get(name, 0) + 1

    def attach_spans(self, events) -> int:
        """Fold a span ring into per-program wall samples: ``decode_step``
        spans belong to the slot decode program, ``prefill_chunk`` spans
        to their ``chunk_<size>``/``final_<size>`` bucket program,
        ``train_step`` spans to the train step. Returns samples taken."""
        from . import spans as S

        n = 0
        for ev in events:
            if ev.t1 is None:
                continue
            if ev.kind == S.DECODE_STEP:
                name = "step"
            elif ev.kind == S.PREFILL_CHUNK:
                stem = "final" if ev.meta.get("final") else "chunk"
                name = f"{stem}_{ev.meta.get('size')}"
            elif ev.kind == S.TRAIN_STEP:
                name = "train_step"
            else:
                continue
            self.observe_wall(name, ev.duration)
            n += 1
        return n

    # --------------------------------------------------------------- readout
    def report(self) -> dict:
        """Per-program rows, static + achieved joined. Programs with no
        wall samples report static columns only (and vice versa)."""
        rows: dict[str, dict] = {}
        for name in sorted(set(self._static) | set(self._wall)):
            row = dict(self._static.get(
                name, {k: None for k in _CENSUS_STATIC_FIELDS}))
            res = self._wall.get(name)
            calls = self._calls.get(name, 0)
            wall = res.percentile(50) if res is not None and len(res) \
                else None
            row.update({"calls": calls, "wall_s_p50": wall,
                        "achieved_tflops": None, "mfu": None,
                        "achieved_gbps": None, "mbu": None})
            if wall:
                if row["flops"]:
                    ach = row["flops"] / wall
                    row["achieved_tflops"] = ach / 1e12
                    if self.peak_flops:
                        row["mfu"] = ach / self.peak_flops
                if row["bytes_accessed"]:
                    gbs = row["bytes_accessed"] / wall
                    row["achieved_gbps"] = gbs / 1e9
                    if self.peak_bw:
                        row["mbu"] = gbs / self.peak_bw
            rows[name] = row
        return {"programs": rows, "peak_flops": self.peak_flops,
                "peak_hbm_bw": self.peak_bw}


def _maybe_num(v):
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


def _sum_or_none(d: dict, keys) -> Optional[int]:
    vals = [d.get(k) for k in keys]
    if any(v is None for v in vals):
        return None
    return int(sum(vals))


# ----------------------------------------------------------------- advisor
def capacity_report(*, ledger: dict, census: Optional[dict] = None,
                    workload: Optional[dict] = None,
                    occupancy_avg: Optional[float] = None,
                    meta: Optional[dict] = None,
                    pages: Optional[dict] = None,
                    commscope: Optional[dict] = None,
                    kvscope: Optional[dict] = None,
                    loadscope: Optional[dict] = None,
                    tenantscope: Optional[dict] = None) -> dict:
    """Compose ledger + census + workload into the ranked what-if advisor.

    Every lever's score is the estimated fraction of its bounding
    resource it would save ON THE OBSERVED TRAFFIC — comparable across
    levers, honest about what was actually measured (unmeasured inputs
    degrade the lever to score 0 with a stated reason, they never
    invent a payoff). ``pages`` (the paged engine's
    ``PagePool.snapshot()``) closes the loop: levers the paged cache has
    ALREADY pulled report achieved savings next to the projection, so
    the report distinguishes "would save" from "is saving"."""
    levers = []

    # Prefix sharing: the measured shared-prefix fraction IS the fraction
    # of prefill compute (and prefill KV writes) a radix prefix cache
    # would have skipped on this traffic.
    overlap = (workload or {}).get("prefix_overlap")
    dedup = (workload or {}).get("dedupable_prefill_tokens")
    prefix_est = {"prefill_tokens_saved": dedup,
                  "shared_prefix_fraction": overlap}
    why_prefix = ("measured shared-prefix token fraction of admitted "
                  "prompts — the prefill work a prefix cache skips"
                  if overlap is not None else
                  "no workload analytics measured (serving.workload off)")
    if pages is not None and pages.get("prefix_sharing"):
        prefix_est["achieved"] = {
            "prefill_tokens_saved": pages.get("prefill_tokens_saved"),
            "tokens_saved_fraction": pages.get("tokens_saved_fraction"),
            "shared_page_acquires": pages.get("shared_page_acquires"),
            "prefix_hit_rate": pages.get("prefix_hit_rate"),
            "cow_copies": pages.get("cow_copies"),
        }
        why_prefix += ("; paged cache ACTIVE — achieved savings reported "
                       "alongside the estimator's projection")
    levers.append({
        "name": LEVER_PREFIX,
        "score": float(overlap) if overlap is not None else 0.0,
        "estimate": prefix_est,
        "why": why_prefix,
    })

    # int8 KV: decode is bandwidth-bound; the step's byte budget is the
    # streamed weights + the live KV it reads. Quantizing KV to int8
    # shrinks only the KV term — the bound is the byte ratio.
    kv_score = 0.0
    kv_est: dict[str, Any] = {"decode_step_speedup_bound": None,
                              "kv_read_bytes_per_step": None}
    itemsize = ledger.get("cache_itemsize")
    stream = ledger.get("weights_stream_bytes_per_step")
    per_tok = ledger.get("kv_per_token_bytes")
    slots = ledger.get("slots") or 0
    why_kv = "cache itemsize/weight-stream bytes unavailable"
    if itemsize and stream and per_tok:
        mean_ctx = _mean_context(workload, ledger)
        occ = occupancy_avg if occupancy_avg is not None else 1.0
        kv_read = per_tok * mean_ctx * occ * slots
        # int8 keeps 1 byte/elem + per-head scales (small); bound by the
        # pure byte ratio of the step's HBM traffic
        quant_kv = kv_read / itemsize
        bound = (stream + kv_read) / max(1.0, stream + quant_kv)
        kv_score = 1.0 - 1.0 / bound
        kv_est = {"decode_step_speedup_bound": bound,
                  "kv_read_bytes_per_step": int(kv_read),
                  "mean_context_tokens": mean_ctx,
                  "occupancy_avg": occ}
        why_kv = ("byte-ratio bound on the decode step: streamed weights "
                  "+ live KV read at measured occupancy/context, KV "
                  f"shrunk {itemsize}x to int8")
    if ledger.get("kv_quant_bits") == 8:
        # int8 KV is ON: the per-token bytes in the ledger ARE the
        # achieved figure; report them next to the fp equivalent so the
        # report shows the realized shrink, and zero the projection (the
        # lever is already pulled)
        kv_est["achieved"] = {
            "kv_bytes_per_token": per_tok,
            "kv_scale_bytes": ledger.get("kv_scale_bytes"),
            "kv_quant_bits": 8,
        }
        kv_score = 0.0
        why_kv = ("int8 KV ACTIVE — ledger per-token KV bytes are the "
                  "achieved (quantized) cost; nothing further to project")
    levers.append({"name": LEVER_KV_QUANT, "score": float(kv_score),
                   "estimate": kv_est, "why": why_kv})

    # Quantized/overlapped collectives: projected from the step's wire
    # bytes as a share of its HBM bytes (EQuARX-style int8 wires) — and
    # UPGRADED to the measured exposed-collective fraction when the
    # commscope observatory ran (observability/commscope.py): exposed
    # time is exactly the wall a T3-style overlap or a quantized wire
    # can reclaim, so the lever ranks on measured cost, not a proxy.
    coll_score = 0.0
    coll_est: dict[str, Any] = {"collective_byte_share": None}
    step_row = ((census or {}).get("programs") or {}).get("step") or {}
    cb, ba = step_row.get("collective_mbytes"), step_row.get("bytes_accessed")
    why_coll = "no census row for the decode step on this backend"
    if cb is not None and ba:
        share = (cb * 1e6) / ba
        coll_score = 0.5 * share          # int8 wires halve 16-bit bytes
        coll_est = {"collective_byte_share": share,
                    "collective_mbytes_per_step": cb}
        why_coll = ("measured collective bytes as a share of the decode "
                    "step's HBM bytes, halved by int8 wire quantization")
    cs_an = (commscope or {}).get("anatomy") or {}
    if cs_an.get("exposed_comm_frac") is not None:
        coll_score = float(cs_an["exposed_comm_frac"])
        cs_led = ((commscope or {}).get("ledger") or {}).get("by_kind") \
            or {}
        coll_est["measured"] = {
            "exposed_comm_frac": cs_an.get("exposed_comm_frac"),
            "overlap_frac": cs_an.get("overlap_frac"),
            "exposed_collective_s": cs_an.get("exposed_collective_s"),
            "achieved_busbw_gbps": {k: r.get("busbw_gbps")
                                    for k, r in cs_led.items()},
            "roofline_ratio": {k: r.get("roofline_ratio")
                               for k, r in cs_led.items()},
        }
        why_coll = ("MEASURED exposed-collective fraction of the step "
                    "wall (commscope trace anatomy) — the time "
                    "overlapping/quantizing collectives can reclaim; "
                    "achieved bus bandwidth per kind attached")
    # the lever is PULLED (quantized grad collectives / bucketed overlap
    # / int8 TP decode wire active): report what the spelling achieves —
    # exact static wire bytes vs the fp32 equivalent
    # (Engine.grad_comm_summary), the serving tp_quant bits — beside the
    # projection, and score only what REMAINS: the measured exposed
    # fraction still on the wall (self-demoting toward zero as the
    # overlap absorbs it — the PR-14 tiered_kv pattern), or 0 with the
    # reason stated when this backend can't measure what remains.
    gq = (commscope or {}).get("quantized") or {}
    if gq.get("active"):
        coll_est["achieved"] = {k: gq.get(k) for k in (
            "mode", "overlap", "error_feedback", "buckets",
            "tp_quant_bits", "wire_mbytes_per_step",
            "fp32_equivalent_mbytes", "wire_ratio", "data_world")}
        if cs_an.get("exposed_comm_frac") is not None:
            coll_score = float(cs_an["exposed_comm_frac"])
            why_coll += ("; quantized/overlapped collectives ACTIVE — "
                         "achieved wire ratio reported, score is the "
                         "REMAINING measured exposed fraction "
                         "(self-demotes as overlap absorbs it)")
        else:
            coll_score = 0.0
            why_coll = ("quantized/overlapped collectives ACTIVE — "
                        "achieved wire ratio reported; exposed fraction "
                        "unmeasured on this backend, so nothing further "
                        "to project (run the commscope observatory on "
                        "TPU for the remaining-exposed score)")
    levers.append({"name": LEVER_COLLECTIVES, "score": float(coll_score),
                   "estimate": coll_est, "why": why_coll})

    # Tiered (host-offloaded) KV: scored ENTIRELY from measurements —
    # observed eviction-regret traffic (the prefill the tree silently
    # re-pays today, kvscope's ghost ledger), the measured host↔device
    # copy bandwidth (the restore path's cost), and the span ring's
    # measured prefill throughput (the recompute path's cost). The score
    # is the regretted share of prefill work times the fraction of it a
    # host restore would win back (1 - restore/recompute, clipped at 0).
    # ANY unmeasured input degrades the lever to score 0 with the reason
    # stated — the advisor never invents a host-tier payoff.
    ks = kvscope or {}
    reg = ks.get("regret") or {}
    sess = ks.get("sessions") or {}
    tk_score = 0.0
    tk_est: dict[str, Any] = {
        "regret_tokens": reg.get("regret_tokens"),
        "regret_frac": reg.get("regret_frac"),
        "mean_regret_tokens_per_admission": reg.get("mean_regret_tokens"),
        "projected_restore_s_per_resume": None,
        "measured_recompute_s_per_resume": None,
        "copy_h2d_gbps": ((ks.get("copy_bandwidth") or {})
                          .get("h2d_gbps")),
        "prefill_tokens_per_s": ((ks.get("prefill") or {})
                                 .get("tokens_per_s")),
        "hbm_reclaimable_bytes": sess.get("idle_kv_bytes_now"),
        "idle_kv_byte_s": sess.get("idle_kv_byte_s"),
        "resume_overlap": (workload or {}).get("resume_overlap"),
    }
    regret_tokens = reg.get("regret_tokens") or 0
    regret_frac = reg.get("regret_frac")
    mean_tok = reg.get("mean_regret_tokens")
    cbw = tk_est["copy_h2d_gbps"]
    pr = tk_est["prefill_tokens_per_s"]
    ptb = ks.get("per_token_bytes") or ledger.get("kv_per_token_bytes")
    if not ks or not reg:
        why_tk = ("no KV residency observatory measured "
                  "(serving.kvscope off)")
    elif not regret_tokens:
        why_tk = ("no eviction regret observed on this traffic — the "
                  "tree covers the working set; a host tier would only "
                  "add restore latency")
    elif cbw is None:
        why_tk = ("host-to-device copy bandwidth unmeasured on this "
                  "backend — restore cost unknown, lever degraded")
    elif pr is None:
        why_tk = ("no measured prefill timings (serving.spans off) — "
                  "recompute cost unknown, lever degraded")
    elif not ptb:
        why_tk = ("per-token KV byte cost unknown (no paged cache "
                  "layout) — restore bytes unknown, lever degraded")
    else:
        restore_s = mean_tok * ptb / (cbw * 1e9)
        recompute_s = mean_tok / pr
        tk_est["projected_restore_s_per_resume"] = restore_s
        tk_est["measured_recompute_s_per_resume"] = recompute_s
        advantage = max(0.0, 1.0 - restore_s / recompute_s) \
            if recompute_s > 0 else 0.0
        tk_score = float(regret_frac or 0.0) * advantage
        why_tk = ("measured eviction-regret share of prefill work, "
                  "scaled by the measured restore-vs-recompute "
                  f"advantage (host restore {restore_s:.3g}s vs prefill "
                  f"recompute {recompute_s:.3g}s per mean regretted "
                  "resume)")
    ht = ks.get("host_tier") or {}
    if ht.get("restores"):
        # the tier is LIVE: report what it actually restored next to
        # the projection. Remaining regret (the score's input) already
        # excludes restored resumes — the lever demotes itself as the
        # tier absorbs the traffic it was priced on.
        tk_est["achieved"] = {
            "host_tier_bytes": ht.get("bytes"),
            "host_tier_pages": ht.get("pages"),
            "restores": ht.get("restores"),
            "restored_tokens": ht.get("restored_tokens"),
            "restore_bytes": ht.get("restore_bytes"),
            "restore_wait_s": ht.get("restore_wait_s"),
            "restore_tokens_per_s": ht.get("restore_tokens_per_s"),
            "hits": ht.get("hits"),
            "misses": ht.get("misses"),
            "prunes": ht.get("prunes"),
            "fallbacks": ht.get("fallbacks"),
        }
        why_tk += ("; host tier ACTIVE — achieved restores reported "
                   "alongside the projection (remaining regret scores "
                   "what the tier still misses)")
    # The disk rung's sub-estimate: same regret × advantage shape, but
    # the restore cost is the NVMe tier's MEASURED read bandwidth (its
    # verified promotions), falling back to AIO_BENCH numbers would be a
    # projection — unmeasured means score 0 with the reason stated.
    nv = ks.get("nvme_tier")
    if nv is not None:
        nv_score = 0.0
        nv_est: dict[str, Any] = {
            "pages": nv.get("pages"),
            "bytes": nv.get("bytes"),
            "capacity_bytes": nv.get("capacity_bytes"),
            "promotions": nv.get("promotions"),
            "spilled_in": ht.get("spills"),
            "fallbacks": nv.get("fallbacks"),
            "aio_errors": nv.get("aio_errors"),
            "read_mb_s": nv.get("read_mb_s"),
            "projected_nvme_restore_s_per_resume": None,
        }
        rbw = nv.get("read_mb_s")
        if not regret_tokens:
            why_nv = ("no eviction regret on this traffic — the upper "
                      "rungs cover the working set")
        elif rbw is None:
            why_nv = ("NVMe read bandwidth unmeasured (no verified "
                      "promotions yet) — disk restore cost unknown, "
                      "sub-estimate degraded; python -m "
                      "deepspeed_tpu.ops.aio_bench sweeps the disk alone")
        elif pr is None or not ptb or mean_tok is None:
            why_nv = ("prefill/recompute cost unmeasured — cannot "
                      "price disk restore against recompute")
        else:
            nvme_restore_s = mean_tok * ptb / (rbw * 1e6)
            recompute_s = mean_tok / pr
            nv_est["projected_nvme_restore_s_per_resume"] = nvme_restore_s
            adv = max(0.0, 1.0 - nvme_restore_s / recompute_s) \
                if recompute_s > 0 else 0.0
            nv_score = float(regret_frac or 0.0) * adv
            why_nv = ("measured regret share scaled by the measured "
                      f"NVMe-read-vs-recompute advantage (disk restore "
                      f"{nvme_restore_s:.3g}s vs recompute "
                      f"{recompute_s:.3g}s per mean regretted resume, "
                      f"at the tier's achieved {rbw:.1f} MB/s)")
        tk_est["nvme"] = nv_est
        tk_est["nvme_score"] = nv_score
        tk_est["nvme_why"] = why_nv
    levers.append({"name": LEVER_TIERED_KV, "score": float(tk_score),
                   "estimate": tk_est, "why": why_tk})

    # Self-speculation: the prompt-lookup acceptance estimate bounds the
    # extra tokens per verify pass draft-free speculation gets for free.
    accept = ((workload or {}).get("selfspec_accept") or {}).get("mean")
    accept = None if (isinstance(accept, float) and math.isnan(accept)) \
        else accept
    levers.append({
        "name": LEVER_SPECULATION,
        "score": float(accept) if accept is not None else 0.0,
        "estimate": {"selfspec_acceptance": accept},
        "why": ("measured n-gram prompt-lookup acceptance potential on "
                "admitted prompts" if accept is not None else
                "no workload analytics measured (serving.workload off)"),
    })

    # Scaling: the arrival & scaling observatory's measured utilization
    # (loadscope.py) prices capacity moves — add/remove replica and the
    # prefill↔decode rebalance — by predicted goodput and queue-wait
    # delta. Only present when the observatory ran (inert-by-default);
    # any unmeasured input self-demotes the lever with its reason.
    if loadscope is not None:
        util = loadscope.get("utilization") or {}
        rho = util.get("rho")
        wis = loadscope.get("what_ifs") or []
        sc_est: dict[str, Any] = {
            "rho": rho,
            "rho_decode": util.get("rho_decode"),
            "rho_prefill": util.get("rho_prefill"),
            "predicted_queue_wait_s": util.get("predicted_queue_wait_s"),
            "slo_ttv_s": (loadscope.get("forecast") or {}).get("slo_ttv_s"),
            "arrival_rate_per_s": (loadscope.get("arrival")
                                   or {}).get("rate_per_s"),
            "what_ifs": wis,
        }
        reasons = [str(r) for r in (loadscope.get("unmeasured") or [])]
        if rho is None or not wis:
            sc_score = 0.0
            why_sc = ("scaling inputs unmeasured — " + "; ".join(reasons)
                      if reasons else
                      "no utilization estimate on this traffic")
        else:
            best = max(wis, key=lambda w: w.get("score") or 0.0)
            # what-if scores are 0–100 urgency; lever scores are 0–1
            # fractions comparable across the advisor
            sc_score = float(best.get("score") or 0.0) / 100.0
            sc_est["recommendation"] = best.get("action")
            why_sc = (f"measured utilization rho={rho:.3g} prices "
                      f"{best.get('action')} by predicted goodput and "
                      "queue-wait delta (loadscope what-ifs)")
            if reasons:
                why_sc += "; partial inputs: " + "; ".join(reasons)
        ach = loadscope.get("achieved")
        if ach:
            sc_est["achieved"] = ach
            why_sc += ("; scaling backtest ACTIVE — achieved queue-wait/"
                       "goodput deltas reported alongside the prediction")
        levers.append({"name": LEVER_SCALING, "score": sc_score,
                       "estimate": sc_est, "why": why_sc})

    # Tenant affinity / adapter locality: the per-tenant observatory
    # (tenantscope.py) prices tenant-affine routing — keeping each
    # tenant's requests (and, once the S-LoRA build lands, its adapters)
    # on few replicas preserves exactly the prefix sharing the tenant's
    # OWN traffic exhibits, and matters in proportion to how unevenly
    # tenants consume the fleet (cross-tenant interference). Only
    # present when the observatory ran; single-tenant traffic
    # self-demotes with its reason.
    if tenantscope is not None:
        rows = tenantscope.get("tenants") or {}
        fair = tenantscope.get("fairness") or {}
        noisy = tenantscope.get("noisy") or {}
        jain = fair.get("jain")
        ptoks = sum(r.get("prompt_tokens") or 0 for r in rows.values())
        # token-weighted mean of each tenant's OWN prefix overlap — the
        # sharing a tenant-affine replica keeps hot
        t_overlap = (sum((r.get("prefix_overlap") or 0.0)
                         * (r.get("prompt_tokens") or 0)
                         for r in rows.values()) / ptoks
                     if ptoks else None)
        dom = fair.get("dominant_shares") or {}
        top = max(dom, key=dom.get) if dom else None
        tn_est: dict[str, Any] = {
            "per_tenant_overlap": t_overlap,
            "fairness_jain": jain,
            "n_tenants": len(rows),
            "noisy_episodes": noisy.get("episodes"),
            "top_tenant": top,
            "top_dominant_share": dom.get(top) if top else None,
        }
        if len(rows) < 2 or jain is None or t_overlap is None:
            tn_score = 0.0
            why_tn = ("single-tenant traffic (or nothing retired yet) — "
                      "tenant-affine routing has nothing to separate")
        else:
            # interference: 1 - Jain is 0 when tenants consume evenly
            # and → 1 as one tenant dominates; the affinity win is the
            # tenant-local overlap that routing can preserve, scaled by
            # how much there is to isolate
            tn_score = max(0.0, min(1.0, t_overlap * (1.0 - jain)))
            why_tn = (f"measured per-tenant overlap {t_overlap:.3g} × "
                      f"interference (1 - jain {jain:.3g}) prices "
                      "tenant-affine routing / adapter locality on this "
                      "traffic")
            if noisy.get("episodes"):
                why_tn += (f"; {noisy['episodes']} noisy-neighbor "
                           "episode(s) observed — isolation also buys "
                           "SLO protection")
        levers.append({"name": LEVER_TENANT, "score": tn_score,
                       "estimate": tn_est, "why": why_tn})

    levers.sort(key=lambda d: d["score"], reverse=True)
    return {
        "schema": CAPACITY_SCHEMA,
        "meta": dict(meta or {}),
        "workload": workload,
        "ledger": ledger,
        "census": census,
        "pages": pages,
        # the communication observatory's measured rows (None when it
        # didn't run — older reports simply lack the key, which the
        # validator accepts: nulls are the degradation contract, absence
        # is a pre-commscope artifact)
        "commscope": commscope,
        # the KV residency observatory's measured rows (same contract)
        "kvscope": kvscope,
        # the arrival & scaling observatory's measured rows (same
        # contract: None when it didn't run, absent on older artifacts)
        "loadscope": loadscope,
        # the per-tenant observatory's measured rows (same contract)
        "tenantscope": tenantscope,
        "advisor": {"levers": levers,
                    "ranked": [d["name"] for d in levers]},
    }


def _mean_context(workload: Optional[dict], ledger: dict) -> float:
    """Time-averaged live context (prompt + generated-so-far) per
    occupied slot, from the workload histograms when measured, else half
    the slot capacity. The decode-side mean is halved: ``decode_len``
    records the FINAL generated count at retirement, but context grows
    linearly over a slot's residency, so its time average is ~half."""
    if workload:
        p = (workload.get("prompt_len") or {}).get("mean")
        d = (workload.get("decode_len") or {}).get("mean")
        ok = [isinstance(v, (int, float)) and not math.isnan(v)
              for v in (p, d)]
        if any(ok):
            return float((p if ok[0] else 0.0) + (d / 2.0 if ok[1] else 0.0))
    return float(ledger.get("max_len") or 0) / 2.0


_REQUIRED_LEDGER_KEYS = (
    "weights_bytes", "weights_stream_bytes_per_step", "kv_bytes",
    "kv_per_slot_bytes", "kv_per_token_bytes", "cache_itemsize",
    "temp_bytes", "total_bytes", "limit_bytes", "headroom_bytes",
    "projected_max_slots", "projected_max_context",
    # paged decomposition (zero/None on the contiguous path)
    "kv_page_size", "kv_pool_pages", "kv_page_bytes", "kv_quant_bits",
    "kv_pool_used_pages", "kv_pool_free_pages")


def validate_capacity_report(report: dict) -> list:
    """Schema gate for ``CAPACITY_REPORT.json`` (same contract as
    ``validate_chrome_trace``): returns a list of problems, empty when
    the report is well-formed. Null values are legal everywhere — the
    degradation contract — but every field must be PRESENT."""
    errs = []
    if not isinstance(report, dict):
        return [f"report is {type(report).__name__}, not dict"]
    if report.get("schema") != CAPACITY_SCHEMA:
        errs.append(f"schema is {report.get('schema')!r}, "
                    f"want {CAPACITY_SCHEMA!r}")
    ledger = report.get("ledger")
    if not isinstance(ledger, dict):
        errs.append("missing ledger section")
    else:
        for k in _REQUIRED_LEDGER_KEYS:
            if k not in ledger:
                errs.append(f"ledger missing key {k!r}")
    adv = report.get("advisor")
    if not isinstance(adv, dict) or not isinstance(adv.get("levers"), list):
        errs.append("missing advisor.levers list")
    else:
        for i, lv in enumerate(adv["levers"]):
            if not isinstance(lv, dict):
                errs.append(f"advisor.levers[{i}] is "
                            f"{type(lv).__name__}, not dict")
                continue
            for k in ("name", "score", "estimate", "why"):
                if k not in lv:
                    errs.append(f"advisor.levers[{i}] missing {k!r}")
        ranked = adv.get("ranked")
        if ranked != [lv.get("name") for lv in adv["levers"]
                      if isinstance(lv, dict)]:
            errs.append("advisor.ranked does not match lever order")
    census = report.get("census")
    if census is not None and not isinstance(census, dict):
        errs.append(f"census is {type(census).__name__}, not dict")
    elif census is not None and not isinstance(
            census.get("programs", {}), dict):
        errs.append("census.programs is not a dict")
    for k in ("workload", "census", "pages"):
        if k not in report:
            errs.append(f"missing {k!r} section (null is fine)")
    return errs


def write_capacity_report(report: dict, path) -> Path:
    """Atomically write the report (tmp + rename, like the Prometheus
    sink: a concurrent reader never sees a torn file)."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_name(p.name + ".tmp")
    tmp.write_text(json.dumps(report, indent=2, default=_json_default),
                   encoding="utf-8")
    os.replace(tmp, p)
    return p


def _json_default(o):
    f = getattr(o, "item", None)
    if callable(f) and getattr(o, "size", 1) == 1:
        return f()
    f = getattr(o, "tolist", None)
    if callable(f):
        return f()
    return str(o)
