"""ServingEngine: continuous batching over the split prefill/decode programs.

Reference analog: DeepSpeed-MII / FastGen's serving loop (continuous
batching + Dynamic SplitFuse scheduling) re-expressed for XLA's
static-shape world. The engine owns three device assets:

- a slot state (``slots.py``): ONE persistent (L, slots, KV, hd, max_len)
  KV cache plus per-slot length/tok/rng/done vectors, advanced by ONE
  compiled decode-step program regardless of which requests occupy it;
- a prefill lane: per-request chunked prefill through shape-bucketed
  chunk programs (every chunk is ``prefill_chunk`` tokens or a power-of-two
  bucket below it), at most one chunk per iteration so running requests'
  TPOT is never stalled by a long prompt;
- one insert program that writes a finished prefill into its slot
  (donated ``dynamic_update_slice`` — in place, full slot extent).

The host loop dispatches step N + 1 before it reads step N back: the books
run one step behind the device, which never waits for a read-back
(``ServingEngine._iterate``; docs/SERVING.md, "The host loop").

Steady state therefore compiles a BOUNDED program set — decode step +
insert + (2 x bucket count) prefill programs — and ``compiles`` counts
every build so the bench smoke test can assert no compilation happens
after warmup. Outputs are bit-identical to single-request
``generate(request_seeds=[seed], cache_len=max_len)``: per-request RNG
chains are folded from the request seed (never the slot or batch
position), and the decode step is literally the same ``decode_step`` the
static path scans.
"""

from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.config import ServingConfig
from ..inference.decode import (GenCarry, decode_step, forward_with_cache,
                                init_cache)
from ..inference.engine import InferenceEngine
from ..inference.kinds import kind_of
from ..inference.sampling import per_request_keys, split_keys
from ..inference.speculation import NGramTable
from ..observability import spans as _spans
from ..observability.export import request_record
from ..observability.metrics import get_registry
from ..observability.tracing import ServingStats
from ..ops.decode_attention import LANES, blocks_per_turn
from ..resilience.chaos import ChaosMonkey
from ..resilience.guards import QueueFullError, RequestStatus
from ..utils.logging import warning_once
from .pages import (PagePool, export_slot, hydrate_cache, import_slot,
                    init_paged_slots, insert_paged)
from .scheduler import Request, Scheduler
from .slots import init_slots, insert_request, retire_slots

# Serving programs kept per engine; generously above the steady-state set
# (decode step + insert + 2 programs per chunk bucket) so eviction means a
# config bug, not normal traffic.
_MAX_PROGRAMS = 64
# A built program -> the signatures Serve/retraces has counted for it.
# Process-wide: a fleet's replicas share one program set, and a retrace is
# counted by whichever of them sees it first.
_SIGNATURES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# Finished requests retained for pop_result(); a long-running server that
# never collects results must not leak host memory without bound.
_MAX_RESULTS = 4096
# health() reports degraded for this many iterations after a watchdog
# stall, then recovers — one slow step during warmup must not mark the
# replica unhealthy forever (the cumulative stall COUNT never resets).
_DEGRADED_WINDOW = 64


@dataclasses.dataclass
class _Flight:
    """A slot step dispatched and not yet read back: what the host needs to
    book it when it is (``ServingEngine._launch`` / ``_book``)."""

    step: int            # the iteration that dispatched it
    t0: float            # ... and when
    rows: dict           # slot -> request it ran, by the books of then
    queue: int           # the queue's depth then
    ahead: int           # 1: it went out before the step before was read
    read: tuple          # device: tok, done, ok and the trunk's counters
    lens: Optional[np.ndarray]      # the mirror of the lengths it leaves
    chunk_stats: list    # counters of the chunks that ran in front of it
    chunk_routing: list  # ... and their tapped routing


def _mean_exit_pdf(passes: dict, real):
    """Of a batch-1 chunk of a looped trunk: the mean over its first
    ``real`` (traced) positions of the distribution over exit passes."""
    pdf = passes["exit_pdf"][0]                               # (T, passes)
    keep = jnp.arange(pdf.shape[0])[:, None] < real
    return jnp.sum(jnp.where(keep, pdf, 0.0), axis=0) / real


def expand_per_request(v, n: int, default, coerce=None) -> list:
    """One scalar-or-per-request ``serve_batch`` argument expanded to
    ``n`` values (shared by ``ServingEngine`` and ``FleetEngine`` so the
    two surfaces cannot drift on coercion/validation). ``coerce`` (e.g.
    ``int``) applies to every non-None value; None skips coercion —
    session ids keep their caller type."""
    if v is None:
        vals = [default] * n
    elif isinstance(v, (list, tuple, np.ndarray)):
        if len(v) != n:
            raise ValueError(f"expected {n} per-request values, "
                             f"got {len(v)}")
        vals = list(v)
    else:
        vals = [v] * n
    if coerce is not None:
        vals = [x if x is None else coerce(x) for x in vals]
    return vals


class ServingEngine:
    """submit()/step()/drain() continuous batching on an InferenceEngine.

    ``engine`` supplies params, mesh, model, dtype, quantization and eos;
    ``serving`` (a :class:`~..inference.config.ServingConfig` or dict)
    supplies slots/max_len/prefill_chunk and the sampling policy. Serve/*
    load metrics land in ``stats.registry`` — pass ``registry`` to share
    one registry with the engine's request tracer, and ``clock`` to fake
    time in tests.
    """

    @_spans.timed_init("serving")
    def __init__(self, engine: InferenceEngine,
                 serving: ServingConfig | dict | None = None,
                 registry=None, clock=None, programs=None, rid_source=None,
                 name: str = ""):
        self.engine = engine
        # fleet seams (serving/fleet.py): ``programs`` shares ONE compiled
        # program cache across replicas of the same InferenceEngine (a
        # joining replica warms from it — elasticity never compile-storms),
        # ``rid_source`` shares one request-id namespace so a rid names a
        # request fleet-wide, ``name`` labels this replica in fleet
        # metrics. All None/"" on the single-engine path — behavior is
        # byte-identical to the pre-fleet engine.
        self.name = name
        if serving is None:
            serving = engine.config.serving
        self.cfg = ServingConfig.from_any(serving)
        self.model = engine.model
        mcfg = self.model.cfg
        if getattr(mcfg, "pos_embedding", None) == "learned" \
                and self.cfg.max_len > mcfg.max_seq:
            raise ValueError(
                f"serving max_len={self.cfg.max_len} exceeds the model's "
                f"learned-position table (max_seq={mcfg.max_seq})")
        # the cache kind (inference/kinds; docs/SERVING.md, "Cache kinds"):
        # what does not compose yet with it, or with the trunk's sorted
        # expert rows, fails here, in ONE check, and never falls back
        self.kind = kind_of(mcfg, self.cfg.slots, engine.compute_dtype,
                            engine.params)
        refused = self.kind.refusal([feature for feature, on in (
            ("paged", self.cfg.page_size > 0),
            ("kv_quant", bool(self.cfg.kv_quant_bits)),
            ("speculation", self.cfg.speculation is not None
             and self.cfg.speculation.enabled),
            ("host_kv", self.cfg.host_pool_bytes > 0),
            ("quantize", bool(engine.config.quantize)),
            ("mesh", engine.mesh.size > 1)) if on])
        if refused:
            raise ValueError(refused)
        self._flash = engine.config.flash_decode_resolved()
        if self._flash and self.cfg.max_len % 128 != 0:
            raise ValueError(
                f"flash_decode needs max_len to be a multiple of 128 "
                f"(Pallas lane blocks), got {self.cfg.max_len} — round up "
                "or set flash_decode=False")
        self.kind.flash, self.kind.max_len = self._flash, self.cfg.max_len
        # expert counters of chunks dispatched since the last decode
        # read-back: (span, device stats, chunk size)
        self._chunk_stats: list = []
        # a tap, not an option: set to a dict and every request's expert
        # choices are kept there, {rid: [(first position, (expert layers,
        # positions, k) experts)]} in the order computed (a later entry
        # overwrites an overlapping earlier one, as the cache does). Two
        # bf16 programs round a router's input differently now and then
        # and take different experts at a near-tie; a comparison of this
        # path with a reference has to follow THIS path's choices
        # (benchmark/kinds/backlog_routed.py). None: nothing is fetched.
        self.routing_log: Optional[dict] = None
        self._chunk_routing: list = []   # (rid, start, device routing, real)
        self._eos = engine.config.eos_token_id
        self._sampler = engine._sampler(self.cfg.temperature, self.cfg.top_k,
                                        self.cfg.top_p, self.cfg.greedy)
        self._mat = engine._materialized if engine.config.quantize else None
        # ---- self-speculative decoding (inference/speculation.py,
        # docs/SERVING.md): per-slot n-gram prompt-lookup drafts verified
        # by ONE fixed-shape length-(max_draft+1) forward per step. None
        # (default) leaves the decode lane the plain one-token step —
        # same program set, bit-identical behavior.
        self._spec = None
        sp = self.cfg.speculation
        if sp is not None and sp.enabled:
            if not (self.cfg.greedy or self.cfg.temperature == 0.0):
                raise ValueError(
                    "speculation requires greedy sampling (serving.greedy "
                    "= True or temperature = 0): the parity guarantee is "
                    "argmax chaining — stochastic sampling cannot be "
                    "verified against a draft bit-exactly")
            if self._flash:
                raise ValueError(
                    "speculation requires flash_decode off: the verify "
                    "forward runs the dense cache attention (T > 1), and "
                    "greedy parity is guaranteed only when the plain step "
                    "uses the same kernel")
            self._spec = sp
        # slot -> [rid, NGramTable, tokens_fed]: the per-slot drafter
        # state, lazily (re)built from prompt + emitted history so
        # placement, fleet adoption, and plain-step fallbacks all stay
        # in sync without hooks
        self._spec_tables: dict = {}
        self._spec_steps = 0           # verify forwards run
        self._spec_proposed = 0        # draft tokens proposed
        self._spec_accepted = 0        # draft tokens accepted
        self._spec_first_scored = 0    # slots with a non-empty draft
        self._spec_first_hits = 0      # ... whose first draft hit
        # decode-lane totals, BOTH lanes: emitted/slot-steps is the
        # accepted-tokens-per-step the goodput rollup and benches report
        # (exactly 1.0 when speculation is off)
        self._decode_slot_steps = 0
        self._decode_emitted = 0
        kw = {"clock": clock} if clock is not None else {}
        self.stats = ServingStats(registry=registry, **kw)
        # quantized TP decode collective (inference.tp_comm_quant): the
        # knob lives on the InferenceEngine — the shared decode step
        # carries it into every serving program automatically — but
        # serving surfaces it as a gauge so /metrics and the capacity
        # report can tell a quantized-wire replica from an fp one.
        self._tp_quant = int(getattr(engine.config, "tp_comm_quant", 0)
                             or 0)
        if self._tp_quant:
            self.stats.registry.gauge("Serve/tp_quant_bits").set(
                float(self._tp_quant))
        # ---- observability: spans / flight / SLO (docs/OBSERVABILITY.md).
        # All default-off; disabled they cost the hot path `is not None`
        # checks only — no clock reads, no syncs, no programs.
        self.spans: Optional[_spans.SpanRecorder] = None
        if self.cfg.spans:
            self.spans = _spans.SpanRecorder(self.cfg.spans_ring,
                                             clock=self.stats.clock)
        self.flight = None
        if self.cfg.flight_dir is not None:
            from ..observability.flight import FlightRecorder

            self.flight = FlightRecorder(
                self.cfg.flight_dir, spans=self.spans,
                snapshots={"serving": self.metrics_snapshot,
                           "health": self.health,
                           "long_iterations": _spans.long_iterations},
                max_dumps=self.cfg.flight_max_dumps,
                clock=self.stats.clock, job_name="serving",
                registry=self.stats.registry)
        # traffic analytics (observability/workload.py): prefix-overlap /
        # self-speculation estimators + shape histograms on the admission
        # path. None (default) = one `is not None` per admission, nothing
        # else — no programs, no syncs (the compile-freeze gate stays the
        # acceptance test).
        self.workload = None
        if self.cfg.workload is not None and self.cfg.workload.enabled:
            from ..observability.workload import WorkloadAnalyzer

            self.workload = WorkloadAnalyzer(
                self.cfg.workload, registry=self.stats.registry,
                clock=self.stats.clock)
        # traffic capture (observability/replay.py): every admitted
        # submit + terminal result into a bounded host ring — the record
        # half of record→replay; flight dumps bundle the ring's tail so
        # an incident dir is replayable standing alone. None (default)
        # builds nothing: one `is not None` per submit/retire, zero
        # programs, zero syncs (compile-freeze gates stay the oracle).
        self.capture = None
        if self.cfg.capture:
            from ..observability.replay import TrafficCapture

            self.attach_capture(TrafficCapture(
                clock=self.stats.clock, ring=self.cfg.capture_ring,
                meta=self._capture_meta()))
        self._build_slo(self.cfg.slo)
        # goodput/badput wall-time ledger (observability/goodput.py):
        # None (default) = zero clock reads added to the loop; enabled =
        # two host clock reads per iteration, still zero programs/syncs
        self.goodput = None
        if self.cfg.goodput:
            from ..observability.goodput import GoodputLedger

            self.goodput = GoodputLedger(clock=self.stats.clock,
                                         registry=self.stats.registry,
                                         prefix="Serve")
        # arrival & scaling observatory (observability/loadscope.py):
        # None (default) = one `is not None` per submit; enabled = a
        # bounded host-side arrival ring + scrape-cadence readout math,
        # still zero programs/syncs
        self.loadscope = None
        if self.cfg.loadscope is not None:
            from ..observability.loadscope import LoadScope

            self.loadscope = LoadScope(self.cfg.loadscope,
                                       registry=self.stats.registry,
                                       clock=self.stats.clock)
        # live telemetry server (observability/server.py): started at the
        # END of __init__ when config-enabled (the state must exist
        # before a scrape can land), or explicitly via serve_telemetry()
        self.telemetry = None
        self._request_logs: list = []
        # ---- paged KV cache (serving/pages.py, docs/SERVING.md): page
        # pool + radix prefix tree + host page-table mirror. Disabled
        # (page_size=0, the default) builds none of it — the engine is
        # bit-for-bit the contiguous-slot engine, same program set.
        self._paged = self.cfg.page_size > 0
        # where the step attends with ``decode_attention`` over the slot
        # cache: a host mirror of the slots' lengths as the device's vector
        # has them (set at placement, advanced by one a step while the row
        # runs, 0 from its retirement on), from which the ``decode_step``
        # span says how far past the live positions the kernel fetched. No
        # device read
        self._kv_counts = self._flash and not self._paged \
            and self.kind.planes == ("k", "v")
        self._slot_len = np.zeros(self.cfg.slots, np.int64) \
            if self._kv_counts or self.kind.mirrors_lengths else None
        self.pool: Optional[PagePool] = None
        self._table = None
        self._table_dirty = False
        _kvs_on = self.cfg.kvscope is not None and self.cfg.kvscope.enabled
        # demote-ahead needs the same per-entry touch clock kvscope uses
        # (tree tstamps are the session-idleness signal at block grain)
        _da_on = self.cfg.demote_ahead_idle_s > 0
        if self._paged:
            self.pool = PagePool(self.cfg.pool_pages, self.cfg.page_size,
                                 self.cfg.max_len,
                                 registry=self.stats.registry,
                                 prefix_sharing=self.cfg.prefix_sharing,
                                 # the eviction-pressure ages are the
                                 # residency observatory's opt-in; the
                                 # default pool stays clock-free
                                 clock=self.stats.clock
                                 if (_kvs_on or _da_on) else None)
            # host-authoritative page tables, mirrored into the carry on
            # change (insert seats a row, retirement clears one): steady
            # full-slot decode uploads nothing
            self._table = np.zeros(
                (self.cfg.slots, self.pool.pages_per_slot), np.int32)
            if self.flight is not None:
                # stall dumps show the pool at the moment of the stall
                self.flight.add_snapshot_provider("pages",
                                                  self.pool.snapshot)
        # tiered host KV store (serving/hostkv.py, docs/SERVING.md):
        # eviction demotes cold tree-held pages to bounded pinned host
        # memory; admission restores matched cold prefixes by async H2D
        # copy instead of recompute. None (default) builds nothing —
        # one `is not None` per admission and per eviction pass, zero
        # new programs (the compile-freeze gates stay the oracle); ON it
        # adds exactly two fixed-shape programs (demote gather, restore
        # scatter) to the bounded set.
        self.hostkv = None
        # NVMe rung below the host tier + the ranked-store coordinator
        # (serving/tiering.py): built only when serving.nvme_pool_bytes
        # is set — otherwise pool.host is the bare host store, exactly
        # the PR-14 shape
        self.nvmekv = None
        self.kvtier = None
        # demote gathers dispatched this iteration, materialized to the
        # tier at the end of step() — see _demote_pages/_drain_demotes
        self._pending_demotes: list = []
        # demote-ahead lane (cfg.demote_ahead_idle_s): prefixes staged
        # into the tier while still tree-held, so eviction under
        # pressure is a refcount drop. demote_wait_s is the measured
        # admission-path demote-blocking wall the lane exists to zero.
        self._demote_ahead = (self.cfg.demote_ahead_idle_s
                              if _da_on else None)
        self._staged_ahead: set = set()
        self.demote_wait_s = 0.0
        if self._paged and self.cfg.host_pool_bytes > 0:
            from .hostkv import HostKVTier

            self.hostkv = HostKVTier(self.cfg.host_pool_bytes,
                                     self.cfg.page_size,
                                     registry=self.stats.registry,
                                     clock=self.stats.clock)
            self.pool.host = self.hostkv
            if self.cfg.nvme_pool_bytes > 0:
                from .tiering import NVMeKVTier, TieringEngine

                self.nvmekv = NVMeKVTier(self.cfg.nvme_pool_bytes,
                                         self.cfg.page_size,
                                         path=self.cfg.nvme_path,
                                         registry=self.stats.registry,
                                         clock=self.stats.clock)
                self.kvtier = TieringEngine([self.hostkv, self.nvmekv])
                self.pool.host = self.kvtier
            self.pool.on_demote = self._demote_pages
            if self.flight is not None:
                self.flight.add_snapshot_provider("host_kv",
                                                  self.hostkv.snapshot)
                if self.nvmekv is not None:
                    self.flight.add_snapshot_provider(
                        "nvme_kv", self.nvmekv.snapshot)
        # KV residency observatory (observability/kvscope.py,
        # docs/OBSERVABILITY.md): ghost-tree eviction-regret ledger on
        # the page pool + per-session lifecycle heat tracking + the
        # measured host-tier advisor inputs. None (default) builds
        # nothing — one `is not None` per admission/retirement and one
        # on the pool's eviction path; zero programs, zero syncs (the
        # compile-freeze gates stay the acceptance tests).
        self.kvscope = None
        if _kvs_on:
            from ..observability.capacity import kv_cache_bytes
            from ..observability.kvscope import KVScope

            ptb = None
            if self._paged:
                ptb = kv_cache_bytes(
                    mcfg, self.cfg.slots, self.cfg.max_len,
                    engine.compute_dtype, page_size=self.cfg.page_size,
                    pool_pages=self.cfg.pool_pages,
                    kv_quant_bits=self.cfg.kv_quant_bits,
                )["per_token_bytes"]
            pool = self.pool
            self.kvscope = KVScope(
                self.cfg.kvscope, registry=self.stats.registry,
                clock=self.stats.clock, spans=self.spans,
                page_size=self.cfg.page_size, per_token_bytes=ptb,
                # pool truth for "reclaimable now": idle-session sums
                # are capped at the tree's live residency
                tree_held_tokens=(
                    (lambda: pool.tree_held * self.cfg.page_size)
                    if pool is not None else None))
            if self.pool is not None:
                self.pool.on_evict = self.kvscope.on_evictions
            if self.flight is not None:
                self.flight.add_snapshot_provider("kv_residency",
                                                  self.kvscope.snapshot)
        # Per-tenant cost attribution & fairness observatory
        # (observability/tenantscope.py, docs/OBSERVABILITY.md): a
        # ledger keyed by Request.tenant_id on the injectable clock —
        # tokens/latency at the retirement funnel, KV page-seconds
        # through the pool's on_pages hook, resident tier bytes through
        # TierStore owner accounting, Jain fairness + the edge-triggered
        # noisy-neighbor detector (flight why-marker + incident
        # breakdown artifact). None (default) builds nothing — one
        # `is not None` per submit/admission/retirement, zero programs,
        # zero syncs (the compile-freeze gates stay the oracle).
        self.tenantscope = None
        if self.cfg.tenantscope is not None and self.cfg.tenantscope.enabled:
            from ..observability.tenantscope import TenantScope

            self.tenantscope = TenantScope(
                self.cfg.tenantscope, registry=self.stats.registry,
                clock=self.stats.clock, flight=self.flight,
                page_size=self.cfg.page_size)
            if self.pool is not None:
                self.pool.on_pages = self.tenantscope.on_pages
            if self.flight is not None:
                # every flight/incident dump carries the per-tenant
                # breakdown — the noisy-neighbor episode's evidence
                self.flight.add_artifact_provider(
                    "tenant_breakdown.json",
                    self.tenantscope.breakdown_text)
        self.sched = Scheduler(self.cfg.slots, self.cfg.max_len,
                               self.cfg.prefill_chunk,
                               max_queue=self.cfg.max_queue,
                               eos_token_id=self._eos, stats=self.stats,
                               ttft_deadline_s=self.cfg.ttft_deadline_s,
                               total_deadline_s=self.cfg.total_deadline_s,
                               spans=self.spans, pages=self.pool,
                               rid_source=rid_source,
                               recurrent=self.kind.recurrent)
        self._programs: OrderedDict = \
            programs if programs is not None else OrderedDict()
        # disaggregated-serving hook (serving/fleet.py): a side-effecting
        # callback invoked right after a prefill lands in a slot with
        # (req, slot). The fleet's handler takes the request over INSIDE
        # the call (export_request + release_request), so by the time
        # this step reaches its decode lane the request is gone; the
        # return value is ignored. None (default) costs one `is not
        # None` per placement.
        self.on_placed = None
        # ``_prog`` builds: ``jax.jit`` wrappers made, bounded in steady
        # state. Not compilations: a wrapper compiles once per argument
        # signature it meets (Serve/retraces counts those beyond the first;
        # the COMPILE spans name every one)
        self.compiles = 0
        # finished requests awaiting pickup, BOUNDED (oldest evicted): a
        # server whose caller consumes step()'s return values — or
        # pop_result() — never grows this; one that ignores results still
        # can't leak without bound
        self.results: OrderedDict[int, Request] = OrderedDict()
        self._max_results = _MAX_RESULTS
        # (request, chunk plan, next chunk idx, device prefill cache, rng)
        self._prefill = None
        # (chunk, its program's output, its span): the lane's next chunk
        # as _lane_dispatch left it for _prefill_advance, behind the last
        # decode step or in front of this one
        self._ahead = None
        # (a scalar of this iteration's insert program, the batch-1 cache
        # it read): an admission behind the iteration's step waits for the
        # one and frees the other (_admit)
        self._seated = None
        # the step dispatched and not yet read back (a _Flight): the books
        # run one step behind the device (docs/SERVING.md, "The host loop")
        self._inflight: Optional[_Flight] = None
        # (request, its final chunk's batch-1 carry): a first token the
        # host has yet to read, this iteration
        self._first = None
        # what a settle retired between two iterations: the next step()
        # hands it back
        self._late: list = []
        self._read_t = float("-inf")    # when the last step was read back
        self._stall_excess = 0.0
        # resilience state: chaos only exists when explicitly enabled —
        # disabled serving carries a single `is not None` check per step
        self.chaos: Optional[ChaosMonkey] = None
        if self.cfg.chaos is not None and self.cfg.chaos.enabled:
            self.chaos = ChaosMonkey(self.cfg.chaos)
        self._draining = False
        self._any_deadlines = False
        self._last_step_s = 0.0
        self._last_stall_iter: Optional[int] = None
        self._iterations = 0
        # the open iteration's row of the seam's record (always on)
        self._row = _spans.Iteration()
        # _count_retraces: the process's trace count at its last walk of
        # the programs, and when that was
        self._traces_seen = -1
        self._retraces_looked = _spans.now()
        # readable process-wide at 0 too (see _count_retraces; the second
        # counts in forward_with_cache, where a step program is traced)
        get_registry().counter("Serve/retraces")
        get_registry().counter("Serve/decode_fallback_builds")
        get_registry().counter("Serve/chunk_attention_fallback_builds")
        with self.engine.mesh:
            if self._paged:
                self._state = self._prog("init_slots", lambda: jax.jit(
                    lambda: init_paged_slots(
                        mcfg, self.cfg.slots, self.cfg.max_len,
                        self.cfg.page_size, self.cfg.pool_pages,
                        engine.compute_dtype, self.cfg.kv_quant_bits)))()
            else:
                self._state = self._prog("init_slots", lambda: jax.jit(
                    lambda: init_slots(mcfg, self.cfg.slots,
                                       self.cfg.max_len,
                                       engine.compute_dtype)))()
        tcfg = self.cfg.telemetry
        if tcfg is not None and tcfg.enabled:
            self.serve_telemetry(port=tcfg.port, host=tcfg.host,
                                 token=tcfg.token)

    def _build_slo(self, slo) -> None:
        """(Re)build the SLO scorer + anomaly detectors from a
        :class:`~..observability.slo.SLOConfig` (or None). Shared by
        __init__ and the live ``/slo/reload`` control endpoint."""
        self.slo = None
        self._step_anomaly = None
        self._compile_storm = None
        if slo is not None and slo.any_enabled:
            from ..observability.slo import (CompileStormDetector,
                                            MedianMADDetector, SLOScorer)

            self.slo = SLOScorer(slo, self.stats.registry,
                                 flight=self.flight)
            if slo.step_time_mad_k:
                self._step_anomaly = MedianMADDetector(
                    slo.step_time_mad_k, slo.step_time_window,
                    slo.step_time_min_samples)
            if slo.compile_storm_threshold:
                self._compile_storm = CompileStormDetector(
                    slo.compile_storm_threshold, slo.compile_storm_window,
                    slo.compile_storm_grace)

    def reload_slo(self, cfg) -> dict:
        """Swap the SLO config live (the ``POST /slo/reload`` hook): a
        None/empty ``cfg`` tears the scoring machinery down, a dict
        builds it exactly as __init__ would. Burn gauges and the
        violation counter carry over (same registry); detectors restart
        with fresh windows. Raises ``ValueError`` on unknown keys — the
        endpoint maps that to a 400, nothing half-applies."""
        import dataclasses as _dc

        from ..observability.slo import SLOConfig

        slo = SLOConfig.from_any(cfg) if cfg else None
        self.cfg.slo = slo
        self._build_slo(slo)
        return {"reloaded": True, "enabled": self.slo is not None,
                "slo": _dc.asdict(slo) if slo is not None else None}

    def _capture_meta(self) -> dict:
        """Trace-header meta via the ONE shared builder
        (:func:`~..observability.replay.capture_meta`) — the recorded
        config a faithful replay must match."""
        from ..observability.replay import capture_meta

        return capture_meta(self.cfg, engine=self.name or "serving")

    def attach_capture(self, capture) -> None:
        """Adopt a :class:`~..observability.replay.TrafficCapture` (the
        config path builds one automatically when ``serving.capture`` is
        set; tests and benches may attach their own). When a flight
        recorder exists, the capture ring's tail rides every dump as
        ``traffic_trace.jsonl``."""
        self.capture = capture
        if self.flight is not None and capture is not None:
            self.flight.add_artifact_provider("traffic_trace.jsonl",
                                              capture.tail_text)

    def _flush_table(self) -> None:
        """Mirror the host page tables into the decode carry when they
        changed (a row seated at insert, or cleared at retirement before
        its pages can be reused). A handful of int32s per event — steady
        full-slot decode uploads nothing."""
        if self._table_dirty:
            c = self._state.cache
            self._state = self._state._replace(
                cache=c._replace(page_table=jnp.asarray(self._table)))
            self._table_dirty = False

    # ----------------------------------------------------------- programs
    def _prog(self, key, build):
        """InferenceEngine._cached's bounded LRU + a build counter (one
        ``jax.jit`` wrapper made per key; what it compiles is per argument
        signature, see ``_count_retraces`` — the smoke test asserts the
        count freezes after warmup)."""
        def counted():
            self.compiles += 1
            return build()

        return InferenceEngine._cached(self._programs, key, counted,
                                       cap=_MAX_PROGRAMS)

    def _count_retraces(self) -> None:
        """``Serve/retraces``: signatures a built program has been traced
        for beyond its first. ``compiles`` counts ``_prog`` builds; a
        ``jax.jit`` handed an argument of another type, layout or
        placement traces and compiles again inside the same build, and
        only its own cache shows that. Counted always (warm-up is where
        retraces happen), in this engine's registry and the process-wide
        one, and a RETRACE instant in the lifecycle ring names the program
        and says what the new signature cost (``why``). Every new
        signature starts with a trace, so the programs are walked only at
        the end of an iteration in which the process traced something
        (``_spans.traces()``); steady state pays one comparison."""
        traced = _spans.traces()
        if traced == self._traces_seen:
            return
        self._traces_seen = traced
        looked, self._retraces_looked = self._retraces_looked, _spans.now()
        for key, fn in self._programs.items():
            n = fn._cache_size()
            seen = _SIGNATURES.get(fn, 1)     # the first is the build's
            if n <= seen:
                continue
            _SIGNATURES[fn] = n
            for reg in (self.stats.registry, get_registry()):
                reg.counter("Serve/retraces").inc(n - seen)
            module = _spans.module_name(
                getattr(fn, "__name__", "?"), "trace")
            _spans.instant(self.spans, _spans.now, _spans.RETRACE,
                           program=str(key), signatures=n, new=n - seen,
                           step=self._iterations, module=module,
                           why=_spans.compiled_since(looked, module))

    def _chunk_impl(self, params, cache, ids, start):
        """Intermediate prefill chunk: extend the request cache; the head
        is never computed (nothing consumes the logits, XLA removes it)."""
        cache = cache._replace(length=start)
        mat = self._mat if self._mat is not None else (lambda p: p)
        _, cache, stats, routing, passes = forward_with_cache(
            self.model, mat(params), ids, cache, flash_decode=self._flash,
            with_stats=True, with_routing=True, with_passes=True)
        if self.kind.exit_pdf:
            return cache, _mean_exit_pdf(passes, ids.shape[1]), None
        return (cache, stats, routing) if self.kind.moe_stats else cache

    def _final_impl(self, params, cache, ids, start, last_index, true_len,
                    rng):
        """Final prefill chunk: extend the cache AND sample the first token
        from the last real position (``last_index`` — right-padded buckets
        put it before the chunk end), leaving the cache at ``true_len``."""
        cache = cache._replace(length=start)
        mat = self._mat if self._mat is not None else (lambda p: p)
        logits, cache, stats, routing, passes = forward_with_cache(
            self.model, mat(params), ids, cache, flash_decode=self._flash,
            last_token_head=True, last_index=last_index, with_stats=True,
            with_routing=True, with_passes=True)
        rng, sub = split_keys(rng)
        tok = self._sampler(logits[:, -1], sub)
        done = (tok == self._eos) if self._eos is not None \
            else jnp.zeros(tok.shape, bool)
        pf = GenCarry(tok=tok, cache=self.kind.rewound(cache, true_len),
                      rng=rng, done=done)
        if self.kind.exit_pdf:
            return pf, _mean_exit_pdf(passes, last_index + 1), None
        return (pf, stats, routing) if self.kind.moe_stats else pf

    def _step_impl(self, params, carry):
        """The slot step: ``(carry, read)``. ``read`` is what the host
        reads of a step — the tokens, the ``done`` flags, the (B,) per-row
        logit finiteness (logit_guard) and, where the trunk has them, the
        expert layers' counters and choices (moe_stats) or a looped
        trunk's exit distribution a slot (exit_pdf) — as outputs of their
        own: the carry they would otherwise be read from goes into the
        next step, donated, before the host reads this one
        (:meth:`_launch`). One fused read-back a step, whatever it
        carries."""
        out, *read = decode_step(
            self.model, params, carry, sampler=self._sampler,
            eos_token_id=self._eos, flash_decode=self._flash,
            logit_guard=True, moe_stats=self.kind.moe_stats,
            exit_pdf=self.kind.exit_pdf)
        return out, (out.tok, out.done, *read)

    def _step_chaos_impl(self, params, carry, poison_row):
        """Chaos build of the step: identical program + a traced poison-row
        scalar (-1 = clean; `where` on a false mask is bit-exact), so one
        compiled program covers every iteration of a chaos run."""
        out, ok = decode_step(
            self.model, params, carry, sampler=self._sampler,
            eos_token_id=self._eos, flash_decode=self._flash,
            logit_guard=True, poison_row=poison_row)
        return out, (out.tok, out.done, ok)

    # --------------------------------------------------- self-speculation
    def _spec_verify_impl(self, params, carry, drafts):
        """The fixed-shape verify forward: every slot's carried token +
        its (zero-padded) drafts run as ONE length-(max_draft + 1) call
        through the same ``forward_with_cache`` the chunked prefill uses
        — acceptance counts are host-side data, so this is the only
        decode-side shape speculation ever compiles. ``argmax`` over the
        fp32 logits IS the greedy sampler (``sample_logits`` with
        ``greedy=True``), so position j's winner is bit-identical to the
        token the plain step would sample after committing positions
        < j. Raw (possibly WOQ-quantized) params, exactly like
        ``_step_impl`` — the verify logits must match the plain step's
        bitwise. The per-row finiteness flags ride the same fused
        read-back as the winners (logit_guard discipline)."""
        ids = jnp.concatenate([carry.tok[:, None], drafts], axis=1)
        logits, cache = forward_with_cache(self.model, params, ids,
                                           carry.cache)
        m = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        return m, ok, carry._replace(cache=cache)

    def _spec_commit_impl(self, carry, packed):
        """Resolve the host-side acceptance: active rows rewind their
        cache length to the committed extent (rejected drafts' KV past
        it is dead by length — every future append overwrites position
        == committed length first) and take their new carry token, done
        flag and tokens left; a row whose request ends with this commit
        (eos, or nothing left) is ``done`` at length 0, as the plain step
        leaves it. Inactive rows are the slots that were not running,
        which the verify forward left at length 0, and the non-finite
        rows, which ``_spec_resolve`` retires next (``_unseat``).

        ``packed`` is one (5, slots) int32 — active / new_len / new_tok /
        new_done / new_left rows — so the commit costs a single
        host->device upload per step instead of five."""
        active = packed[0].astype(bool)
        new_len, new_tok = packed[1], packed[2]
        new_done = packed[3].astype(bool)
        cache = carry.cache
        length = jnp.where(active, new_len, cache.length)
        tok = jnp.where(active, new_tok, carry.tok)
        done = jnp.where(active, new_done, carry.done)
        left = jnp.where(active, packed[4], carry.left)
        return carry._replace(tok=tok, done=done, left=left,
                              cache=cache._replace(length=length))

    def _spec_plan(self):
        """Build this step's draft matrix from the per-slot n-gram
        tables, or None to fall back to the plain step. Host-side only.

        Fallbacks: (a) headroom — EVERY occupied slot must fit the full
        verify write extent (``live + max_draft + 1 <= max_len``),
        because both cache layouts clamp out-of-range writes in ways
        that would fold onto live positions; (b) no slot drafted
        anything — a verify forward with zero drafts is a plain step at
        (max_draft + 1)x the FLOPs.

        Drafter tables sync lazily against ``prompt + tokens`` (the
        ``tokens_fed`` watermark), so plain-step fallbacks, fleet
        adoption, and requeues need no hooks."""
        spec = self._spec
        K = spec.max_draft
        running = self.sched.running
        tables = self._spec_tables
        for slot in list(tables):
            req = running.get(slot)
            if req is None or tables[slot][0] != req.rid:
                del tables[slot]
        drafts = np.zeros((self.cfg.slots, K), np.int32)
        lens = np.zeros(self.cfg.slots, np.int32)
        any_draft = False
        for slot, req in running.items():
            P = len(req.prompt)
            total = P + len(req.tokens)
            if total - 1 + K + 1 > self.cfg.max_len:
                return None
            ent = tables.get(slot)
            if ent is None:
                tab = NGramTable(spec.ngram)
                tab.extend(np.asarray(req.prompt).reshape(-1).tolist())
                tab.extend(req.tokens)
                tables[slot] = [req.rid, tab, total]
            else:
                tab = ent[1]
                if ent[2] < total:
                    tab.extend(req.tokens[ent[2] - P:])
                    ent[2] = total
            cap = min(K, req.max_new - len(req.tokens) - 1)
            if cap <= 0:
                continue
            d = tables[slot][1].draft(cap)
            if d:
                drafts[slot, :len(d)] = d
                lens[slot] = len(d)
                any_draft = True
        return (drafts, lens) if any_draft else None

    def _spec_verify_commit(self, plan):
        """Run the verify forward, resolve per-slot acceptance host-side,
        and commit the accepted extents — the speculative decode lane's
        device work, all inside the caller's watchdog window. ONE fused
        read-back (winners + finiteness flags), same discipline as the
        plain step's. Returns ``(emitted, bad, tallies)`` for
        :meth:`_spec_resolve` to feed the scheduler AFTER the timing
        bookkeeping, exactly where ``on_step`` runs in the plain lane."""
        drafts, lens = plan
        ver = self._prog("spec_verify", lambda: jax.jit(
            self._spec_verify_impl, donate_argnums=(1,)))
        m_dev, ok_dev, self._state = ver(self.engine.params, self._state,
                                         jnp.asarray(drafts))
        m, vok = self._row.wait(jax.device_get, (m_dev, ok_dev))
        self._row.read_step = 1
        eos = self._eos
        B = self.cfg.slots
        # rows: active, new_len, new_tok, new_done, new_left — one packed
        # upload
        packed = np.zeros((5, B), np.int32)
        active, new_len, new_tok, new_done, new_left = packed
        emitted: dict = {}
        bad: list = []
        proposed = accepted = first_scored = first_hits = 0
        for slot, req in self.sched.running.items():
            if not bool(vok[slot]):
                bad.append(slot)
                continue
            dlen = int(lens[slot])
            toks = [int(m[slot, 0])]
            j = 0
            # the acceptance chain: draft j survives iff it equals the
            # verified winner at j-1 (then winner j is the next plain
            # token); stop at the first miss or at eos — emissions past
            # eos would diverge from the plain lane's retirement
            while j < dlen and (eos is None or toks[-1] != eos) \
                    and int(drafts[slot, j]) == toks[-1]:
                toks.append(int(m[slot, j + 1]))
                j += 1
            proposed += dlen
            accepted += j
            if dlen:
                first_scored += 1
                if int(drafts[slot, 0]) == toks[0]:
                    first_hits += 1
            live = len(req.prompt) + len(req.tokens) - 1
            active[slot] = True
            new_tok[slot] = toks[-1]
            new_left[slot] = req.max_new - len(req.tokens) - len(toks)
            # on_spec_step's predicate: the request ends with this commit
            new_done[slot] = new_left[slot] <= 0 \
                or (eos is not None and toks[-1] == eos)
            new_len[slot] = 0 if new_done[slot] else live + len(toks)
            emitted[slot] = toks
        com = self._prog("spec_commit", lambda: jax.jit(
            self._spec_commit_impl, donate_argnums=(0,)))
        self._state = com(self._state, jnp.asarray(packed))
        return emitted, bad, (proposed, accepted, first_scored, first_hits)

    def _spec_resolve(self, spec_out) -> list:
        """Scheduler + metrics half of the speculative lane: retire
        nonfinite rows first (before their garbage could be appended),
        commit every surviving slot's emissions (page-table rollback for
        paged retirements happens inside ``on_spec_step``), and account
        the step."""
        emitted, bad, (proposed, accepted, first_scored, first_hits) = \
            spec_out
        finished: list = []
        if bad:
            finished += self.sched.retire_nonfinite(bad)
            self._unseat(bad)
            for slot in bad:
                self._spec_tables.pop(slot, None)
        n_emitted = sum(len(t) for t in emitted.values())
        self._decode_slot_steps += len(emitted) + len(bad)
        self._decode_emitted += n_emitted
        finished += self.sched.on_spec_step(emitted)
        self._spec_steps += 1
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._spec_first_scored += first_scored
        self._spec_first_hits += first_hits
        r = self.stats.registry
        r.counter("Serve/spec_steps").inc()
        r.counter("Serve/spec_draft_tokens").inc(proposed)
        r.counter("Serve/spec_accepted_tokens").inc(accepted)
        r.counter("Serve/spec_emitted_tokens").inc(n_emitted)
        if self.workload is not None:
            self.workload.on_spec(proposed, accepted, n_emitted,
                                  first_scored, first_hits)
        return finished

    def spec_snapshot(self) -> Optional[dict]:
        """Live speculation readout (None when the lane is off): the
        accepted-tokens-per-step multiple over BOTH lanes (plain steps
        count 1 token per slot, so the ratio is the wall-clock decode
        multiple), the draft acceptance rates, and the raw tallies the
        fleet rollup sums."""
        if self._spec is None:
            return None
        steps = self._decode_slot_steps
        return {
            "ngram": self._spec.ngram,
            "max_draft": self._spec.max_draft,
            "verify_steps": self._spec_steps,
            "proposed_tokens": self._spec_proposed,
            "accepted_tokens": self._spec_accepted,
            "slot_steps": steps,
            "emitted_tokens": self._decode_emitted,
            "accepted_tokens_per_step":
                (self._decode_emitted / steps) if steps else None,
            "accept_rate":
                (self._spec_accepted / self._spec_proposed)
                if self._spec_proposed else None,
            "first_accept_rate":
                (self._spec_first_hits / self._spec_first_scored)
                if self._spec_first_scored else None,
        }

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               seed: int = 0, ttft_deadline_s: Optional[float] = None,
               total_deadline_s: Optional[float] = None,
               session_id=None, tenant_id=None) -> int:
        """Queue one request; returns its request id. Tokens sample with
        a per-request RNG folded from ``seed`` — bit-identical (up to eos
        truncation) to ``engine.generate(prompt[None], max_new,
        request_seeds=[seed], cache_len=<serving max_len>, ...)`` with the
        same sampling knobs; ``cache_len`` must match because the cache
        width is part of the sampled bit-stream.

        ``ttft_deadline_s`` / ``total_deadline_s`` override the config
        defaults for this request (0 disables); ``session_id`` (opaque,
        hashable) keys session-lifecycle tracking (kvscope / workload)
        and fleet affinity; ``tenant_id`` (optional string, default
        ``"default"``) is the cost-attribution dimension
        (observability/tenantscope.py). Raises
        :class:`~..resilience.guards.QueueFullError` (status ``SHED``)
        when the queue is at ``max_queue`` or the engine is draining."""
        with self._span(_spans.SRV_SUBMIT) as sp:
            if self._draining:
                self.stats.on_shed(self.sched.queue_depth)
                if self.tenantscope is not None:
                    self.tenantscope.on_shed(tenant_id)
                raise QueueFullError(
                    "serving engine is draining; request shed",
                    queue_depth=self.sched.queue_depth,
                    max_queue=self.cfg.max_queue)
            max_new = int(max_new_tokens
                          or self.engine.config.max_out_tokens)
            try:
                req = self.sched.submit(prompt, max_new, seed,
                                        ttft_deadline_s=ttft_deadline_s,
                                        total_deadline_s=total_deadline_s,
                                        session_id=session_id,
                                        tenant_id=tenant_id)
            except QueueFullError:
                # typed shed (queue full / pool can never fit it): billed
                # to the tenant even though no Request object exists yet
                if self.tenantscope is not None:
                    self.tenantscope.on_shed(tenant_id)
                raise
            if req.deadline_ttft is not None \
                    or req.deadline_total is not None:
                self._any_deadlines = True
            if self.capture is not None:
                # record the OVERRIDES as passed (None = config default),
                # so replay under the same config reproduces deadline
                # semantics
                self.capture.on_submit(req, ttft_deadline_s=ttft_deadline_s,
                                       total_deadline_s=total_deadline_s)
            if self.loadscope is not None:
                self.loadscope.on_submit(len(req.prompt), req.max_new,
                                         self.sched.queue_depth)
            if self.tenantscope is not None:
                self.tenantscope.on_submit(req)
            sp.note(rid=req.rid)
        return req.rid

    def requeue(self, req: Request) -> Request:
        """Failover intake (serving/fleet.py): adopt a request whose
        replica was lost — typed ``REQUEUED`` transition via the
        scheduler, plus the engine-side deadline bookkeeping a normal
        ``submit`` would have done (the requeued request keeps its
        ORIGINAL absolute deadlines; this engine's sweep must see
        them). Bypasses ``max_queue`` and the drain gate: failover work
        is already-admitted work, not new intake."""
        self.sched.requeue(req)
        if req.deadline_ttft is not None or req.deadline_total is not None:
            self._any_deadlines = True
        if self.tenantscope is not None:
            self.tenantscope.on_requeue(req)
        return req

    def cancel(self, rid: int) -> Optional[Request]:
        """Cancel a request wherever it currently lives — queue, prefill
        lane, or decode slot. Returns the request (status ``CANCELLED``,
        also placed in ``results``) or None if it already finished / is
        unknown (a request the step in flight ended is finished: the
        settle books it first)."""
        self._settle()
        if self._prefill is not None and self._prefill[0].rid == rid:
            req = self._prefill[0]
            self._prefill = None
            self.sched.abort(req, RequestStatus.CANCELLED,
                             "cancelled during prefill")
        else:
            req = self.sched.cancel(rid)
        if req is not None:
            self._unseat([req.slot])
            self._store_result(req)
        return req

    # ------------------------------------------------------------ serving
    def step(self) -> list[Request]:
        """One serving iteration: deadline sweep + <= 1 prefill chunk + 1
        decode step over the occupied slots. Returns requests that
        finished — normally (status ``OK``) or through a guard
        (``TIMEOUT`` / ``NONFINITE``); all are also kept in ``results``.
        **The books run one step behind the device** (docs/SERVING.md,
        "The host loop"): this call dispatches its step BEFORE it reads
        the step the call before dispatched, so what it returns are the
        requests that step ended (and those that ended at prefill, and
        whatever a settle retired since). Chaos disabled adds nothing to
        the device work and no host syncs beyond a step's one fused
        read-back.

        The iteration is one ``srv.step`` span and its phases are its
        children, disjoint and in this order: ``deadlines``, ``admit``,
        ``prefill_chunk``, ``place`` (the seat, behind its chunk),
        ``decode_dispatch``, ``decode_readback`` and ``retire`` (of the
        step before), ``prefill_readback`` (the seat's first token),
        ``tail`` (observability/spans.py; the span less the children is
        the loop's own time). Where a request was waiting with a slot
        free, or the lane had a further chunk, when the step went out,
        ``admit`` and ``prefill_chunk`` (``ahead`` 1) stand between
        ``decode_dispatch`` and ``decode_readback`` (``_lane_dispatch``);
        in front of the step they are the fallback for a request that
        arrived, or a slot that was freed, since. A serial engine
        (``_serial``) reads its step at once, and its seat follows its
        ``prefill_readback`` as it always did."""
        with self._span(_spans.SRV_STEP, step=self._iterations):
            # the iteration's row (observability/spans.py, always on): its
            # stamps lie one clock read inside the span's own
            self._row.open(self._iterations, self.compiles,
                           self._decode_emitted)
            done = self._iterate()
            self._row.close()
        self._row.write(self.compiles, len(self.sched.running),
                        self.sched.queue_depth, self._decode_emitted)
        return done

    def _span(self, kind, **fields):
        """The seam (observability/spans.py) on this engine's ring and
        clock: every timed piece of host code in this file goes through
        here."""
        return _spans.span(self.spans, self.stats.clock, kind, **fields)

    def _attn_counts(self, fl: "_Flight") -> dict:
        """Of a step, two ratios in which 1 is ideal, from the mirror of
        the device's lengths as the step had them (``fl.lens``, with
        ``fl.rows`` the rows it ran). ``attn_fetched_over_live``: the
        positions ``decode_attention`` fetches (every slot's length
        rounded up to the kernel's block) over those the running requests
        attend to. ``append_moved_over_new``: the bytes the kernel moves
        between HBM and VMEM to append (the block of 128 positions it
        writes back for every slot whose length is over 0; the block's
        read is the attention's own fetch; where the cache keeps a deferred
        tail of T rows, ``kinds/dense.py``, the slot's tile in and out and
        the block only where the step completed a group of T: 2 T + 128 / T
        in the mean, 40 at T = 16) over the bytes of the running
        requests' new K/V: a ratio of positions, since both are K and V of
        every head and layer. And ``idle_fetched``: the positions of those
        fetched that belong to no running request, 0 while every row that
        is not running stands at length 0. ``attn_blocks_per_turn``: the
        live blocks the slots fetch over the loop turns the kernel takes
        for them (``blocks_per_turn`` of a turn, fewer of a slot's last):
        1 where the heads fill a turn with one block. {} of a step that
        ran no row (the device had retired them all before the host
        knew)."""
        ran = list(fl.rows)
        if not ran:
            return {}
        blocks = -(-fl.lens // LANES)
        fetched = blocks * LANES
        live, T = fl.lens > 0, self._tail_rows
        written = LANES * np.count_nonzero(
            live & (fl.lens % T == 0) if T else live) \
            + 2 * T * np.count_nonzero(live)
        total = fetched.sum()
        turns = -(-blocks // self._blocks_per_turn)
        return {"attn_fetched_over_live": float(total / fl.lens[ran].sum()),
                "append_moved_over_new": float(written / len(ran)),
                "idle_fetched": int(total - fetched[ran].sum()),
                "attn_blocks_per_turn": float(blocks.sum() / turns.sum())}

    @cached_property
    def _tail_rows(self) -> int:
        """T of the slot cache's deferred tail, 0 where it has none."""
        return self.kind.deferred_rows(self._state.cache.k.dtype)

    @cached_property
    def _blocks_per_turn(self) -> int:
        """The kernel's own W (``ops/decode_attention.py``) at the shapes
        it sees: a shard's of the cache's K and V planes (of a trunk with
        two kinds of attention layers, the full layers')."""
        k, v = self._state.cache.k, self._state.cache.v
        _, _, KV, hd, S = k.sharding.shard_shape(k.shape)
        return blocks_per_turn(KV, hd, v.shape[3], S, k.dtype)

    def _log_routing(self, step, tapped: list, chunks: list,
                     rows: dict) -> None:
        """Into ``routing_log``: the chunks' choices (their real tokens,
        at the positions they wrote), then this step's, one position for
        every request it is booked to (``rows``, before the booking):
        that of the token it was fed."""
        log = self.routing_log
        # (a kind's routing may be several arrays, each (layers, B, T, .))
        for (rid, start, _, real), routing in zip(tapped, chunks):
            log.setdefault(rid, []).append((start, jax.tree.map(
                lambda r: r[:, 0, :real], routing)))
        for slot, req in rows.items():
            log.setdefault(req.rid, []).append(
                (req.prompt_len + len(req.tokens) - 1,
                 jax.tree.map(lambda r: r[:, slot], step)))

    @property
    def _serial(self) -> bool:
        """Whether every step is read back before anything else goes out
        (docs/SERVING.md, "The host loop"): with speculation the next
        step's drafts are made from this step's tokens on the host, and
        chaos chooses its poison row and its hang a step at a time."""
        return self._spec is not None or self.chaos is not None

    def _iterate(self) -> list[Request]:
        n_it = self._iterations
        finished: list[Request] = []
        ran_chunk = ran_decode = read_step = False
        self._stall_excess = 0.0
        gp = self.goodput
        if gp is not None:
            # the iteration window: two clock reads (entry/exit) — the
            # ledger's whole hot-path cost; None (default) pays nothing
            gp_t0 = gp.clock()
            gp_compiles0 = self.compiles
        chaos = self.chaos
        if chaos is not None:
            it = chaos.on_iteration()
            if it == 0 and chaos.cfg.flood_submits:
                self._chaos_flood(chaos.cfg.flood_submits)
        # deadline sweep FIRST: an expired queued request never spends a
        # prefill chunk, an expired running one frees its slot for this
        # very iteration's admission. _any_deadlines means some live or
        # past request carried one — a deadline-free server never pays
        # the sweep (or its clock read)
        if self._any_deadlines:
            with self._span(_spans.SRV_DEADLINES, step=n_it):
                finished += self._expire_deadlines()
        with self.engine.mesh:
            if self._paged:
                # retired rows cleared last iteration must reach the
                # device BEFORE their pages can be reused by this
                # iteration's admission or written by this decode step
                self._flush_table()
            # admission and the lane's chunk, in front of the step: the
            # fallback for what the last step's dispatch could not see
            self._lane_dispatch(n_it, ahead=0)
            # prefill lane: one bucket-shaped chunk per iteration
            if self._prefill is not None:
                finished += self._prefill_advance(n_it)
                ran_chunk = True
            # decode lane: every occupied slot advances one token — or,
            # with speculation on, up to max_draft + 1 through one
            # fixed-shape verify forward (chaos keeps the plain step:
            # poison-row semantics are per-token). The plain step goes out
            # BEFORE the step in front of it is read (docs/SERVING.md,
            # "The host loop"; _serial engines read at once)
            due, lane_s = None, 0.0
            if self.sched.running:
                t0 = self.stats.clock()
                plan = fl = None
                self._row.stepped = 1
                self._row.slots = len(self.sched.running)
                with self._span(_spans.SRV_DECODE_DISPATCH, step=n_it):
                    if chaos is not None:
                        chaos.maybe_hang(it)
                        fl = self._launch(
                            n_it, t0, "step_chaos", self._step_chaos_impl,
                            jnp.int32(chaos.poison_slot(
                                self.sched.running.keys())))
                    else:
                        if self._spec is not None:
                            plan = self._spec_plan()
                        if plan is None:
                            fl = self._launch(n_it, t0, "step",
                                              self._step_impl)
                if plan is None and chaos is None:
                    # next iteration's admission and chunk, behind the step
                    lane_t0 = self.stats.clock()
                    self._lane_dispatch(n_it, ahead=1)
                    lane_s = self.stats.clock() - lane_t0
                if plan is not None:
                    finished += self._spec_step(plan, n_it, t0)
                    read_step = True
                elif self._serial:
                    due = fl
                else:
                    # the books run one step behind: what is read now is
                    # the step that was in flight when this one went out
                    due, self._inflight = self._inflight, fl
                ran_decode = True
            else:
                # nothing left running by the books: the last step out, if
                # one is, ran rows the device had retired already
                due, self._inflight = self._inflight, None
            if due is not None:
                finished += self._book(due, n_it, lane_s)
                read_step = True
            if self._first is not None:
                finished += self._first_token(n_it)
        with self._span(_spans.SRV_TAIL, step=n_it):
            if self._seated is not None and self._seated[0].is_ready():
                self._seated = None     # inserted: let its cache go
            if self._demote_ahead is not None:
                # background demotion lane: stage idle tree-held pages
                # into the tier BEFORE pressure (the staged gathers drain
                # with this same iteration's batch below)
                self._demote_ahead_tick()
            if self._pending_demotes:
                # off the TTFT path: the gathers dispatched at admission
                # land in the host tier after this iteration's device
                # work
                self._drain_demotes()
            self.stats.on_iteration(
                self.sched.queue_depth, self.sched.occupancy,
                self.cfg.slots, ran_chunk, ran_decode)
            _spans.instant(self.spans, self.stats.clock, _spans.OCCUPANCY,
                           queue_depth=self.sched.queue_depth,
                           occupancy=self.sched.occupancy)
            self._count_retraces()
            if self._compile_storm is not None:
                new = self._compile_storm.update(self._iterations,
                                                 self.compiles)
                if new:
                    self.stats.registry.counter(
                        "Serve/compile_storms").inc()
                    warning_once(
                        f"serving compile storm: {new} new programs within "
                        f"{self._compile_storm.window} iterations after "
                        "warmup — shape drift or program-cache eviction "
                        "(see docs/SERVING.md bucket tuning)")
                    if self.flight is not None:
                        self.flight.note("compile_storm", new_compiles=new,
                                         total_compiles=self.compiles,
                                         iteration=self._iterations)
            self._iterations += 1
            if gp is not None:
                gp.on_serving_iteration(
                    gp_t0, gp.clock(),
                    # the step this iteration READ: the device's time
                    decode_s=self._last_step_s if read_step else 0.0,
                    ran_decode=ran_decode or read_step, ran_chunk=ran_chunk,
                    compiled=self.compiles > gp_compiles0,
                    stall_excess_s=self._stall_excess,
                    draining=self._draining,
                    idle=self.sched.idle and self._prefill is None)
            for req in finished:
                self._store_result(req)
            # what a settle between two iterations retired (stored then)
            late, self._late = self._late, []
        return late + finished

    def _fetched(self, read: tuple) -> tuple:
        """Of a step's outputs (tok, done, ok, then the trunk's counters
        and, last, its routing) what the host fetches: the routing stays
        on the device unless ``routing_log`` taps it."""
        return read if self.routing_log is not None else read[:4]

    def _launch(self, n_it: int, t0: float, key: str, impl,
                *extra) -> "_Flight":
        """Dispatch one slot step (``key`` names its program) on the state
        the last program left, and say what the host will need to book it:
        the rows it runs, by the books of this moment (a snapshot: by the
        time the step is read a slot may hold a successor), the queue's
        depth, the mirror of the lengths it leaves, and the counters of
        the chunks that ran since the step before (they are ready when
        this step is). The outputs the host reads start their way to the
        host now."""
        step = self._prog(key, lambda: jax.jit(impl, donate_argnums=(1,)))
        self._state, read = step(self.engine.params, self._state, *extra)
        for out in jax.tree.leaves(self._fetched(read)):
            out.copy_to_host_async()
        lens = None
        if self._slot_len is not None:
            # a running row appends, then attends; the others stay
            self._slot_len[self._slot_len > 0] += 1
            lens = self._slot_len.copy()
        self._row.ahead = ahead = int(self._inflight is not None)
        if ahead:
            self.stats.registry.counter("Serve/decode_steps_ahead").inc()
        fl = _Flight(step=n_it, t0=t0, rows=dict(self.sched.running),
                     queue=self.sched.queue_depth, ahead=ahead, read=read,
                     lens=lens, chunk_stats=self._chunk_stats,
                     chunk_routing=self._chunk_routing)
        self._chunk_stats, self._chunk_routing = [], []
        return fl

    def _book(self, fl: "_Flight", n_it: int,
              lane_s: float = 0.0) -> list[Request]:
        """Read one step back and book it: its tokens to the requests its
        dispatch ran and the host has not retired since, its counters onto
        its ``decode_step`` span, its time to the watchdog. Returns the
        requests that ended with it."""
        finished: list[Request] = []
        running = self.sched.running
        with self._span(_spans.SRV_DECODE_READBACK, step=n_it):
            # ONE fused host read-back a step (tok + done + per-row
            # logit finiteness together): the per-step sync is the
            # scheduler's steering cost — don't pay it twice, and don't
            # let the guard add a second one
            # ... nor the expert layers' counters (this step's, and
            # those of the chunks dispatched since the step before) a
            # third
            pending = fl.chunk_stats
            tapped = fl.chunk_routing if self.routing_log is not None else []
            toks, dones, oks, *moe = self._row.wait(jax.device_get, (
                *self._fetched(fl.read), *(st for _, st, _ in pending),
                *(r for _, _, r, _ in tapped)))
            self._row.read_step = 1
            # a slot the device retired and the host seated again, a row
            # the host retired meanwhile: not this step's to book
            rows = {slot: req for slot, req in fl.rows.items()
                    if running.get(slot) is req}
            if self.routing_log is not None and moe:
                self._log_routing(
                    moe.pop(1), tapped,
                    [moe.pop() for _ in tapped][::-1], rows)
            counts = self._attn_counts(fl) if self._kv_counts else {}
            counts.update(
                self.kind.step_meta(moe, pending, fl.lens, fl.rows))
        self._time_step(fl.t0, lane_s, step=fl.step, slots=len(fl.rows),
                        queue=fl.queue, ahead=fl.ahead, **counts)
        with self._span(_spans.SRV_RETIRE, step=n_it):
            if not oks.all():
                # retire ONLY the poisoned rows, before on_step can
                # append their garbage tokens; every other slot's
                # bookkeeping (and output bits) is untouched
                bad = [int(s) for s in np.nonzero(~oks)[0] if int(s) in rows]
                finished += self.sched.retire_nonfinite(bad)
                self._unseat(bad)
                for slot in bad:
                    del rows[slot]
            self._decode_slot_steps += len(fl.rows)
            self._decode_emitted += len(rows)
            ended = self.sched.on_step(toks, dones, rows)
            # at eos or out of tokens: the step has put these rows at
            # length 0 itself, and the step behind it ran them so
            self._retired_on_device([r.slot for r in ended])
            finished += ended
        return finished

    def _retired_on_device(self, slots: list) -> None:
        """Rows the host has just learned the device retired itself (eos,
        the budget): the mirror follows, and the step in flight, which
        went out before the host knew, is corrected to what the device ran
        — these rows at length 0, not running."""
        if not slots:
            return
        fl = self._inflight
        if self._slot_len is not None:
            self._slot_len[slots] = 0
            if fl is not None:
                fl.lens[slots] = 0
        if fl is not None:
            for slot in slots:
                fl.rows.pop(slot, None)

    def _time_step(self, t0: float, lane_s: float, **fields) -> None:
        """A step has been read: its ``decode_step`` span and the
        watchdog's measurement. The span runs from the read-back in front
        of it (from the step's dispatch where that came later: the first
        step, a serial engine) to its own, which is when the device ran
        it; ``_last_step_s`` is that less the lane's host work in between
        (a tree match, a tiered restore's tiles: not the step's, however
        long it takes)."""
        t1 = self.stats.clock()
        t0 = max(t0, self._read_t)
        self._read_t = t1
        self._last_step_s = t1 - t0 - lane_s
        # the counts at the step's dispatch (slots decoding, requests
        # waiting; an expert trunk's rows and a latent cache's bytes) ride
        # on the span
        _spans.emit(self.spans, _spans.DECODE_STEP, t0, t1, **fields)
        wd = self.cfg.watchdog_s
        if wd and self._last_step_s > wd:
            # rising edge: the previous iteration was healthy. A
            # stall STORM (every step slow — threshold too low, or
            # a degraded device) must not burn the max_dumps
            # budget that a later terminal post-mortem (SIGTERM,
            # nonfinite halt) will need — dump once per episode,
            # mark every stall.
            new_episode = self._last_stall_iter != \
                self._iterations - 1
            self._last_stall_iter = self._iterations
            self._stall_excess = self._last_step_s - wd
            self.stats.on_watchdog_stall(self._last_step_s, wd)
            warning_once(
                f"serving watchdog: a decode step exceeded "
                f"{wd:.3f}s (see Serve/last_stall_s for the "
                "latest measurement; further stalls only count)")
            if self.flight is not None:
                # the black box IS the post-mortem: stamp why,
                # then freeze the last-N events + snapshots
                self.flight.note("watchdog_stall", t=t1,
                                 step_s=self._last_step_s,
                                 threshold_s=wd,
                                 cause=self._row.cause(self.compiles),
                                 iteration=self._iterations)
                if new_episode:
                    self.flight.dump("watchdog_stall")
        if self._step_anomaly is not None \
                and self._step_anomaly.observe(self._last_step_s):
            r = self.stats.registry
            r.counter("Serve/step_time_regressions").inc()
            med, mad = self._step_anomaly.stats()
            r.gauge("Serve/step_time_baseline_s").set(med)
            if self.flight is not None:
                self.flight.note("step_time_regression", t=t1,
                                 step_s=self._last_step_s,
                                 cause=self._row.cause(self.compiles),
                                 median_s=med, mad_s=mad,
                                 iteration=self._iterations)

    def _spec_step(self, plan, n_it: int, t0: float) -> list[Request]:
        """The speculative lane's step: verify + host acceptance + commit,
        all inside the watchdog window; the scheduler's half after the
        timing bookkeeping, exactly where the plain lane books."""
        with self._span(_spans.SRV_DECODE_READBACK, step=n_it):
            spec_out = self._spec_verify_commit(plan)
        self._time_step(t0, 0.0, step=n_it,
                        slots=len(self.sched.running),
                        queue=self.sched.queue_depth, ahead=0, spec=True)
        with self._span(_spans.SRV_RETIRE, step=n_it):
            return self._spec_resolve(spec_out)

    def _settle(self) -> None:
        """Read the step in flight back and book it, outside the loop's
        own order: whoever reads or changes slot state from the host's
        books between two iterations (``cancel``, a deadline's or a
        hand-off's ``_unseat``, ``export_request`` / ``import_request``,
        ``drain``, ``close``) calls this first, so that the books are the
        device's. What it retires is stored, and handed back by the next
        ``step()``. Nothing in flight: nothing happens."""
        fl, self._inflight = self._inflight, None
        if fl is None:
            return
        retired = self._book(fl, self._iterations)
        for req in retired:
            self._store_result(req)
        self._late += retired

    def _admit(self) -> None:
        """Start the head-of-queue request's prefill: pop it, tell the
        observatories, dispatch the cache's init / hydrate / restore, and
        seat it in the prefill lane."""
        req = self.sched.pop_next()
        if req is None:
            return
        wa = None
        if self.workload is not None:
            # admission hook: score the prompt's prefix overlap /
            # self-speculation potential (host-side only)
            wa = self.workload.on_admit(req.prompt,
                                        session_id=req.session_id)
        if self.tenantscope is not None:
            # partition the same estimate by tenant (prompt tokens,
            # shared-prefix overlap)
            self.tenantscope.on_admit(req, workload=wa)
        if self.kvscope is not None:
            # residency probe beside it: ghost-tree regret match +
            # session resume edge (host-side only)
            self.kvscope.on_admit(req)
        if self._seated is not None:
            # one batch-1 cache at a time: behind a step that follows a
            # seat the seated cache may still wait for its insert, and the
            # runtime makes room for init_cache's output at dispatch. The
            # insert went out to an idle device and the step runs
            # meanwhile, so the wait is short and costs the device nothing
            inserted, old = self._seated
            self._seated = None
            self._row.wait(jax.block_until_ready, inserted)
            for buf in jax.tree_util.tree_leaves(old):
                buf.delete()
        # the batch-1 cache and the request's key from ONE program: the
        # key is per_request_keys([seed]) traced, bit for bit, where the
        # eager call is three small programs in front of the chunk
        cache, rng = self._prog("init_cache", lambda: jax.jit(
            lambda seed: (init_cache(self.model.cfg, 1, self.cfg.max_len,
                                     self.engine.compute_dtype),
                          jax.random.PRNGKey(seed)[None])))(
            np.int64(req.seed))
        alloc = req.page_alloc
        if alloc is not None and alloc.hydrate_pages > 0:
            # prefix sharing: gather the shared pages into the prefill
            # cache ONCE; the chunk plan then recomputes only the
            # unshared suffix
            hyd = self._prog("hydrate", lambda: jax.jit(
                hydrate_cache, donate_argnums=(1,)))
            cache = hyd(self._state, cache, alloc.hydrate_row,
                        np.int32(alloc.hydrate_pages))
        if alloc is not None and alloc.restored:
            # host-tier restore: the pending-restore lane beside the
            # prefill lane — scatter the cold blocks' tiles into the
            # prefill cache; the suffix chunks dispatched next overlap
            # the H2D
            cache = self._restore_dispatch(cache, alloc)
        self._prefill = (req, self.sched.plan(req), 0, cache, rng)

    def _store_result(self, req: Request) -> None:
        if self._paged and req.slot >= 0 \
                and self.sched.running.get(req.slot) is None:
            # neutralize the retired slot's page-table row (scratch) so
            # its freed pages can be handed to the next admission; the
            # flush lands before any device work next iteration. Guard on
            # the slot being EMPTY, not merely not-ours: a successor
            # placed into this slot within the same step already seated
            # its own row, which must not be zeroed under it
            self._table[req.slot] = 0
            self._table_dirty = True
        if self.workload is not None:
            self.workload.on_retire(req)
        if self.kvscope is not None:
            # session idle edge: the byte-seconds-held-while-idle meter
            # starts when a session's LAST live request terminates
            self.kvscope.on_retire(req)
        if self.tenantscope is not None:
            # terminal attribution: OK retirements credit the tenant
            # with the SAME len(req.tokens) ServingStats.on_retire adds
            # to Serve/completed_tokens — per-tenant sums conserve it
            self.tenantscope.on_retire(req)
        if self.capture is not None:
            self.capture.on_result(req)
        if self._request_logs or self.flight is not None:
            rec = request_record(req)
            for sink in self._request_logs:
                sink.log_request(rec)
            if self.flight is not None:
                self.flight.on_request(rec)
        self.results[req.rid] = req
        if len(self.results) > self._max_results:
            self.results.popitem(last=False)
            self.stats.on_results_evicted()
            warning_once(
                f"serving results store hit its cap ({self._max_results}); "
                "evicting oldest finished requests — collect results via "
                "step()'s return value or pop_result() (further evictions "
                "count in Serve/results_evicted)")

    def _expire_deadlines(self) -> list[Request]:
        """One deadline sweep over queue + slots + the prefill lane."""
        now = self.stats.clock()
        if self._inflight is not None and any(
                r.deadline_total is not None and now >= r.deadline_total
                for r in self.sched.running.values()):
            # a row is about to be taken out of its slot: book the step
            # in flight first (it may have ended the request in time)
            self._settle()
        expired = self.sched.expire_deadlines(now)
        self._unseat([req.slot for req in expired])
        if self._prefill is not None:
            req = self._prefill[0]
            if (req.deadline_ttft is not None and now >= req.deadline_ttft) \
                    or (req.deadline_total is not None
                        and now >= req.deadline_total):
                self._prefill = None
                expired.append(self.sched.abort(
                    req, RequestStatus.TIMEOUT,
                    "deadline expired during prefill"))
        return expired

    def _chaos_flood(self, n: int) -> None:
        """Chaos queue flood: slam ``n`` junk one-token submits through the
        normal intake. With ``max_queue`` set, the overflow sheds through
        QueueFullError — exactly the backpressure path under test."""
        for i in range(n):
            try:
                self.submit(np.asarray([1], np.int32), 1,
                            seed=int(self.chaos.rng.integers(1 << 30)))
            except QueueFullError:
                pass  # the shed IS the scenario; counted in Serve/shed

    def _lane_dispatch(self, n_it: int, ahead: int) -> None:
        """The prefill lane's host work for one iteration: admit the head
        of the queue if the lane is empty, and dispatch the lane's next
        chunk, final or not, into ``_ahead`` for ``_prefill_advance``.
        Called behind the decode step just dispatched (``ahead=1``) it is
        NEXT iteration's admission and chunk: the device runs
        ``init_cache`` and the chunk while the host reads the step back,
        retires and books, instead of standing idle until the host comes
        round. Called at the top of an iteration (``ahead=0``) it does what
        is left: a request that arrived or a slot that was freed since the
        last step, an engine with nothing running. Either way one chunk an
        iteration; a lane cleared meanwhile (cancel, deadline) leaves its
        chunk unused and it is dropped here. The spans say ``ahead`` and
        carry the iteration that dispatched them."""
        if self._prefill is None and (self.sched.queue or not ahead):
            self._ahead = None
            if ahead:
                # rows cleared since the top of the iteration reach the
                # device before an admission can reuse their pages
                self._flush_table()
            with self._span(_spans.SRV_ADMIT, step=n_it, ahead=ahead):
                self._admit()
        if self._prefill is None or self._ahead is not None:
            return
        req, plan, idx, cache, rng = self._prefill
        ch = plan[idx]
        params = self.engine.params
        # the span is the DISPATCH of one chunk program: where dispatch
        # is asynchronous (any accelerator) it times an enqueue, and the
        # chunk's device time shows up in whatever blocks next
        # (prefill_readback on a final chunk, else decode_readback). The
        # scalars go in as numpy: an argument of the chunk's program, not
        # a convert program of their own in front of it
        with self._span(_spans.PREFILL_CHUNK, name="srv.prefill_chunk",
                        rid=req.rid, step=n_it, chunk=idx, size=ch.size,
                        final=ch.final, ahead=ahead,
                        **self.kind.chunk_meta(ch),
                        **self.sched._attempt_meta(req)) as chunk_span:
            ids = ch.ids[None]
            if not ch.final:
                fwd = self._prog(("chunk", ch.size), lambda: jax.jit(
                    self._chunk_impl, donate_argnums=(1,)))
                out = fwd(params, cache, ids, np.int32(ch.start))
            else:
                fin = self._prog(("final", ch.size), lambda: jax.jit(
                    self._final_impl, donate_argnums=(1,)))
                out = fin(params, cache, ids, np.int32(ch.start),
                          np.int32(ch.last_index), np.int32(ch.true_len),
                          rng)
        self._ahead = ch, out, chunk_span
        if ch.final:
            self._row.finals += 1
        else:
            self._row.chunks += 1
        # the program took the lane's cache (donated)
        self._prefill = (req, plan, idx, None, rng)

    def _prefill_advance(self, n_it: int) -> list[Request]:
        req, plan, idx, _, rng = self._prefill
        (ch, out, chunk_span), self._ahead = self._ahead, None
        if self.kind.moe_stats or self.kind.exit_pdf:
            # the chunk's expert counters (a looped trunk's: its mean exit
            # distribution) stay on the device until the next decode
            # read-back fetches them beside its own
            out, stats, routing = out
            if chunk_span.recording:
                self._chunk_stats.append((chunk_span, stats, ch.size))
            if self.routing_log is not None:
                self._chunk_routing.append(
                    (req.rid, ch.start, routing,
                     ch.last_index + 1 if ch.final else ch.size))
        if not ch.final:
            self._prefill = (req, plan, idx + 1, out, rng)
            return []
        pf = out
        self._prefill = None
        for arr in (pf.tok, pf.done):
            arr.copy_to_host_async()
        self._first = req, pf
        if req.max_new == 1 or self._serial or self.on_placed is not None:
            # one token asked for: it is never seated. A serial engine
            # plans its next step from the token, and a hand-off takes the
            # request before a step can run it: read first, seat after
            return self._first_token(n_it)
        # the seat goes out right behind the chunk, with the slot the host
        # chooses now; the token it carries is read behind the step
        with self._span(_spans.SRV_PLACE, step=n_it):
            self._place(req, pf)
        return []

    def _first_token(self, n_it: int) -> list[Request]:
        """Read the first token the lane's final chunk sampled and book
        it. Of a request seated already (``_prefill_advance``) the chunk
        ran before the step now running, so the read waits for nothing
        the device is not doing anyway; where it ended the request (eos),
        the insert has seated the row as one that is not running and the
        host gives the slot back. Otherwise (one token asked for, a serial
        engine, a hand-off) the seat follows the read, as it always did."""
        (req, pf), self._first = self._first, None
        with self._span(_spans.SRV_PREFILL_READBACK, step=n_it):
            tok, done = self._row.wait(jax.device_get, (pf.tok, pf.done))
            self._row.read_first = 1
            first_tok = int(tok[0])
            ended = req.max_new == 1 or bool(done[0])
        if req.slot < 0:
            if ended:
                return [self.sched.complete_at_prefill(req, first_tok)]
            with self._span(_spans.SRV_PLACE, step=n_it):
                self._place(req, pf, first_tok)
        elif ended:
            slot = req.slot
            self.sched.unseat(req)
            if self._paged:
                self._table[slot] = 0
                self._table_dirty = True
            self._retired_on_device([slot])
            return [self.sched.complete_at_prefill(req, first_tok)]
        else:
            self.sched.first_token(req, first_tok)
        return []

    def _insert_impl(self, state, slot, *rest):
        """The seat's program, and a scalar of the state it leaves: what
        the host can wait for to know the insert has run, since the state
        itself goes on into the step (donated)."""
        state = (insert_paged if self._paged else insert_request)(
            state, slot, *rest)
        return state, state.done[slot]

    def _place(self, req: Request, pf, first_tok: Optional[int] = None):
        """Seat a prefilled request: take a slot, dispatch the insert of
        its cache into the slot state. ``first_tok``: the token ``pf``
        carries, where the host has read it already (a seat in the serial
        order, with its hand-off)."""
        slot = self.sched.seat(req)
        if first_tok is not None:
            self.sched.first_token(req, first_tok)
        # donate only the slot state: the batch-1 prefill buffers have
        # different shapes and could never alias the slot cache anyway
        ins = self._prog("insert", lambda: jax.jit(
            self._insert_impl, donate_argnums=(0,)))
        if self._paged:
            alloc = req.page_alloc
            self._table[slot] = alloc.row
            self._table_dirty = True
            self._flush_table()
            self._state, inserted = ins(
                self._state, np.int32(slot), pf, alloc.row,
                np.int32(alloc.shared), np.int32(req.max_new - 1))
            # the prompt's blocks are in the pool now: index them for
            # future sharing and release the copy-on-write source pin
            self.pool.on_inserted(req.rid, req.prompt)
            if self.tenantscope is not None:
                # first-writer block ownership: a later demotion of any
                # of these blocks bills its tier bytes to this tenant
                self.tenantscope.on_blocks(req)
        else:
            self._state, inserted = ins(self._state, np.int32(slot), pf,
                                        np.int32(req.max_new - 1))
            if self._slot_len is not None:
                self._slot_len[slot] = req.prompt_len
        self._seated = inserted, pf.cache
        self._row.seats += 1
        if self.on_placed is not None and first_tok is not None:
            # disaggregated handoff: the fleet may export the freshly
            # seated request and release the slot before this very
            # iteration's decode lane runs — a prefill replica never
            # spends a decode step on a handed-off request. The hook reads
            # slot state: the step in flight is booked first
            self._settle()
            self.on_placed(req, slot)

    def _unseat(self, slots) -> None:
        """Slots whose requests the host has just retired for a reason the
        device cannot foresee (a deadline, ``cancel``, non-finite logits,
        a hand-off to another replica) stop running on the device as well:
        one small program marks them ``done`` at length 0
        (``serving/slots.py`` ``retire_slots``) and the mirror follows.
        Entries under 0 (a request that held no slot) are skipped. At eos
        and at a request's ``max_new`` the step retires the row itself and
        nothing runs here: the common path has no program of this kind."""
        slots = [int(s) for s in slots if s >= 0]
        if not slots:
            return
        mask = np.zeros(self.cfg.slots, bool)
        mask[slots] = True
        with self.engine.mesh:
            ret = self._prog("retire", lambda: jax.jit(
                retire_slots, donate_argnums=(0,)))
            self._state = ret(self._state, mask)
        if self._slot_len is not None:
            self._slot_len[slots] = 0

    # ------------------------------------------------------ tiered host KV
    def _demote_pages(self, entries: list) -> None:
        """``PagePool.on_demote`` handler: DISPATCH a gather of the
        evicted full-block pages' tiles (K, V, int8 scale planes) with
        ONE fixed-shape program (row padded with the scratch page) and
        queue the result for host materialization at the END of this
        iteration (:meth:`_drain_demotes`). Dispatching here pins the
        ordering — the gather reads the pages before any later-dispatched
        insert can rewrite them (XLA executes in dispatch order and
        honors pending reads across donation) — while the blocking
        ``device_get``, the CRC stamp, and the host copies stay OFF the
        admission path, so demotion never bills the resuming request's
        TTFT.

        With demote-ahead on, pages the background lane already staged
        into the tier need NO gather at all — their eviction is the
        refcount drop that already happened in the pool; only the
        never-staged remainder pays the dispatch. The pressure-tagged
        gather-dispatch wall (and the matching ``device_get`` wall in
        :meth:`_drain_demotes`) accumulates into
        ``Serve/host_tier_demote_wait_s`` — the admission-path
        demote-blocking time the lane exists to zero (a fully staged
        eviction adds exactly nothing to it)."""
        todo = entries
        if self._demote_ahead is not None:
            from ..observability.workload import token_hash

            tier, todo, fast = self.pool.host, [], 0
            for e in entries:
                key = (len(e["tokens"]), token_hash(e["tokens"]))
                self._staged_ahead.discard(key)
                if tier.holds(e["tokens"], key=key):
                    fast += 1   # pre-staged: eviction is a pure free
                else:
                    todo.append(e)
            if fast:
                self.stats.registry.counter(
                    "Serve/demote_ahead_fastfrees").inc(fast)
                self.stats.registry.set_gauges({
                    "Serve/host_tier_staged_ahead":
                        float(len(self._staged_ahead))})
        if todo:
            self._dispatch_demote_gather(todo, pressure=True)

    def _dispatch_demote_gather(self, entries: list,
                                pressure: bool) -> None:
        """Dispatch fixed-shape gathers of ``entries``' pages (the ONE
        compiled "demote" program — the eviction path and the
        demote-ahead lane share it, so the lane adds zero programs).
        ``pressure`` tags eviction-driven batches: their dispatch wall
        here and their ``device_get`` wall at drain count as
        admission-path demote blocking; background staging's do not."""
        from .hostkv import demote_rows

        t0 = self.stats.clock() if pressure else None
        n = self.pool.pages_per_slot
        for off in range(0, len(entries), n):
            batch = entries[off:off + n]
            row = np.zeros(n, np.int32)
            row[:len(batch)] = [e["page"] for e in batch]
            prog = self._prog("demote", lambda: jax.jit(demote_rows))
            with self.engine.mesh:
                self._pending_demotes.append(
                    (prog(self._state, jnp.asarray(row)), batch,
                     pressure))
        if pressure:
            self.demote_wait_s += max(0.0, self.stats.clock() - t0)
            self.stats.registry.set_gauges({
                "Serve/host_tier_demote_wait_s": self.demote_wait_s})

    def _demote_ahead_tick(self) -> None:
        """The background demotion lane (cfg.demote_ahead_idle_s):
        tree-held full blocks idle past the threshold — per-entry touch
        stamps, the block-grain spelling of the session idleness
        kvscope's heat ledger tracks — are gathered and staged into the
        tier OFF the admission path, one ``pages_per_slot`` batch per
        iteration, oldest first. Staging is a COPY: the pages stay
        tree-held, a resuming session still takes the normal tree hit
        (wasting at most the staged copy), and tree-held pages with no
        slot users are immutable (divergence copies-on-write), so a
        staged copy can never go stale."""
        pool = self.pool
        if pool.tree_held == 0:
            return
        from ..observability.workload import token_hash

        tier = pool.host
        cutoff = self.stats.clock() - self._demote_ahead
        cand = pool.demote_ahead_candidates(cutoff, pool.pages_per_slot,
                                            skip=tier.holds)
        if not cand:
            return
        self._dispatch_demote_gather(cand, pressure=False)
        for e in cand:
            self._staged_ahead.add(
                (len(e["tokens"]), token_hash(e["tokens"])))
        self.stats.registry.counter(
            "Serve/demote_ahead_staged").inc(len(cand))
        self.stats.registry.set_gauges({
            "Serve/host_tier_staged_ahead":
                float(len(self._staged_ahead))})

    def _drain_demotes(self) -> None:
        """Materialize this iteration's dispatched demote gathers into
        the tier (one blocking ``device_get`` per batch — by now the
        gather has usually completed under the iteration's other device
        work). Runs at the end of every ``step()``; the transient
        device residency is bounded by one gather output per batch of
        one iteration. Pressure-tagged batches (reactive eviction
        demotes) bill their ``device_get`` wall to the demote-wait
        meter; demote-ahead's background staging does not."""
        pending, self._pending_demotes = self._pending_demotes, []
        pressured = False
        for out, batch, pressure in pending:
            t0 = self.stats.clock() if pressure else None
            tiles = self._row.wait(jax.device_get, out)
            if pressure:
                self.demote_wait_s += max(0.0, self.stats.clock() - t0)
                pressured = True
            for i, e in enumerate(batch):
                self.pool.host.put(
                    e["tokens"],
                    {k: np.ascontiguousarray(v[:, i])
                     for k, v in tiles.items()},
                    # tier-byte attribution: the tenant whose request
                    # first wrote this block (None when tenantscope is
                    # off or the block predates it)
                    owner=(self.tenantscope.block_owner(e["tokens"])
                           if self.tenantscope is not None else None))
        if pressured:
            self.stats.registry.set_gauges({
                "Serve/host_tier_demote_wait_s": self.demote_wait_s})

    def _restore_dispatch(self, cache, alloc):
        """Scatter one admission's host-restored tiles into its prefill
        cache pages ``[shared, shared + restored)`` — in up to two
        fixed-shape batches, so the second H2D upload overlaps the first
        batch's device write (double-buffered), and the whole restore
        overlaps the unshared-suffix chunk programs dispatched right
        after (async dispatch, no host sync here). The cache then flows
        through the SAME chunk-plan → ``insert_paged`` path as a tree
        hit. The measured dispatch window is honest on CPU and a lower
        bound where the scatter overlaps the async device queue."""
        from .hostkv import restore_into_cache

        t0 = self.stats.clock()
        n = self.pool.pages_per_slot
        R = max(1, (n + 1) // 2)          # batch size: <= 2 dispatches
        tiles = alloc.restore_tiles
        prog = self._prog("restore", lambda: jax.jit(
            restore_into_cache, donate_argnums=(0,)))
        off = 0
        while off < alloc.restored:
            cnt = min(R, alloc.restored - off)
            batch = {}
            for key, v in tiles.items():
                pad = np.zeros(v.shape[:1] + (R,) + v.shape[2:], v.dtype)
                pad[:, :cnt] = v[:, off:off + cnt]
                batch[key] = pad
            cache = prog(cache, batch, np.int32(alloc.shared + off),
                         np.int32(cnt))
            off += cnt
        self.pool.host.on_restore(self.stats.clock() - t0,
                                  pages=alloc.restored,
                                  tokens=alloc.restore_tokens,
                                  nbytes=alloc.restore_bytes)
        alloc.restore_tiles = None        # the payload is on device now
        return cache

    def prefix_residency(self, prompt) -> tuple:
        """``(tree_blocks, host_blocks)`` of ``prompt``'s leading full
        blocks resident on THIS engine — the fleet router's affinity
        input (tree hit ranks above host-tier hit ranks above miss).
        ``(0, 0)`` on the contiguous engine. Read-only."""
        if not self._paged:
            return (0, 0)
        return self.pool.residency(np.asarray(prompt, np.int32))

    def begin_drain(self) -> None:
        """Graceful drain mode: stop ADMITTING new submits (they shed with
        :class:`QueueFullError`, status ``SHED``) while queued and running
        requests keep being served to completion. ``health()`` reports
        ``ready: False`` so load balancers rotate the replica out."""
        self._draining = True

    def end_drain(self) -> None:
        """Reopen intake after a drain (e.g. a cancelled rollout)."""
        self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, max_iterations: int = 1_000_000) -> dict[int, Request]:
        """Graceful shutdown: enter drain mode, run until queue and slots
        are empty, return ``results``. Intake stays closed afterwards —
        call :meth:`end_drain` to reopen."""
        self.begin_drain()
        it = 0
        while not self.sched.idle or self._prefill is not None:
            self.step()
            it += 1
            if it > max_iterations:
                raise RuntimeError(
                    f"serving failed to drain in {max_iterations} "
                    "iterations — scheduler stuck?")
        # the last step out ran nothing the books still hold: read it, so
        # that a drained engine has nothing in flight
        self._settle()
        return self.results

    def pop_result(self, rid: int) -> Optional[Request]:
        """Collect (and release) a finished request; None if not finished
        or already collected."""
        return self.results.pop(rid, None)

    # ------------------------------------------------- fleet handoff seams
    def export_request(self, req: Request) -> dict:
        """Gather a slot-resident request's complete decode state (pool
        page tiles + slot vectors) to HOST numpy — the source half of the
        disaggregated prefill→decode handoff (serving/fleet.py). One
        compiled program regardless of request or slot (the table row is
        data). Paged engines only."""
        if not self._paged:
            raise RuntimeError("export_request needs the paged KV cache "
                               "(set serving.page_size)")
        self._settle()       # the books (its tokens) as the device has them
        if req.slot < 0 or self.sched.running.get(req.slot) is not req:
            raise ValueError(f"request {req.rid} is not slot-resident here")
        with self.engine.mesh:
            self._flush_table()
            exp = self._prog("export", lambda: jax.jit(export_slot))
            out = exp(self._state, jnp.asarray(self._table[req.slot]),
                      jnp.int32(req.slot))
        return jax.device_get(out)

    def release_request(self, req: Request) -> None:
        """Drop a slot-resident request WITHOUT retiring it: free the
        slot, release its page refs (the prompt's blocks stay tree-held
        for future sharing), neutralize the table row. The request
        object itself stays live — the fleet seats it elsewhere. No
        retirement stats, no terminal span: this is a move, not an
        outcome."""
        slot = req.slot
        if slot >= 0 and self.sched.running.get(slot) is req:
            del self.sched.running[slot]
            self.sched.free.append(slot)
        self.sched._release_pages(req)
        req.page_alloc = None
        req.slot = -1
        if self._paged and slot >= 0 \
                and self.sched.running.get(slot) is None:
            self._table[slot] = 0
            self._table_dirty = True
        self._unseat([slot])
        if self.kvscope is not None:
            # the handoff ends the session's activity on THIS replica
            # (its tree keeps the prompt blocks); without this edge a
            # prefill replica's sessions would stay ACTIVE forever
            self.kvscope.on_retire(req)

    def import_request(self, req: Request, payload: dict) -> bool:
        """Seat an exported request into THIS engine's pool and a free
        slot — the destination half of the handoff. Returns False (try
        again after a retirement) when no slot is free or the pool is
        transiently full; True when the request is decoding here. The
        imported bits continue the source's exact RNG chain, so the
        output stream is bit-identical to a single engine's."""
        if not self._paged:
            raise RuntimeError("import_request needs the paged KV cache "
                               "(set serving.page_size)")
        self._settle()       # a slot the step in flight freed counts
        if not self.sched.free:
            return False
        if self.tenantscope is not None:
            # rid → tenant binding must exist BEFORE try_admit fires the
            # pool's on_pages hook, or the pages bill to "default"
            self.tenantscope.on_adopt(req)
        # book_savings=False: seating already-computed KV skips no
        # prefill — the SOURCE replica owns the savings accounting
        alloc = self.pool.try_admit(req.prompt, req.max_new, req.rid,
                                    book_savings=False)
        if alloc is None:
            return False
        # hop stamp: the import window opens now (the attempt that will
        # seat the request — failed probes above returned before work);
        # handoff_wait_s ends here, import_s covers the scatter below
        req.import_t0 = self.stats.clock()
        req.page_alloc = alloc
        slot = self.sched.adopt(req)
        if req.deadline_ttft is not None or req.deadline_total is not None:
            # this engine never saw the request's submit(): the deadline
            # sweep must still cover the imported residency
            self._any_deadlines = True
        with self.engine.mesh:
            self._table[slot] = alloc.row
            self._table_dirty = True
            self._flush_table()
            imp = self._prog("import", lambda: jax.jit(
                import_slot, donate_argnums=(0,)))
            self._state = imp(self._state, jnp.int32(slot),
                              {k: jnp.asarray(v) for k, v in payload.items()},
                              jnp.asarray(alloc.row), jnp.int32(alloc.shared))
            self.pool.on_inserted(req.rid, req.prompt)
            if self.tenantscope is not None:
                self.tenantscope.on_blocks(req)
        if self.kvscope is not None:
            # decode-side session intake: residency moves here (no
            # regret probe — this replica paid no prefill)
            self.kvscope.on_import(req)
        req.import_t1 = self.stats.clock()
        return True

    def serve_batch(self, prompts, max_new_tokens=None, seeds=None,
                    session_ids=None, tenant_ids=None) -> list:
        """Convenience: submit a list of (ragged) prompts, drain, return
        each request's tokens as an int32 array, in submission order.
        ``max_new_tokens``, ``seeds``, ``session_ids``, and
        ``tenant_ids`` may be scalars or per-request lists. Results are
        collected (popped) — repeated calls on one engine don't
        accumulate host state."""
        n = len(prompts)
        mn = expand_per_request(max_new_tokens, n, None, int)
        sd = expand_per_request(seeds, n, 0, int)
        sid = expand_per_request(session_ids, n, None)
        tid = expand_per_request(tenant_ids, n, None)
        rids = [self.submit(p, mn[i], seed=sd[i], session_id=sid[i],
                            tenant_id=tid[i])
                for i, p in enumerate(prompts)]
        want = set(rids)
        got: dict[int, Request] = {}
        it = 0
        while len(got) < n:
            for req in self.step():
                if req.rid in want:
                    got[req.rid] = req
                    self.results.pop(req.rid, None)
            it += 1
            if it > 1_000_000:
                raise RuntimeError("serve_batch failed to finish — "
                                   "scheduler stuck?")
        return [np.asarray(got[r].tokens, np.int32) for r in rids]

    # ------------------------------------------------------------ metrics
    @property
    def degraded(self) -> bool:
        """A watchdog stall within the last ``_DEGRADED_WINDOW``
        iterations (recovers once steps are healthy again; the
        cumulative stall COUNT doesn't) — one definition shared by
        :meth:`health` and the fleet router."""
        return (self._last_stall_iter is not None
                and self._iterations - self._last_stall_iter
                <= _DEGRADED_WINDOW)

    @property
    def pool_pressure(self) -> bool:
        """Paged engine with an empty free list: admissions are
        deferring or shedding — shared by :meth:`health` and the fleet
        router."""
        return self._paged and not self.pool.free

    def health(self) -> dict:
        """Liveness/readiness snapshot for probes, also exported as
        ``Serve/*`` gauges (so the Prometheus textfile carries the same
        truth the probe endpoint returns). ``ready`` means "will accept a
        submit right now": not draining and not at queue capacity.
        ``degraded`` flags a watchdog stall within the last
        ``_DEGRADED_WINDOW`` iterations — and recovers once steps are
        healthy again (the cumulative ``watchdog_stalls`` count doesn't).

        On the paged engine the snapshot also mirrors the page-pool
        picture (``pages``: free/used/tree-held + ``pool_pressure`` when
        the free list is empty — admissions are deferring or shedding),
        so ``/readyz`` reports pool-exhaustion pressure alongside the
        queue/drain state it always knew about."""
        snap = self.stats.registry.snapshot()
        stalls = int(snap["counters"].get("Serve/watchdog_stalls", 0))
        queue_full = bool(self.cfg.max_queue
                          and self.sched.queue_depth >= self.cfg.max_queue)
        degraded = self.degraded
        out = {
            "state": "draining" if self._draining else "serving",
            "ready": not self._draining and not queue_full,
            "degraded": degraded,
            "queue_depth": self.sched.queue_depth,
            "occupancy": self.sched.occupancy,
            "slots": self.cfg.slots,
            "prefill_inflight": self._prefill is not None,
            "iterations": self._iterations,
            "last_step_s": self._last_step_s,
            "watchdog_stalls": stalls,
            "results_held": len(self.results),
            "pool_pressure": False,
        }
        gauges = {
            "Serve/ready": float(out["ready"]),
            "Serve/draining": float(self._draining),
            "Serve/degraded": float(degraded),
            "Serve/last_step_s": self._last_step_s,
            # results-store depth: a caller that never collects results
            # shows up as a climbing gauge long before evictions start
            "Serve/results_held": float(len(self.results)),
        }
        if self._paged:
            ps = self.pool.snapshot()
            pressure = ps["free_pages"] == 0
            out["pages"] = {
                "free_pages": ps["free_pages"],
                "used_pages": ps["used_pages"],
                "usable_pages": ps["usable_pages"],
                "tree_held_pages": ps["tree_held_pages"],
                # eviction pressure through /readyz: what the next
                # admission under pressure would reclaim, how often
                # pressure has bitten, and how stale the coldest entry is
                "evictable_pages": ps["evictable_pages"],
                "eviction_events": ps["eviction_events"],
                "pages_evicted": ps["pages_evicted"],
                "oldest_tree_entry_age_s": ps["oldest_tree_entry_age_s"],
                "pool_pressure": pressure,
            }
            out["pool_pressure"] = pressure
            # keep the Serve/page_* gauges fresh at probe time too (the
            # pool only rewrites them on alloc/free events)
            gauges.update({
                "Serve/page_pool_free": float(ps["free_pages"]),
                "Serve/page_pool_used": float(ps["used_pages"]),
                "Serve/page_pool_tree_held": float(ps["tree_held_pages"]),
                "Serve/page_pool_pressure": float(pressure),
            })
        if self.hostkv is not None:
            # host-tier occupancy + pressure through /readyz, beside the
            # device pool's eviction-pressure fields: a full tier means
            # the next demotion starts pruning cold history (regret
            # creeps back) — ops sees it before the regret ledger does
            hs = self.hostkv.snapshot()
            out["host_tier"] = {
                "pages": hs["pages"],
                "bytes": hs["bytes"],
                "capacity_bytes": hs["capacity_bytes"],
                "occupancy": hs["occupancy"],
                "pressure": hs["pressure"],
                "restores": hs["restores"],
                "prunes": hs["prunes"],
                "fallbacks": hs["fallbacks"],
            }
            if self._demote_ahead is not None:
                out["host_tier"]["staged_ahead"] = len(self._staged_ahead)
                out["host_tier"]["demote_wait_s"] = self.demote_wait_s
            # snapshot() already refreshed the Serve/host_tier_* gauges
        if self.nvmekv is not None:
            # the disk rung beside it: occupancy, verified promotions,
            # and the two failure signals ops gates on (counted CRC
            # fallbacks, aio transport errors)
            ns = self.nvmekv.snapshot()
            out["nvme_tier"] = {
                "pages": ns["pages"],
                "bytes": ns["bytes"],
                "capacity_bytes": ns["capacity_bytes"],
                "occupancy": ns["occupancy"],
                "pressure": ns["pressure"],
                "promotions": ns["promotions"],
                "spilled_in": self.hostkv.spills,
                "fallbacks": ns["fallbacks"],
                "aio_errors": ns["aio_errors"],
                "native_aio": ns["native_aio"],
            }
        self.stats.registry.set_gauges(gauges)
        if self.loadscope is not None:
            # refresh Serve/utilization / predicted-wait / TTV at probe
            # cadence (the report sets its own gauges as a side effect)
            self.scaling_snapshot()
        return out

    def metrics_snapshot(self) -> dict:
        out = {"compiles": self.compiles, **self.stats.snapshot()}
        if self.workload is not None:
            out["workload"] = self.workload.snapshot()
        spec = self.spec_snapshot()
        if spec is not None:
            out["speculation"] = spec
        if self._paged:
            out["pages"] = self.pool.snapshot()
        if self.kvscope is not None:
            out["kv_residency"] = self.kvscope.snapshot()
        if self.goodput is not None:
            out["goodput"] = self.goodput.snapshot()
        if self.loadscope is not None:
            out["loadscope"] = self.scaling_snapshot()
        if self.tenantscope is not None:
            out["tenants"] = self.tenants_snapshot()
        return out

    def tenants_snapshot(self) -> Optional[dict]:
        """The per-tenant breakdown (``GET /tenants``, doctor's
        ``[tenants]`` section): tenantscope's report with this engine's
        tier stores attached so resident bytes split by owner. None when
        tenantscope is off."""
        if self.tenantscope is None:
            return None
        tiers = {}
        if self.hostkv is not None:
            tiers["host_tier"] = self.hostkv
        if self.nvmekv is not None:
            tiers["nvme_tier"] = self.nvmekv
        return self.tenantscope.report(tiers=tiers or None)

    def requests_table(self) -> list[dict]:
        """Live in-flight table (the ``GET /requests`` endpoint): every
        request currently queued, prefilling, or decoding — host-side
        bookkeeping only, no device reads. Reads the prefill lane
        through ONE local binding: the HTTP thread races the serving
        loop, which may clear ``_prefill`` between a check and a
        subscript."""
        p = self._prefill
        return self.sched.inflight_table(p[0] if p is not None else None)

    def _find_request(self, rid: int) -> Optional[Request]:
        """The request wherever it lives on THIS engine — results,
        prefill lane, slots, or queue; None if unknown here. Containers
        are copied before iteration: the telemetry HTTP thread calls
        this while the serving loop mutates them."""
        req = self.results.get(rid)
        if req is not None:
            return req
        p = self._prefill
        if p is not None and p[0].rid == rid:
            return p[0]
        for r in list(self.sched.running.values()):
            if r.rid == rid:
                return r
        for r in list(self.sched.queue):
            if r.rid == rid:
                return r
        return None

    def request_trace(self, rid: int) -> Optional[dict]:
        """One request's hop-latency decomposition
        (:func:`~..observability.export.hop_trace`) — finished requests
        from ``results``, live ones from the scheduler (hops completed
        so far; the rest null). None when this engine doesn't know the
        rid. Host timestamps only — no span ring required, no device
        reads."""
        from ..observability.export import hop_trace

        req = self._find_request(rid)
        if req is None:
            return None
        return {"rid": rid, "status": req.status.value,
                "finished": req.finished, "slot": req.slot,
                "tokens": len(req.tokens), "hops": hop_trace(req)}

    # ----------------------------------------------------------- capacity
    def _built_programs(self):
        """``(name, jitted, args)`` for the programs traffic has actually
        built: the slot decode step and every prefill bucket compiled so
        far, with the avals each lowers for. Building (and
        compile-counting) the step here would put a phantom compile in the
        freeze gates and feed the compile-storm detector."""
        params = self.engine.params
        if "step" in self._programs:
            yield "step", self._programs["step"], (params, self._state)
        elif "step_chaos" in self._programs:
            yield "step", self._programs["step_chaos"], (
                params, self._state, jnp.int32(-1))
        # avals only — a batch-1 cache never materializes
        cache_aval = jax.eval_shape(
            lambda: init_cache(self.model.cfg, 1, self.cfg.max_len,
                               self.engine.compute_dtype))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        rng_aval = jax.eval_shape(lambda: per_request_keys([0]))
        for key in [k for k in self._programs
                    if isinstance(k, tuple) and k[0] in ("chunk", "final")]:
            stem, size = key
            ids = jax.ShapeDtypeStruct((1, size), jnp.int32)
            if stem == "chunk":
                yield (f"chunk_{size}", self._programs[key],
                       (params, cache_aval, ids, i32))
            else:
                yield (f"final_{size}", self._programs[key],
                       (params, cache_aval, ids, i32, i32, i32, rng_aval))

    def compiled_programs(self) -> dict:
        """Each built program AOT-compiled for the live shapes (nothing
        executes): ``as_text()`` says which kernels (``tpu_custom_call``)
        and collectives the compiler put in, ``memory_analysis()`` what
        the program holds beside its arguments."""
        with self.engine.mesh:
            return {name: jitted.lower(*args).compile()
                    for name, jitted, args in self._built_programs()}

    def compiled_texts(self) -> dict:
        """Optimized HLO text per built program."""
        return {name: c.as_text()
                for name, c in self.compiled_programs().items()}

    def capacity_census(self) -> dict:
        """Per-program cost census over the engine's bounded program set:
        static FLOPs / HBM bytes / collective bytes (compiler + HLO truth,
        AOT-lowered — nothing executes) joined with achieved wall times
        from the span ring (``decode_step`` / ``prefill_chunk`` spans)
        into achieved-vs-roofline MBU/MFU per program. Census rows cover
        the programs traffic has actually built: the slot decode step and
        every prefill bucket compiled so far. Backends without
        cost/memory analysis degrade rows to null fields, never raise."""
        from ..observability.capacity import ProgramCensus, roofline_peaks

        pf, bw = roofline_peaks()
        census = ProgramCensus(peak_flops=pf, peak_bw=bw)
        for name, jitted, args in self._built_programs():
            census.measure(name, jitted, *args, mesh=self.engine.mesh)
        if self.spans is not None:
            census.attach_spans(self.spans.events())
        return census.report()

    def _prefill_rate(self) -> Optional[dict]:
        """Prefill tokens over the wall time of the span ring's
        ``prefill_chunk`` spans — the recompute-cost side of the
        tiered_kv lever. A ``prefill_chunk`` span is the DISPATCH of a
        chunk program: on the CPU backend, which runs it before
        returning, this is the prefill rate; on a device, where dispatch
        is an enqueue, it is tokens per second of enqueueing, a rate no
        chip can reach, and the lever that reads it (loadscope, kvscope)
        then underprices recompute. None when spans are off or no chunk
        has run (the lever then degrades to score 0: unmeasured, not
        guessed)."""
        if self.spans is None:
            return None
        from ..observability import spans as _sp

        toks = 0
        wall = 0.0
        for ev in self.spans.events():
            if ev.kind == _sp.PREFILL_CHUNK and ev.t1 is not None:
                toks += int(ev.meta.get("size") or 0)
                wall += ev.duration
        if toks <= 0 or wall <= 0:
            return None
        return {"tokens": toks, "wall_s": wall,
                "tokens_per_s": toks / wall}

    def _decode_rate(self) -> Optional[dict]:
        """Measured decode slot-throughput from the span ring's
        ``decode_step`` spans: emitted tokens (one per active slot per
        step) over busy slot-seconds — the serviceable-rate side of the
        loadscope utilization model. None when spans are off or no
        decode step has run (ρ then degrades to unmeasured, not
        guessed)."""
        if self.spans is None:
            return None
        from ..observability import spans as _sp

        toks = 0
        slot_s = 0.0
        wall = 0.0
        steps = 0
        for ev in self.spans.events():
            if ev.kind == _sp.DECODE_STEP and ev.t1 is not None:
                n = int(ev.meta.get("slots") or 0)
                toks += n
                slot_s += ev.duration * n
                wall += ev.duration
                steps += 1
        if toks <= 0 or slot_s <= 0:
            return None
        return {"steps": steps, "tokens": toks, "wall_s": wall,
                "slot_s": slot_s, "tokens_per_slot_s": toks / slot_s,
                "tokens_per_s": toks / wall if wall > 0 else None}

    def scaling_snapshot(self) -> Optional[dict]:
        """The arrival & scaling observatory's readout (``GET /scaling``,
        the capacity report's ``loadscope`` section, one replica row of
        ``FleetEngine.scaling_report()``): arrival-process estimates
        joined with span-measured service rates into utilization ρ,
        predicted queue wait, SLO time-to-violation, and scored scaling
        what-ifs. None when loadscope is off; unmeasured inputs degrade
        field-by-field with stated reasons, never raise."""
        if self.loadscope is None:
            return None
        dec = self._decode_rate()
        pre = self._prefill_rate()
        service = {
            "slots": self.cfg.slots,
            "decode_tokens_per_slot_s": (dec or {}).get("tokens_per_slot_s"),
            "decode_tokens_per_s": (dec or {}).get("tokens_per_s"),
            "effective_concurrency": (
                dec["tokens_per_s"] / dec["tokens_per_slot_s"]
                if dec and dec.get("tokens_per_s")
                and dec.get("tokens_per_slot_s") else None),
            "prefill_tokens_per_s": (pre or {}).get("tokens_per_s"),
        }
        slo_cfg = self.slo.cfg if self.slo is not None else None
        return self.loadscope.report(service=service, slo=slo_cfg,
                                     queue_depth=self.sched.queue_depth)

    def kv_residency(self) -> Optional[dict]:
        """The KV residency observatory's readout plus the two measured
        host-tier inputs the capacity advisor joins it with: the (cached)
        host↔device copy-bandwidth probe and the span ring's measured
        prefill throughput. None when kvscope is off."""
        if self.kvscope is None:
            if self.hostkv is None:
                return None
            # no observatory, but the tier's achieved side still reports
            out = {"enabled": False, "host_tier": self.hostkv.snapshot()}
            if self.nvmekv is not None:
                out["nvme_tier"] = self.nvmekv.snapshot()
            return out
        snap = self.kvscope.snapshot()
        snap["copy_bandwidth"] = self.kvscope.copy_bandwidth()
        snap["prefill"] = self._prefill_rate()
        if self.hostkv is not None:
            # the ACHIEVED side of the tiered_kv lever: what the host
            # tier actually restored, at what measured rate — reported
            # next to the advisor's projection (observability/capacity.py)
            snap["host_tier"] = self.hostkv.snapshot()
        if self.nvmekv is not None:
            # the disk rung's achieved side (verified promotions +
            # measured read bandwidth) — the nvme sub-estimate's input
            snap["nvme_tier"] = self.nvmekv.snapshot()
        return snap

    def hbm_ledger(self, temp_bytes: Optional[int] = None) -> dict:
        """The live HBM budget decomposed (weights / KV / temp) with
        projected headroom, as ``Memory/ledger_*`` gauges in the serving
        registry — see :func:`~..observability.capacity.hbm_ledger`.
        On the paged engine the KV term is the page pool (int8 + scale
        planes when KV quantization is on) and the ledger carries the
        live used/free page decomposition instead of the contiguous
        estimate."""
        from ..observability.capacity import hbm_ledger

        paged_kw = {}
        if self._paged:
            snap = self.pool.snapshot()
            paged_kw = {"page_size": self.cfg.page_size,
                        "pool_pages": self.cfg.pool_pages,
                        "kv_quant_bits": self.cfg.kv_quant_bits,
                        "pages_used": snap["used_pages"],
                        "pages_free": snap["free_pages"]}
        if self.kvscope is not None:
            # the host-tier row: bytes reclaimable by demoting idle
            # sessions' tree-held pages at the measured idle distribution
            paged_kw["idle_kv_bytes"] = self.kvscope.idle_kv_bytes()
        if self.hostkv is not None:
            # achieved: host bytes the tier holds right now
            paged_kw["host_tier_bytes"] = self.hostkv.bytes_used
        return hbm_ledger(
            params=self.engine.params, model_cfg=self.model.cfg,
            slots=self.cfg.slots, max_len=self.cfg.max_len,
            cache_dtype=self.engine.compute_dtype, temp_bytes=temp_bytes,
            registry=self.stats.registry, **paged_kw)

    def capacity_report(self, path=None, census: bool = True,
                        commscope=None) -> dict:
        """The capacity advisor: workload analytics + HBM ledger + program
        census composed into ranked what-if estimates on the observed
        traffic (``CAPACITY_REPORT.json`` when ``path`` is given; see
        docs/OPERATIONS.md capacity-planning runbook). ``census=False``
        skips the AOT lowering pass (cheaper; advisor loses the
        collective-byte lever's input). ``commscope`` optionally carries
        a communication-observatory report (``Engine.comm_observatory``
        / ``observability/commscope.py``) — the quantize/overlap
        collectives lever then ranks on MEASURED exposed time instead of
        the byte-share projection."""
        import math as _math

        from ..observability.capacity import (capacity_report,
                                              write_capacity_report)

        cen = self.capacity_census() if census else None
        temp = None
        if cen:
            temps = [r.get("temp_bytes") for r in cen["programs"].values()]
            temps = [t for t in temps if t is not None]
            temp = max(temps) if temps else None
        ledger = self.hbm_ledger(temp_bytes=temp)
        gauges = self.stats.registry.snapshot()["gauges"]
        occ = gauges.get("Serve/slot_occupancy_avg",
                         gauges.get("Serve/slot_occupancy"))
        if isinstance(occ, float) and _math.isnan(occ):
            occ = None
        wl = self.workload.snapshot() if self.workload is not None else None
        if self._tp_quant:
            # the quantized TP decode collective is ON: the advisor's
            # quantized_collectives lever reports it as achieved (wire
            # already int8) instead of projecting the same win again
            commscope = dict(commscope) if commscope else {}
            gq = dict(commscope.get("quantized") or {})
            gq.update({"active": True, "tp_quant_bits": self._tp_quant})
            commscope["quantized"] = gq
        rep = capacity_report(
            ledger=ledger, census=cen, workload=wl, occupancy_avg=occ,
            commscope=commscope, kvscope=self.kv_residency(),
            loadscope=self.scaling_snapshot(),
            tenantscope=self.tenants_snapshot(),
            pages=self.pool.snapshot() if self._paged else None,
            meta={"job": "serving", "slots": self.cfg.slots,
                  "max_len": self.cfg.max_len,
                  "prefill_chunk": self.cfg.prefill_chunk,
                  "page_size": self.cfg.page_size,
                  "kv_quant_bits": self.cfg.kv_quant_bits,
                  "iterations": self._iterations,
                  "compiles": self.compiles})
        if path is not None:
            write_capacity_report(rep, path)
        return rep

    def score_slo(self) -> dict:
        """One SLO scoring pass (``Serve/slo_*_burn`` gauges + flight
        markers on new breaches); {} when no SLO config is set. Runs
        inside ``publish_metrics`` so a normal serving loop needs no
        extra call."""
        return self.slo.score() if self.slo is not None else {}

    def attach_monitor(self, monitor) -> None:
        """Adopt a MonitorMaster's request-log writers: every retired
        request is logged as one JSON record through the fan-out's
        ``RequestLogSink`` (config ``monitor.request_log``). Scalar
        metrics still flow via :meth:`publish_metrics` — call that on the
        loop's cadence as before."""
        for w in getattr(monitor, "writers", []):
            if hasattr(w, "log_request") and w not in self._request_logs:
                self._request_logs.append(w)

    def dump_flight(self, reason: str = "manual"):
        """Freeze the flight recorder now (ops triage / shutdown hook);
        returns the dump directory or None (no recorder / dump cap)."""
        if self.flight is None:
            return None
        return self.flight.dump(reason)

    def publish_metrics(self, monitor, step: Optional[int] = None) -> int:
        """Push ``Serve/*`` through a monitor fan-out (same contract as
        ``InferenceEngine.publish_metrics`` — the serving loop owns the
        cadence). Scores SLOs and exports the goodput decomposition
        first so burn and ``Serve/goodput_*`` gauges ride the same
        flush."""
        from ..observability.metrics import publish_registry

        self.score_slo()
        if self.goodput is not None:
            self.goodput.export()
        return publish_registry(self.stats.registry, monitor, step,
                                default_step_counter="Serve/iterations")

    # ----------------------------------------------------------- telemetry
    def serve_telemetry(self, port: Optional[int] = None,
                        host: Optional[str] = None,
                        token: Optional[str] = None) -> int:
        """Start the live telemetry & control plane
        (:class:`~..observability.server.TelemetryServer`) for this
        engine; returns the bound port (pass ``port=0`` for an
        ephemeral one). Explicit arguments override the config block;
        idempotent — a second call returns the running server's port.

        The server thread only reads host-side state (registry under
        its own lock, scheduler tables copied per request) — it adds no
        device work, no syncs, and no compiled programs to the serving
        loop."""
        if self.telemetry is not None:
            return self.telemetry.port
        from ..observability.server import (TelemetryHooks, TelemetryServer,
                                            flight_summary)

        tcfg = self.cfg.telemetry
        host = host if host is not None else (
            tcfg.host if tcfg is not None else "127.0.0.1")
        port = port if port is not None else (
            tcfg.port if tcfg is not None else 0)
        token = token if token is not None else (
            tcfg.token if tcfg is not None else "")
        reg = self.stats.registry

        def refresh():
            # /metrics must carry the truth of NOW: the health mirror
            # (ready/draining/pool gauges) and the goodput decomposition
            # refresh before every exposition render
            self.health()
            if self.goodput is not None:
                self.goodput.export()

        hooks = TelemetryHooks(
            registry=reg,
            step_fn=lambda: int(reg.counter("Serve/iterations").value),
            refresh_fn=refresh,
            health_fn=self.health,
            requests_fn=self.requests_table,
            capacity_fn=lambda census: self.capacity_report(census=census),
            goodput_fn=(self.goodput.export if self.goodput is not None
                        else None),
            flight_fn=((lambda: flight_summary(self.flight))
                       if self.flight is not None else None),
            trace_fn=self._trace_endpoint,
            drain_fn=self._drain_control,
            dump_fn=((lambda: self.dump_flight("manual"))
                     if self.flight is not None else None),
            slo_reload_fn=self.reload_slo,
            scaling_fn=(self.scaling_snapshot
                        if self.loadscope is not None else None),
            tenants_fn=(self.tenants_snapshot
                        if self.tenantscope is not None else None))
        server = TelemetryServer(hooks, host=host, port=port, token=token)
        # bind FIRST: a failed bind (port in use) must not leave a dead
        # server object behind that makes the idempotency guard return
        # an unbound port on every retry
        bound = server.start()
        self.telemetry = server
        return bound

    def _trace_endpoint(self, rid: Optional[int]):
        """The ``GET /trace`` hook: ``?rid=N`` returns that request's
        hop-latency decomposition (:meth:`request_trace`); without a rid
        it returns the engine's span ring as a Chrome/Perfetto trace —
        None (→404) when spans are disabled or the rid is unknown."""
        if rid is not None:
            return self.request_trace(rid)
        if self.spans is None:
            return None
        from ..observability.export import to_chrome_trace

        return to_chrome_trace(self.spans.events(),
                               job_name=self.name or "serving")

    def _drain_control(self, end: bool) -> dict:
        """The ``POST /drain`` hook: begin (default) or end
        (``{"end": true}``) a graceful drain; returns the resulting
        health-relevant state."""
        if end:
            self.end_drain()
        else:
            self.begin_drain()
        return {"draining": self._draining,
                "queue_depth": self.sched.queue_depth,
                "occupancy": self.sched.occupancy}

    def close(self) -> None:
        """Teardown: stop the telemetry server's listener thread (when
        one is running) and read back the step in flight, if any. Safe to
        call more than once; the engine remains usable for serving
        afterwards."""
        self._settle()
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
