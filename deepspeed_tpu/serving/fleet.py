"""Multi-replica serving fleet: SLO-aware router over N ServingEngines.

Reference analog: the DeepSpeed-MII / FastGen serving deployment layer
(the survey's "from one engine to a service" step) — N replicas behind
one ``submit/step/drain/pop_result`` surface — with ZeRO-Infinity's
streaming discipline applied to KV handoff: finished prefill state moves
between roles as a page transfer instead of being recomputed.

:class:`FleetEngine` fronts N in-process
:class:`~.engine.ServingEngine` replicas built over ONE shared
:class:`~..inference.engine.InferenceEngine` (params and compiled
programs are shared; queues, slots, page pools, and metrics registries
are per-replica). What the fleet adds:

- **SLO-aware routing** — every admission consults each replica's live
  ``health()`` snapshot plus its ``Serve/slo_*_burn`` and
  ``Serve/goodput_frac`` gauges: least-loaded wins, and a draining,
  degraded, queue-full, or pool-pressured replica is never chosen while
  an alternative exists. All replicas draining → a typed
  :class:`~..resilience.guards.QueueFullError` shed, exactly like a
  single engine's drain.
- **Session affinity** — requests carrying a ``session_id`` stick to
  the replica whose radix tree already holds their prefix (that is
  where their prefill is nearly free). Routing is residency-ranked:
  tree hit > host-tier hit (the prefix was evicted but demoted to the
  replica's pinned-host store, ``serving/hostkv.py``) > cold miss, so
  a session falls back to the replica that can restore at copy
  bandwidth before one that must recompute. When the sticky replica is
  unhealthy the router falls back to policy and records the move in
  ``Fleet/affinity_misses``; a resume the sticky replica restores from
  its host tier books NO ``Fleet/affinity_regret`` (it paid copy
  bytes, not prefill — that is the host tier doing its job).
- **Replica loss/join** — ``remove_replica`` / a chaos kill requeues
  the victim's queued and in-flight requests onto survivors with a
  typed ``REQUEUED`` transition and a bumped ``Request.attempts`` (zero
  request loss — ``tests/unit/test_fleet.py``'s oracle); per-request
  RNG folds from the seed, so a rerun's bits match a fresh submission.
  ``add_replica`` warms from the fleet's shared compiled-program cache:
  a joining replica serves traffic with ZERO new compiles.
- **Disaggregated prefill/decode** — ``prefill_replicas=k`` dedicates k
  replicas to chunked prefill; a finished prefill is exported from the
  source page pool (:func:`~.pages.export_slot` — gather the request's
  page-table row), moved host-side, and imported into a decode
  replica's pool (:func:`~.pages.import_slot` — scatter into a fresh
  allocation, shared-prefix entries redirected to scratch). The RNG
  chain travels with the payload, so disaggregated output is
  bit-identical to a single engine's (the parity oracle in tier-1).

- **Distributed request tracing** — with ``serving.spans`` on, the
  fleet keeps its OWN span ring (router decisions, requeues, handoff
  export/pending/import hops) next to each replica's lifecycle ring, a
  bounded **route-audit ring** (every route / shed / affinity-fallback
  / requeue with the ranked candidates and per-replica exclusion
  reasons — :meth:`FleetEngine.route_audit`), a per-request
  **hop-latency decomposition** whose non-null hops tile the request's
  e2e wall (:meth:`FleetEngine.request_trace`, ``Fleet/hop_*``
  histograms), and :meth:`FleetEngine.merge_trace` — ONE
  Chrome/Perfetto trace with every replica as a named pid and each
  cross-replica request stitched into a flow. Disabled (the default),
  none of it exists.
- **Correlated incident capture** — with ``serving.flight_dir`` set,
  ANY replica's flight-recorder trigger (watchdog stall, nonfinite
  halt, SIGTERM, manual) redirects into one shared
  ``incident_<stamp>_<reason>/`` directory and fans out: every sibling
  replica dumps too, and the fleet adds ``incident.json``, its ring,
  the route audit, and the merged cross-replica trace. The doctor's
  incident section reconstructs the timeline and gates on an
  unreconciled capture.

``Fleet/*`` metrics land in the fleet's own
:class:`~..observability.metrics.MetricsRegistry` (same sinks as
everything else via :meth:`publish_metrics`); fleet goodput is the
PR-8 rollup math (:func:`~..observability.goodput.rollup_goodput`) over
per-replica ledgers. Everything is host-side — the fleet layer adds no
device programs beyond the export/import pair, no syncs, and no
threads.
"""

from __future__ import annotations

import dataclasses
import json
import math
import threading
import time
from collections import OrderedDict, deque
from pathlib import Path
from typing import Optional

from ..inference.config import ServingConfig
from ..inference.engine import InferenceEngine
from ..observability import spans as _spans
from ..observability.export import HOP_NAMES, hop_trace
from ..observability.metrics import MetricsRegistry
from ..resilience.chaos import FleetChaosConfig, FleetChaosMonkey
from ..resilience.guards import QueueFullError, RequestStatus
from ..utils.logging import log_dist, warning_once
from .engine import _MAX_RESULTS, ServingEngine
from .scheduler import Request

__all__ = ["FleetEngine"]

# Uniform fleets have one role; disaggregated fleets split it. Routing
# matches roles exactly: a prefill replica never takes decode residency
# and vice versa.
ROLE_SERVE = "serve"
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"

# Router decision audit ring capacity (host dicts; ~minutes of context
# around an incident — the flight/incident dump carries it to disk).
_AUDIT_RING = 1024


class FleetEngine:
    """N in-process serving replicas behind one engine-shaped surface.

    ``engine`` supplies params/mesh/model (shared by every replica);
    ``serving`` is the per-replica :class:`ServingConfig` (or dict) —
    replicas are homogeneous by construction. ``prefill_replicas > 0``
    switches to disaggregated roles (requires the paged KV cache — the
    handoff is a page transfer). ``chaos`` takes a
    :class:`~..resilience.chaos.FleetChaosConfig` for deterministic
    replica-kill tests; ``clock`` is injectable and shared with every
    replica, so fake-clock tests drive the whole fleet.
    """

    def __init__(self, engine: InferenceEngine,
                 serving: ServingConfig | dict | None = None,
                 replicas: int = 2, prefill_replicas: int = 0,
                 names: Optional[list] = None, chaos=None,
                 registry=None, clock=None, session_cap: int = 4096,
                 programs: Optional[OrderedDict] = None,
                 tracing: Optional[bool] = None):
        if replicas < 1:
            raise ValueError(f"fleet needs >= 1 replica, got {replicas}")
        if prefill_replicas < 0 or (prefill_replicas
                                    and prefill_replicas >= replicas):
            raise ValueError(
                f"prefill_replicas={prefill_replicas} must be >= 0 and "
                f"leave at least one decode replica (replicas={replicas})")
        self.engine = engine
        if serving is None:
            # the replicas would fall back to engine.config.serving (the
            # ServingEngine default) — validate against THAT config, not
            # a default-constructed one
            serving = engine.config.serving
        self._spec = serving
        cfg0 = ServingConfig.from_any(
            dataclasses.replace(serving) if isinstance(serving,
                                                       ServingConfig)
            else serving)
        self._disagg = prefill_replicas > 0
        if self._disagg and cfg0.page_size == 0:
            raise ValueError(
                "disaggregated prefill/decode needs the paged KV cache "
                "(set serving.page_size) — the handoff is a page transfer")
        tcfg = cfg0.telemetry
        # checked BEFORE any replica binds (below) and again at every
        # later _build_replica, so add_replica() on a 1-replica fleet
        # cannot bind-crash on the same port either
        self._fixed_port_telemetry = bool(
            tcfg is not None and tcfg.enabled and tcfg.port)
        if replicas > 1 and self._fixed_port_telemetry:
            raise ValueError(
                "serving.telemetry with a fixed port cannot be shared by "
                f"{replicas} replicas — use port=0 (ephemeral) or start "
                "telemetry per replica via engine.serve_telemetry()")
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._clock = clock if clock is not None else time.perf_counter
        self._engine_clock = clock
        # fleet-shared seams: ONE compiled-program cache (a joining
        # replica warms from it) and ONE rid namespace (a rid names a
        # request fleet-wide; requeue keeps the id). ``programs`` lets a
        # caller seed the cache from another fleet over the SAME engine
        # and an IDENTICAL serving config (blue/green rollouts, test
        # suites) — programs bake in shapes AND the sampling policy, so
        # sharing across differing configs is a caller bug.
        self._programs: OrderedDict = \
            programs if programs is not None else OrderedDict()
        self._rid_next = [0]

        def _rid():
            rid = self._rid_next[0]
            self._rid_next[0] += 1
            return rid

        self._rid = _rid
        # ---- distributed tracing (docs/OBSERVABILITY.md fleet tracing).
        # Follows the replicas' span knob by default: serving.spans=True
        # gives every replica its ring AND the fleet this router/handoff
        # ring + the route-audit ring. Disabled (the default) builds
        # NEITHER — the fleet layer pays `is not None` checks only, zero
        # new programs (the bench_fleet --smoke compile freeze stays the
        # oracle).
        self._tracing = bool(cfg0.spans) if tracing is None \
            else bool(tracing)
        self.spans: "Optional[_spans.SpanRecorder]" = None
        self._audit: "Optional[deque]" = None
        self._audit_seq = 0
        if self._tracing:
            self.spans = _spans.SpanRecorder(cfg0.spans_ring,
                                             clock=self._clock)
            self._audit = deque(maxlen=_AUDIT_RING)
        # ---- traffic capture (observability/replay.py): the FLEET owns
        # the trace — one stream recording routed submits (with session
        # ids), terminal results, and chaos events (kills/joins/drains),
        # replayable against any topology. Replicas are built with
        # capture stripped (_replica_cfg) so nothing double-records.
        # Off (default) builds nothing.
        self.capture = None
        if cfg0.capture:
            from ..observability.replay import TrafficCapture, capture_meta

            self.capture = TrafficCapture(
                clock=self._clock, ring=cfg0.capture_ring,
                meta=capture_meta(cfg0, engine="fleet",
                                  replicas=replicas,
                                  prefill_replicas=prefill_replicas))
        # ---- correlated incident capture: when the replicas carry
        # flight recorders (serving.flight_dir), any one replica's dump
        # trigger (watchdog stall, nonfinite halt, SIGTERM, manual) is
        # redirected into ONE shared incident dir and fanned out to
        # every other replica + the fleet's own artifacts + a merged
        # trace. No flight_dir = no machinery.
        self._incident_base: Optional[Path] = \
            Path(cfg0.flight_dir) if cfg0.flight_dir is not None else None
        self._incident_open: Optional[Path] = None
        # (dir, fleet iteration) of the newest capture: a second
        # TRIGGER in the same iteration joins it instead of opening a
        # duplicate (two replicas tripping on one event, or a manual
        # /flight/dump racing the serving thread's watchdog)
        self._incident_last: "Optional[tuple[Path, int]]" = None
        self._incident_lock = threading.RLock()
        self._incidents = 0
        self.replicas: "OrderedDict[str, ServingEngine]" = OrderedDict()
        self.roles: dict = {}
        self._draining = False
        self._joined = 0              # monotonic: default-name uniqueness
        if names is not None and len(names) != replicas:
            raise ValueError(f"{len(names)} names for {replicas} replicas")
        try:
            for i in range(replicas):
                if self._disagg:
                    role = (ROLE_PREFILL if i < prefill_replicas
                            else ROLE_DECODE)
                    default = (f"p{i}" if i < prefill_replicas
                               else f"d{i - prefill_replicas}")
                else:
                    role, default = ROLE_SERVE, f"r{i}"
                self._build_replica(
                    names[i] if names is not None else default, role)
        except Exception:
            # a failed build (bad name, port bind, ...) must not leak
            # the replicas — and their telemetry listeners — already up
            for eng_built in self.replicas.values():
                eng_built.close()
            raise
        # router state: rid -> owning replica name; (role, session) ->
        # sticky replica, LRU-bounded so a million sessions can't leak
        self._owner: dict[int, str] = {}
        self._session: OrderedDict = OrderedDict()
        self._session_cap = int(session_cap)
        # finished requests awaiting pickup, bounded exactly like one
        # engine's store; evictions attribute to the OWNING replica
        self.results: "OrderedDict[int, Request]" = OrderedDict()
        self._max_results = _MAX_RESULTS
        # pending prefill→decode handoffs: (request, host payload)
        self._handoffs: list = []
        # requests the FLEET layer itself retired (handoff-deadline
        # timeouts, requeue sheds) — drained into the next step()'s
        # return so its "everything that retired" contract stays true
        self._retired_inline: list = []
        self.chaos: Optional[FleetChaosMonkey] = None
        cc = FleetChaosConfig.from_any(chaos)
        if cc is not None and cc.enabled:
            self.chaos = FleetChaosMonkey(cc)
        # ---- elastic autoscaler (serving/autoscaler.py): the actuation
        # loop over scaling_report(). Off (the default) builds nothing —
        # step() pays one `is not None`, zero threads/programs/syncs
        # (the bench_autoscale --smoke compile freeze is the oracle).
        self.autoscaler = None
        acfg = cfg0.autoscale
        if acfg is not None and getattr(acfg, "enabled", True):
            from .autoscaler import Autoscaler

            self.autoscaler = Autoscaler(self, acfg)
        self._iterations = 0

    # ------------------------------------------------------------ replicas
    def _replica_cfg(self) -> ServingConfig | dict | None:
        """A FRESH config per replica (``reload_slo`` mutates in place —
        replicas must not share one instance). Traffic capture is
        STRIPPED: the fleet records the trace at its own surface (one
        stream, session ids, chaos events); a per-replica capture would
        double-record every request."""
        if isinstance(self._spec, ServingConfig):
            cfg = dataclasses.replace(self._spec)
            cfg.capture = False
            return cfg
        if isinstance(self._spec, dict) and self._spec.get("capture"):
            return {**self._spec, "capture": False}
        return self._spec

    def _build_replica(self, name: str, role: str) -> ServingEngine:
        if name in self.replicas:
            raise ValueError(f"duplicate replica name {name!r}")
        if self.replicas and self._fixed_port_telemetry:
            raise ValueError(
                "serving.telemetry with a fixed port cannot be shared by "
                "multiple replicas — use port=0 (ephemeral) or start "
                "telemetry per replica via engine.serve_telemetry()")
        eng = ServingEngine(self.engine, self._replica_cfg(),
                            clock=self._engine_clock,
                            programs=self._programs, rid_source=self._rid,
                            name=name)
        if role == ROLE_PREFILL:
            eng.on_placed = (lambda req, slot, _n=name:
                             self._on_prefill_placed(_n, req, slot))
        if eng.flight is not None:
            # correlated incident capture: this replica's dump triggers
            # (watchdog stall, nonfinite halt, SIGTERM, manual) redirect
            # into a shared fleet incident dir and fan out to siblings
            eng.flight.redirect = (lambda reason, _n=name:
                                   self._incident_redirect(_n, reason))
        if eng.kvscope is not None:
            # affinity-aware regret (observability/kvscope.py): a resume
            # that re-pays ghost-covered prefill ON THE REPLICA the
            # session was sticky to means affinity routed the session
            # home only for home to have evicted its prefix
            eng.kvscope.on_regret_resume = (
                lambda sid, toks, _n=name:
                self._on_regret_resume(_n, sid, toks))
        if self._draining:
            eng.begin_drain()
        self.replicas[name] = eng
        self.roles[name] = role
        self._joined += 1
        return eng

    def add_replica(self, name: Optional[str] = None,
                    role: Optional[str] = None) -> str:
        """Elastic join: build one more replica over the SAME inference
        engine and the fleet's shared program cache — it serves traffic
        with zero new compiles (warm join; the tier-1 test pins
        ``compiles == 0`` on the joined replica). Returns its name."""
        if role is None:
            role = ROLE_DECODE if self._disagg else ROLE_SERVE
        valid = {ROLE_PREFILL, ROLE_DECODE} if self._disagg \
            else {ROLE_SERVE}
        if role not in valid:
            raise ValueError(f"role {role!r} not in {sorted(valid)} for "
                             "this fleet")
        if name is None:
            stem = {ROLE_SERVE: "r", ROLE_PREFILL: "p",
                    ROLE_DECODE: "d"}[role]
            name = f"{stem}{self._joined}"
            while name in self.replicas:
                self._joined += 1
                name = f"{stem}{self._joined}"
        self._build_replica(name, role)
        self.registry.counter("Fleet/replica_joins").inc()
        if self.capture is not None:
            # role recorded so a disaggregated autoscaled run replays
            # its joins into the right phase
            self.capture.on_chaos("add_replica", name, role=role)
        return name

    def remove_replica(self, name: str) -> list:
        """Planned scale-down: take ``name`` out of the fleet; its
        queued and in-flight requests requeue onto survivors (typed
        ``REQUEUED``, ``attempts`` bumped, original deadlines kept).
        Returns the requeued rids."""
        out = self._remove(name)
        if self.capture is not None:
            self.capture.on_chaos("remove_replica", name)
        return out

    def kill_replica(self, name: str) -> list:
        """Abrupt replica loss (the chaos fault): mechanically identical
        to :meth:`remove_replica` — the router's knowledge of its
        outstanding requests IS the failover source — but counted as a
        kill so dashboards separate incidents from scale-downs. A
        REFUSED kill (unknown name, last replica of a role) raises
        without counting: dashboards never show a phantom incident."""
        out = self._remove(name)
        self.registry.counter("Fleet/replica_kills").inc()
        if self.autoscaler is not None:
            # latch scale-down: the failover's requeue burst and arrival
            # dip must never be read as a remove signal
            self.autoscaler.on_incident("kill_replica", name)
        if self.capture is not None:
            # the chaos script half of the trace: replay re-kills this
            # replica at the same position in the stream
            self.capture.on_chaos("kill_replica", name)
        return out

    def _remove(self, name: str) -> list:
        if name not in self.replicas:
            raise KeyError(f"no replica named {name!r} "
                           f"(have {list(self.replicas)})")
        if len(self.replicas) == 1:
            raise RuntimeError("cannot remove the last replica")
        if self._disagg:
            role = self.roles[name]
            others = [n for n in self.replicas
                      if n != name and self.roles[n] == role]
            if not others:
                raise RuntimeError(
                    f"cannot remove the last {role} replica of a "
                    "disaggregated fleet")
        eng = self.replicas.pop(name)
        self.roles.pop(name)
        # results that retired before the loss are NOT lost: harvest
        for rid in list(eng.results):
            self._adopt_result(eng.pop_result(rid), name)
        # live requests: the prefill lane + every slot + the queue
        live = []
        if eng._prefill is not None:
            live.append(eng._prefill[0])
            eng._prefill = None
        live += eng.sched.take_live()
        # the lane's request may be younger than one that is running
        live.sort(key=lambda r: (r.submit_t, r.rid))
        requeued = []
        requeue_role = ROLE_PREFILL if self._disagg else ROLE_SERVE
        # ONE ranking pass for the whole failover burst (the pattern
        # _pump_handoffs uses): re-ranking per orphan would re-snapshot
        # every survivor's registry exactly when the fleet is absorbing
        # a spike. take_live is oldest-first; iterating it REVERSED
        # (newest-first) against Scheduler.requeue's push-to-head leaves
        # each survivor's queue head oldest-first — the deadline-closest
        # request admits first.
        ranked_infos = self._ranked(requeue_role, admission=False)
        for req in reversed(live):
            self._requeue(req, requeue_role, ranked_infos,
                          lost_replica=name)
            requeued.append(req.rid)
        requeued.reverse()
        # pending handoffs the victim EXPORTED are host-held payloads —
        # they survive its removal — but their owner map still points at
        # it. Clear the ghost entries and re-pump NOW, before the
        # scheduler is gone, so they land on survivors this call instead
        # of waiting (possibly forever, if the fleet idles) for the next
        # step's pump.
        if self._handoffs:
            for req, _payload in self._handoffs:
                if self._owner.get(req.rid) == name:
                    self._owner.pop(req.rid, None)
            self._pump_handoffs()
        eng.close()
        return requeued

    def _requeue(self, req: Request, role: str,
                 ranked: "Optional[list]" = None,
                 lost_replica: str = "") -> None:
        """Move one orphaned request onto a survivor: affinity-aware
        (its session's prefix may live on another replica too), typed
        REQUEUED transition via the survivor's scheduler. Requeue
        bypasses ``max_queue`` — this is already-admitted work, not new
        intake. ``ranked`` (routing-info dicts) lets :meth:`_remove`
        amortize one ranking pass over the whole failover burst."""
        if ranked is None:
            ranked = self._ranked(role, admission=False)
        names = [i["name"] for i in ranked]
        sticky = (self._session.get((role, req.session_id))
                  if req.session_id is not None else None)
        name = sticky if sticky in names else \
            (names[0] if names else None)
        if name is None:
            # no survivor of this role can ever host it: terminal shed
            req.status = RequestStatus.SHED
            req.error = "no surviving replica to requeue onto"
            req.finish_t = self._clock()
            self.registry.counter("Fleet/requeue_sheds").inc()
            self._audit_record("requeue_shed", rid=req.rid, role=role,
                               session_id=req.session_id,
                               tenant_id=req.tenant_id,
                               candidates=ranked,
                               lost_replica=lost_replica)
            self._adopt_result(req, "")
            self._retired_inline.append(req)
            return
        self.replicas[name].requeue(req)
        self._owner[req.rid] = name
        if req.session_id is not None:
            self._stick(role, req.session_id, name)
        self.registry.counter("Fleet/requeued").inc()
        self._audit_record("requeue", rid=req.rid, role=role,
                           session_id=req.session_id, chosen=name,
                           tenant_id=req.tenant_id,
                           sticky=sticky, candidates=ranked,
                           lost_replica=lost_replica)
        if self.spans is not None:
            # the cross-replica hop event: this rid's trace continues
            # on the survivor, attempt bumped (scheduler stamped it)
            self.spans.emit(_spans.REQUEUE, req.requeue_t, rid=req.rid,
                            replica=name, attempt=req.attempts,
                            lost_replica=lost_replica)

    # -------------------------------------------------------------- router
    def _replica_info(self, name: str) -> dict:
        """One replica's routing picture: direct host state (queue,
        slots, drain/degraded/pool flags — the same definitions
        ``health()`` reports, via the engine's shared properties) plus
        ONE registry snapshot for the SLO-burn and goodput gauges.
        Routing runs per admission, so it must not pay ``health()``'s
        full gauge-mirror pass on top."""
        eng = self.replicas[name]
        g = eng.stats.registry.snapshot()["gauges"]
        burn = 0.0
        for k, v in g.items():
            if k.startswith("Serve/slo_") and k.endswith("_burn") \
                    and isinstance(v, float) and not math.isnan(v):
                burn = max(burn, v)
        gp = g.get("Serve/goodput_frac")
        if not isinstance(gp, float) or math.isnan(gp):
            gp = 1.0
        queue_depth = eng.sched.queue_depth
        queue_full = bool(eng.cfg.max_queue
                          and queue_depth >= eng.cfg.max_queue)
        load = (queue_depth + eng.sched.occupancy
                + (1 if eng._prefill is not None else 0)) \
            / max(1, eng.cfg.slots)
        # "would I route here if anyone else could take it": healthy =
        # no exclusion reason holds. The reasons list IS the router's
        # explanation — the audit ring records it verbatim, so every
        # decision is explicable after the fact.
        reasons = []
        if eng.draining:
            reasons.append("draining")
        if queue_full:
            reasons.append("queue_full")
        if eng.degraded:
            reasons.append("degraded")
        if eng.pool_pressure:
            reasons.append("pool_pressure")
        if burn > 1.0:
            reasons.append("slo_burn")
        return {
            "name": name,
            "draining": eng.draining,
            "healthy": not reasons,
            "reasons": reasons,
            "load": load, "burn": burn, "goodput": gp,
        }

    def _ranked(self, role: str, exclude=(), admission: bool = True) \
            -> list:
        """Routing infos of ``role``'s replicas, best-first: healthy
        before unhealthy, then least-loaded, then lowest SLO burn, then
        highest goodput. ``admission=False`` keeps draining replicas in
        the pool (handoffs and requeues are backlog, which a drain must
        finish). Returns the info dicts so callers reuse ONE snapshot
        pass instead of re-reading registries per decision."""
        infos = [self._replica_info(n) for n in self.replicas
                 if self.roles[n] == role and n not in exclude]
        if admission:
            infos = [i for i in infos if not i["draining"]]
        infos.sort(key=lambda i: (0 if i["healthy"] else 1, i["load"],
                                  i["burn"], -i["goodput"], i["name"]))
        return infos

    def _stick(self, role: str, sid, name: str) -> None:
        key = (role, sid)
        self._session[key] = name
        self._session.move_to_end(key)
        if len(self._session) > self._session_cap:
            self._session.popitem(last=False)

    # --------------------------------------------------------- route audit
    def _audit_record(self, event: str, rid: Optional[int] = None,
                      role: Optional[str] = None, session_id=None,
                      tenant_id=None,
                      chosen: Optional[str] = None,
                      sticky: Optional[str] = None,
                      affinity: Optional[str] = None,
                      candidates=(), lost_replica: str = "") -> None:
        """One router decision into the bounded audit ring: the ranked
        candidates with their per-replica exclusion reasons (draining /
        queue_full / degraded / pool_pressure / slo_burn) — why the
        chosen replica won and why every other one didn't. No-op when
        tracing is disabled (the ring doesn't exist)."""
        if self._audit is None:
            return
        self._audit_seq += 1
        entry = {
            "seq": self._audit_seq, "t": self._clock(), "event": event,
            "rid": rid, "role": role, "session_id": session_id,
            "tenant_id": tenant_id,
            "chosen": chosen, "sticky": sticky, "affinity": affinity,
            "candidates": [
                {"name": i["name"], "healthy": i["healthy"],
                 "reasons": list(i["reasons"]),
                 "load": i["load"], "burn": i["burn"],
                 "goodput": i["goodput"],
                 # residency class when the router probed it (session
                 # routes): 0 tree hit / 1 host-tier hit / 2 cold
                 **({"residency": i["residency"]}
                    if "residency" in i else {})}
                for i in candidates],
        }
        if lost_replica:
            entry["lost_replica"] = lost_replica
        self._audit.append(entry)

    def route_audit(self, rid: Optional[int] = None) -> list:
        """The router decision audit: every route / shed /
        affinity-fallback / requeue still in the ring, oldest first —
        filtered to one request when ``rid`` is given. Each entry
        explains the decision: the ranked candidates with per-replica
        exclusion reasons. Empty when tracing is disabled."""
        if self._audit is None:
            return []
        entries = list(self._audit)
        if rid is None:
            return entries
        return [e for e in entries if e.get("rid") == rid]

    def _route(self, role: str, session_id=None, exclude=(),
               prompt=None) -> "tuple[str, dict]":
        """Pick the admission target; raises a typed shed when no
        replica of ``role`` is accepting (all draining/removed).
        Returns ``(name, decision)`` — the decision dict carries the
        ranked candidates and the affinity outcome so :meth:`submit`
        can write ONE audit entry once the rid exists.

        Session routing is RESIDENCY-ranked: among healthy candidates a
        replica whose radix tree holds the prompt's prefix ranks first,
        one whose HOST TIER holds it (evicted but demoted —
        serving/hostkv.py) ranks between tree hit and miss, policy
        (least-loaded) breaks the ties. The sticky replica still wins
        while healthy (it usually IS the tree hit); the ranking decides
        fallbacks and first routes, via read-only residency probes."""
        infos = self._ranked(role, exclude=exclude, admission=False)
        eligible = [i for i in infos if not i["draining"]]
        if not eligible:
            self.registry.counter("Fleet/sheds").inc()
            # the request never got a rid — the shed is still a routing
            # decision someone will ask about
            self._audit_record("shed", role=role, session_id=session_id,
                               candidates=infos)
            raise QueueFullError(
                f"no {role} replica accepting admissions (all draining); "
                "request shed")
        by_name = {i["name"]: i for i in eligible}
        choice = eligible[0]["name"]
        affinity = None
        sticky = None
        if session_id is not None:
            sticky = self._session.get((role, session_id))
            si = by_name.get(sticky) if sticky is not None else None
            sticky_ok = si is not None and si["healthy"]
            if not sticky_ok and prompt is not None:
                # no usable sticky replica: residency-rank the healthy
                # candidates (read-only probes — and ONLY on this
                # fallback/first-route path; a healthy sticky replica
                # wins below without paying the per-replica walks)
                healthy = [i for i in eligible if i["healthy"]]
                for i in healthy:
                    tb, hb = self.replicas[i["name"]] \
                        .prefix_residency(prompt)
                    # 0 = tree hit, 1 = host-tier hit, 2 = cold miss
                    i["residency"] = 0 if tb else (1 if hb else 2)
                if healthy:
                    best = min(healthy,
                               key=lambda i: (i["residency"], i["load"],
                                              i["burn"], -i["goodput"],
                                              i["name"]))
                    choice = best["name"]
            if sticky is not None:
                # stick when the sticky replica is routable AND healthy;
                # otherwise fall back to policy and record the miss (the
                # prefix will be rebuilt — or host-restored — at the new
                # home the residency ranking above picked)
                if sticky_ok:
                    choice = sticky
                if choice == sticky:
                    affinity = "hit"
                    self.registry.counter("Fleet/affinity_hits").inc()
                else:
                    affinity = "miss"
                    self.registry.counter("Fleet/affinity_misses").inc()
            self._stick(role, session_id, choice)
        return choice, {"role": role, "session_id": session_id,
                        "sticky": sticky, "affinity": affinity,
                        "candidates": infos}

    # -------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               seed: int = 0, session_id=None, tenant_id=None,
               ttft_deadline_s: Optional[float] = None,
               total_deadline_s: Optional[float] = None) -> int:
        """Route one request into the fleet; returns its fleet-wide rid.
        Same contract as ``ServingEngine.submit`` plus ``session_id``
        (opaque, hashable): requests of one session prefer the replica
        holding their shared prefix. ``tenant_id`` rides along for
        per-tenant attribution (tenantscope). Raises the same typed
        :class:`QueueFullError` when every eligible replica sheds."""
        role = ROLE_PREFILL if self._disagg else ROLE_SERVE
        tried: set = set()
        last: Optional[QueueFullError] = None
        while True:
            try:
                name, decision = self._route(role, session_id=session_id,
                                             exclude=tried, prompt=prompt)
            except QueueFullError:
                if last is not None:
                    raise last
                raise
            eng = self.replicas[name]
            try:
                rid = eng.submit(prompt, max_new_tokens, seed=seed,
                                 ttft_deadline_s=ttft_deadline_s,
                                 total_deadline_s=total_deadline_s,
                                 session_id=session_id,
                                 tenant_id=tenant_id)
                break
            except QueueFullError as e:
                # this replica flipped to full/draining between the
                # health read and the submit: try the next-best before
                # shedding fleet-wide
                last = e
                tried.add(name)
        req = eng.sched.queue[-1]
        self._owner[rid] = name
        r = self.registry
        r.counter("Fleet/submitted").inc()
        r.counter(f"Fleet/routed_{name}").inc()
        # the decision becomes auditable the moment the rid exists; an
        # affinity fallback is its own event kind so dashboards can
        # count prefix-locality losses without parsing candidates
        self._audit_record(
            "affinity_fallback" if decision["affinity"] == "miss"
            else "route",
            rid=rid, chosen=name, tenant_id=tenant_id, **decision)
        if self.spans is not None:
            # the trace context's first fleet hop: rid → replica. The
            # replica's own ring continues from its queue span.
            self.spans.emit(_spans.ROUTE, req.submit_t, rid=rid,
                            replica=name)
        if self.capture is not None:
            self.capture.on_submit(req, session_id=session_id,
                                   ttft_deadline_s=ttft_deadline_s,
                                   total_deadline_s=total_deadline_s)
        return rid

    def cancel(self, rid: int) -> Optional[Request]:
        """Cancel wherever the request lives — its owning replica or the
        pending-handoff buffer."""
        for i, (req, _payload) in enumerate(self._handoffs):
            if req.rid == rid:
                del self._handoffs[i]
                self.registry.gauge("Fleet/handoff_pending").set(
                    len(self._handoffs))
                req.status = RequestStatus.CANCELLED
                req.error = "cancelled during prefill→decode handoff"
                req.finish_t = self._clock()
                self._adopt_result(req, self._owner.get(rid, ""))
                return req
        name = self._owner.get(rid)
        if name in self.replicas:
            req = self.replicas[name].cancel(rid)
            if req is not None:
                self.replicas[name].pop_result(rid)
                self._adopt_result(req, name)
            return req
        return None

    # ------------------------------------------------------------- serving
    def step(self) -> list:
        """One fleet iteration: chaos hook, pending handoffs, then one
        ``step()`` on every replica. Returns every request that retired
        anywhere in the fleet this iteration; results are also held in
        the fleet's own bounded store for :meth:`pop_result`."""
        out: list = []
        if self.chaos is not None:
            # only offer LEGALLY removable victims (never the last
            # replica, never the last of a disaggregated role) — a
            # chaos fault must inject failure, not crash the router
            victim = self.chaos.maybe_kill(self._killable())
            if victim is not None:
                self.kill_replica(victim)
        if self._handoffs:
            self._pump_handoffs()
        for name in list(self.replicas):
            eng = self.replicas[name]
            for req in eng.step():
                eng.pop_result(req.rid)
                self._adopt_result(req, name)
                out.append(req)
        if self.autoscaler is not None:
            # after the replica loop (safe to mutate the replicas dict)
            # and before the inline-retire drain, so anything a removal
            # sheds rides THIS step's return
            self.autoscaler.on_step()
        if self._retired_inline:
            # retirements the fleet layer itself produced (handoff
            # timeouts, requeue sheds) ride the same return channel
            out.extend(self._retired_inline)
            self._retired_inline = []
        if self._tracing:
            for req in out:
                self._observe_hops(req)
        self._iterations += 1
        self.registry.counter("Fleet/iterations").inc()
        return out

    def _observe_hops(self, req: Request) -> None:
        """One retired request's hop decomposition into the
        ``Fleet/hop_*`` histograms (p50/p99 per hop across the fleet —
        the aggregate view of :meth:`request_trace`). Null hops (e.g.
        handoff on a uniform fleet) are skipped, not recorded as 0."""
        tr = hop_trace(req)
        r = self.registry
        for h in HOP_NAMES + ("e2e",):
            v = tr.get(f"{h}_s")
            if v is not None:
                r.histogram(f"Fleet/hop_{h}_s").observe(v)

    def _killable(self) -> list:
        """Replica names whose removal :meth:`_remove` would accept."""
        if len(self.replicas) <= 1:
            return []
        if not self._disagg:
            return list(self.replicas)
        counts: dict = {}
        for n in self.replicas:
            counts[self.roles[n]] = counts.get(self.roles[n], 0) + 1
        return [n for n in self.replicas if counts[self.roles[n]] > 1]

    def _on_prefill_placed(self, name: str, req: Request,
                           slot: int) -> None:
        """The disaggregation seam (``ServingEngine.on_placed``): a
        prefill replica just seated a finished prefill — export its
        pages to host, release the slot (the prompt's blocks stay in the
        source tree for future sharing), queue the handoff. The takeover
        happens via these side effects; the hook returns nothing."""
        eng = self.replicas[name]
        t0 = self._clock()
        payload = eng.export_request(req)
        eng.release_request(req)
        # export stamp is unconditional (two host clock reads): hop_trace
        # needs it to tell "died waiting for a decode slot" apart from
        # "decoded" even when tracing is off
        req.export_t = self._clock()
        if self.spans is not None:
            # the export hop: pages gathered to host on the source
            # replica — the first fleet-side leg of this rid's trace
            self.spans.emit(_spans.HANDOFF_EXPORT, t0, req.export_t,
                            rid=req.rid, replica=name,
                            **({"attempt": req.attempts}
                               if req.attempts else {}))
        self._handoffs.append((req, payload))
        self.registry.counter("Fleet/handoffs").inc()
        self.registry.gauge("Fleet/handoff_pending").set(
            len(self._handoffs))

    def _pump_handoffs(self) -> None:
        """Try to land every pending handoff on a decode replica:
        affinity-aware, best-ranked first, and a destination that cannot
        take it right now (no free slot / pool pressure) just leaves the
        payload host-held for the next iteration. Expired deadlines
        retire here — a handed-off request is in no scheduler's sweep.
        The ranking snapshot is taken ONCE per pump and refreshed only
        after a successful import changes a replica's load — not per
        pending request (handoffs pile up exactly when this loop runs
        hottest)."""
        remaining = []

        def _targets() -> list:
            # prefer replicas still accepting intake: an import onto a
            # DRAINING decode replica gives it new work exactly when a
            # scale-down is waiting for it to idle. Fall back to the
            # draining pool only when EVERY decode replica drains
            # (fleet-wide drain: handoffs are backlog and must finish).
            infos = self._ranked(ROLE_DECODE, admission=False)
            open_ = [i["name"] for i in infos if not i["draining"]]
            return open_ if open_ else [i["name"] for i in infos]

        ranked = _targets()
        for req, payload in self._handoffs:
            now = self._clock()
            if req.deadline_total is not None and now >= req.deadline_total:
                req.status = RequestStatus.TIMEOUT
                req.error = "total deadline expired during handoff"
                req.finish_t = now
                self.registry.counter("Fleet/handoff_timeouts").inc()
                if self.spans is not None:
                    self.spans.emit(_spans.MARKER, now,
                                    name="handoff_timeout", rid=req.rid)
                self._adopt_result(req, self._owner.get(req.rid, ""))
                self._retired_inline.append(req)
                continue
            order = list(ranked)
            sticky = (self._session.get((ROLE_DECODE, req.session_id))
                      if req.session_id is not None else None)
            if sticky in order:
                order.remove(sticky)
                order.insert(0, sticky)
            placed = False
            for name in order:
                if self.replicas[name].import_request(req, payload):
                    self._owner[req.rid] = name
                    if req.session_id is not None:
                        self._stick(ROLE_DECODE, req.session_id, name)
                    self.registry.counter("Fleet/handoff_imports").inc()
                    if self.spans is not None:
                        # the pending + import hops: host-held wait,
                        # then the scatter into the decode replica (the
                        # engine stamped import_t0/t1 on the request)
                        att = ({"attempt": req.attempts}
                               if req.attempts else {})
                        if req.export_t is not None \
                                and req.import_t0 is not None:
                            self.spans.emit(_spans.HANDOFF_PENDING,
                                            req.export_t,
                                            req.import_t0, rid=req.rid,
                                            **att)
                        if req.import_t0 is not None \
                                and req.import_t1 is not None:
                            self.spans.emit(_spans.HANDOFF_IMPORT,
                                            req.import_t0, req.import_t1,
                                            rid=req.rid, replica=name,
                                            **att)
                    placed = True
                    ranked = _targets()
                    break
            if not placed:
                remaining.append((req, payload))
        self._handoffs = remaining
        self.registry.gauge("Fleet/handoff_pending").set(
            len(self._handoffs))

    def _adopt_result(self, req: Request, name: str) -> None:
        if self.capture is not None:
            # every terminal path funnels through adoption; the capture
            # dedupes by rid, so late re-visits (loss harvest) are safe
            self.capture.on_result(req)
        self.results[req.rid] = req
        if name:
            self._owner[req.rid] = name
        if len(self.results) > self._max_results:
            old_rid, _old = self.results.popitem(last=False)
            owner = self._owner.pop(old_rid, None)
            rep = self.replicas.get(owner)
            if rep is not None:
                # the eviction is attributed to the replica that served
                # the request — its Serve/results_evicted counter is the
                # one dashboards already watch
                rep.stats.on_results_evicted()
            self.registry.counter("Fleet/results_evicted").inc()
            warning_once(
                f"fleet results store hit its cap ({self._max_results}); "
                "evicting oldest finished requests — collect results via "
                "step()'s return value or pop_result()")

    def pop_result(self, rid: int) -> Optional[Request]:
        """Collect (and release) a finished request by rid, regardless
        of which replica retired it — routed by rid through the owner
        map, never a scan."""
        req = self.results.pop(rid, None)
        if req is None:
            name = self._owner.get(rid)
            if name in self.replicas:
                req = self.replicas[name].pop_result(rid)
        if req is not None:
            self._owner.pop(rid, None)
        return req

    # ---------------------------------------------------------- lifecycle
    def begin_drain(self) -> None:
        """Fleet-wide drain: every replica stops admitting (new submits
        shed typed); queued, running, and handed-off requests finish."""
        self._draining = True
        for eng in self.replicas.values():
            eng.begin_drain()
        if self.capture is not None:
            self.capture.on_chaos("begin_drain")

    def end_drain(self) -> None:
        self._draining = False
        for eng in self.replicas.values():
            eng.end_drain()
        if self.capture is not None:
            self.capture.on_chaos("end_drain")

    def begin_drain_replica(self, name: str) -> None:
        """Drain ONE replica (the scale-down prelude): its intake
        closes — the router stops admitting to it, handoffs route to
        its siblings — while its queued/running backlog finishes.
        Recorded as a replica-scoped chaos event so an autoscaled run
        replays its drain edges deterministically."""
        if name not in self.replicas:
            raise KeyError(f"no replica named {name!r} "
                           f"(have {list(self.replicas)})")
        self.replicas[name].begin_drain()
        self.registry.counter("Fleet/replica_drains").inc()
        if self.capture is not None:
            self.capture.on_chaos("begin_drain", name)

    def end_drain_replica(self, name: str) -> None:
        """Reopen one replica's intake (drain aborted: load reversed,
        or an operator changed their mind). No-op on a fleet-wide
        drain — that outranks per-replica state."""
        if name not in self.replicas:
            raise KeyError(f"no replica named {name!r} "
                           f"(have {list(self.replicas)})")
        if self._draining:
            return
        self.replicas[name].end_drain()
        if self.capture is not None:
            self.capture.on_chaos("end_drain", name)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def idle(self) -> bool:
        return (not self._handoffs
                and all(e.sched.idle and e._prefill is None
                        for e in self.replicas.values()))

    def drain(self, max_iterations: int = 1_000_000) -> dict:
        """Graceful fleet shutdown: drain mode, run until every replica
        is idle and no handoff is pending, return the fleet results."""
        self.begin_drain()
        it = 0
        while not self.idle:
            self.step()
            it += 1
            if it > max_iterations:
                raise RuntimeError(
                    f"fleet failed to drain in {max_iterations} "
                    "iterations — scheduler stuck?")
        return self.results

    def serve_batch(self, prompts, max_new_tokens=None, seeds=None,
                    session_ids=None, tenant_ids=None) -> list:
        """Convenience mirror of ``ServingEngine.serve_batch`` across the
        fleet: submit, drive, return each request's tokens in submission
        order (results popped)."""
        import numpy as np

        from .engine import expand_per_request

        n = len(prompts)
        mn = expand_per_request(max_new_tokens, n, None, int)
        sd = expand_per_request(seeds, n, 0, int)
        sid = expand_per_request(session_ids, n, None)
        tid = expand_per_request(tenant_ids, n, None)
        rids = [self.submit(p, mn[i], seed=sd[i], session_id=sid[i],
                            tenant_id=tid[i])
                for i, p in enumerate(prompts)]
        want = set(rids)
        got: dict = {}
        it = 0
        while len(got) < n:
            for req in self.step():
                if req.rid in want:
                    got[req.rid] = req
                    self.results.pop(req.rid, None)
                    self._owner.pop(req.rid, None)
            it += 1
            if it > 1_000_000:
                raise RuntimeError("fleet serve_batch failed to finish — "
                                   "scheduler stuck?")
        return [np.asarray(got[r].tokens, np.int32) for r in rids]

    # ------------------------------------------------------------- readout
    def health(self) -> dict:
        """Fleet liveness/readiness rollup + per-replica snapshots,
        mirrored to ``Fleet/*`` gauges (replicas/ready/queue/occupancy/
        handoffs) so the scrape surface carries the router's picture."""
        per = {name: eng.health() for name, eng in self.replicas.items()}
        ready = sum(1 for h in per.values() if h["ready"])
        out = {
            "replicas": len(per),
            "ready_replicas": ready,
            "ready": ready > 0 and not self._draining,
            "state": "draining" if self._draining else "serving",
            "queue_depth": sum(h["queue_depth"] for h in per.values()),
            "occupancy": sum(h["occupancy"] for h in per.values()),
            "handoff_pending": len(self._handoffs),
            "iterations": self._iterations,
            "roles": dict(self.roles),
            "per_replica": per,
        }
        self.registry.set_gauges({
            "Fleet/replicas": float(out["replicas"]),
            "Fleet/replicas_ready": float(ready),
            "Fleet/ready": float(out["ready"]),
            "Fleet/queue_depth": float(out["queue_depth"]),
            "Fleet/occupancy": float(out["occupancy"]),
            "Fleet/handoff_pending": float(len(self._handoffs)),
        })
        return out

    def _on_regret_resume(self, name: str, session_id, tokens: int) \
            -> None:
        """A replica's kvscope reported a regretted resume (the session
        came back and re-paid ghost-covered prefill there). Fleet-wide
        it always counts; when the session was STICKY to that very
        replica it is an affinity regret — the router sent the session
        home for its prefix, and home had evicted it. That is the
        failure a host KV tier (or smarter eviction) removes."""
        r = self.registry
        r.counter("Fleet/resume_regrets").inc()
        r.counter("Fleet/resume_regret_tokens").inc(tokens)
        role = ROLE_PREFILL if self._disagg else ROLE_SERVE
        if self._session.get((role, session_id)) == name:
            r.counter("Fleet/affinity_regret").inc()
            r.counter("Fleet/affinity_regret_tokens").inc(tokens)

    def kv_residency(self) -> Optional[dict]:
        """Fleet-wide KV residency rollup: every replica's kvscope
        snapshot plus the affinity-aware regret counters only the
        router can attribute. None when no replica runs the observatory
        (``serving.kvscope`` off)."""
        per = {}
        for n, e in self.replicas.items():
            if e.kvscope is None:
                continue
            s = e.kvscope.snapshot()
            if e.hostkv is not None:
                s["host_tier"] = e.hostkv.snapshot()
            if e.nvmekv is not None:
                s["nvme_tier"] = e.nvmekv.snapshot()
            per[n] = s
        if not per:
            return None
        c = self.registry.snapshot()["counters"]
        totals = {
            "regret_tokens": sum((s["regret"]["regret_tokens"])
                                 for s in per.values()),
            "prefill_tokens_paid": sum(s["regret"]["prefill_tokens_paid"]
                                       for s in per.values()),
            "sessions_resumed": sum(s["sessions"]["resumed"]
                                    for s in per.values()),
            "regret_resumes": sum(s["sessions"]["regret_resumes"]
                                  for s in per.values()),
            "host_restored_resumes": sum(
                s["sessions"].get("host_restored_resumes", 0)
                for s in per.values()),
            "host_tier_restores": sum(
                (s.get("host_tier") or {}).get("restores", 0)
                for s in per.values()),
            "host_tier_bytes": sum(
                (s.get("host_tier") or {}).get("bytes", 0)
                for s in per.values()),
            # the disk rung, rolled up beside the DRAM rung: verified
            # promotions (blocks read back), resident bytes, and the
            # fallbacks/aio-errors ops gates on fleet-wide
            "nvme_tier_promotions": sum(
                (s.get("nvme_tier") or {}).get("promotions", 0)
                for s in per.values()),
            "nvme_tier_bytes": sum(
                (s.get("nvme_tier") or {}).get("bytes", 0)
                for s in per.values()),
            "nvme_tier_fallbacks": sum(
                (s.get("nvme_tier") or {}).get("fallbacks", 0)
                for s in per.values()),
            "nvme_aio_errors": sum(
                (s.get("nvme_tier") or {}).get("aio_errors", 0)
                for s in per.values()),
        }
        totals["regret_frac"] = (
            totals["regret_tokens"] / totals["prefill_tokens_paid"]
            if totals["prefill_tokens_paid"] else 0.0)
        return {
            "replicas": per,
            "totals": totals,
            "fleet": {
                "resume_regrets": int(c.get("Fleet/resume_regrets", 0)),
                "resume_regret_tokens": int(
                    c.get("Fleet/resume_regret_tokens", 0)),
                "affinity_regret": int(c.get("Fleet/affinity_regret", 0)),
                "affinity_regret_tokens": int(
                    c.get("Fleet/affinity_regret_tokens", 0)),
            },
        }

    def fleet_goodput(self) -> Optional[dict]:
        """The PR-8 rollup math over per-replica goodput ledgers
        (wall-weighted fraction, summed buckets), exported as
        ``Fleet/goodput_*`` gauges. None when no replica has a ledger
        (``serving.goodput`` off).

        With self-speculative decoding on anywhere in the fleet, the
        rollup also carries the fleet-wide accepted-tokens-per-step
        multiple (summed emitted tokens over summed slot-steps across
        replicas running the lane) — the decode-throughput multiplier
        the goodput fraction alone cannot see, since a verify step is
        one productive iteration whether it commits 1 token or 5."""
        from ..observability.goodput import rollup_goodput

        snaps = [eng.goodput.snapshot() for eng in self.replicas.values()
                 if eng.goodput is not None]
        spec = [s for s in (eng.spec_snapshot()
                            for eng in self.replicas.values())
                if s is not None]
        if not snaps and not spec:
            return None
        roll = rollup_goodput(snaps) if snaps else {
            "wall_s": 0.0, "productive_s": 0.0, "badput_total_s": 0.0,
            "goodput_frac": None}
        gauges = {"Fleet/goodput_wall_s": roll["wall_s"],
                  "Fleet/goodput_productive_s": roll["productive_s"],
                  "Fleet/goodput_badput_total_s": roll["badput_total_s"]}
        if roll["goodput_frac"] is not None:
            gauges["Fleet/goodput_frac"] = roll["goodput_frac"]
        if spec:
            steps = sum(s["slot_steps"] for s in spec)
            emitted = sum(s["emitted_tokens"] for s in spec)
            roll["speculation"] = {
                "replicas": len(spec),
                "slot_steps": steps,
                "emitted_tokens": emitted,
                "accepted_tokens": sum(s["accepted_tokens"] for s in spec),
                "proposed_tokens": sum(s["proposed_tokens"] for s in spec),
                "accepted_tokens_per_step":
                    (emitted / steps) if steps else None,
            }
            if steps:
                gauges["Fleet/spec_accepted_tokens_per_step"] = \
                    emitted / steps
        self.registry.set_gauges(gauges)
        return roll

    def scaling_report(self) -> Optional[dict]:
        """Fleet-wide arrival & scaling rollup over per-replica loadscope
        snapshots (``observability/loadscope.py``): summed offered load,
        the bottleneck utilization, the nearest SLO time-to-violation,
        and the scaling what-ifs — add_replica / remove_replica / the
        prefill↔decode rebalance a disaggregated fleet can make —
        scored at fleet size. Exported as ``Fleet/arrival_*`` /
        ``Fleet/utilization_max`` / ``Fleet/slo_ttv_min_s`` gauges.
        None when no replica runs the observatory (``serving.loadscope``
        off); per-replica unmeasured inputs degrade the dependent
        aggregates to None, never raise."""
        from ..observability.loadscope import (SCALING_SCHEMA,
                                               score_what_ifs)

        per = {}
        for n, e in self.replicas.items():
            if getattr(e, "loadscope", None) is None:
                continue
            snap = e.scaling_snapshot()
            if snap is not None:
                per[n] = snap

        if not per:
            return None

        def _vals(section, key):
            vs = [(s.get(section) or {}).get(key) for s in per.values()]
            return [v for v in vs if v is not None]

        rates = _vals("arrival", "rate_per_s")
        offered = _vals("arrival", "offered_tokens_per_s")
        off_dec = _vals("arrival", "decode_tokens_per_s")
        off_pre = _vals("arrival", "prompt_tokens_per_s")
        serviceable = _vals("service", "serviceable_decode_tokens_per_s")
        svc_pre = _vals("service", "prefill_tokens_per_s")
        rhos = _vals("utilization", "rho")
        cvs = _vals("arrival", "interarrival_cv")
        svc_means = _vals("utilization", "mean_service_s")
        ttvs = _vals("forecast", "slo_ttv_s")

        offered_total = sum(offered) if offered else None
        serviceable_total = sum(serviceable) if serviceable else None
        # fleet ρ is PER PHASE over the measured replicas only (honest
        # when some replica's spans are off — its load is also
        # excluded), then the bottleneck max: decode demand over decode
        # capacity, prompt demand over prefill capacity
        rho_dec_fleet = (sum(off_dec) / serviceable_total
                         if off_dec and serviceable_total else None)
        rho_pre_fleet = (sum(off_pre) / sum(svc_pre)
                         if off_pre and svc_pre and sum(svc_pre) > 0
                         else None)
        rho_fleet = (max(v for v in (rho_dec_fleet, rho_pre_fleet)
                         if v is not None)
                     if rho_dec_fleet is not None
                     or rho_pre_fleet is not None else None)
        rho_prefill = rho_decode = None
        pr_count = sum(1 for r in self.roles.values()
                       if r == ROLE_PREFILL)
        if self._disagg:
            pre = [(per[n].get("utilization") or {}).get("rho")
                   for n in per if self.roles.get(n) == ROLE_PREFILL]
            dec = [(per[n].get("utilization") or {}).get("rho")
                   for n in per if self.roles.get(n) == ROLE_DECODE]
            pre = [v for v in pre if v is not None]
            dec = [v for v in dec if v is not None]
            rho_prefill = max(pre) if pre else None
            rho_decode = max(dec) if dec else None

        slots = next(iter(self.replicas.values())).cfg.slots
        cfg0 = next(iter(per.values()))
        rho_high = ((cfg0.get("utilization") or {}).get("rho_high")
                    or 0.85)
        what_ifs = score_what_ifs(
            rho=rho_fleet if rho_fleet is not None
            else (max(rhos) if rhos else None),
            replicas=len(self.replicas), slots=slots,
            mean_service_s=(sum(svc_means) / len(svc_means)
                            if svc_means else None),
            arrival_cv=(sum(cvs) / len(cvs) if cvs else None),
            rho_high=rho_high, rho_prefill=rho_prefill,
            rho_decode=rho_decode, prefill_replicas=pr_count)

        gauges = {}
        if rates:
            gauges["Fleet/arrival_rate_per_s"] = sum(rates)
        if offered_total is not None:
            gauges["Fleet/offered_tokens_per_s"] = offered_total
        if rhos:
            gauges["Fleet/utilization_max"] = max(rhos)
        if ttvs:
            gauges["Fleet/slo_ttv_min_s"] = min(ttvs)
        self.registry.set_gauges(gauges)

        return {
            "schema": SCALING_SCHEMA,
            "replicas": per,
            "fleet": {
                "replica_count": len(self.replicas),
                "prefill_replicas": pr_count,
                "arrival_rate_per_s": sum(rates) if rates else None,
                "offered_tokens_per_s": offered_total,
                "serviceable_tokens_per_s": serviceable_total,
                "rho": rho_fleet,
                "rho_prefill": (rho_prefill if self._disagg
                                else rho_pre_fleet),
                "rho_decode": (rho_decode if self._disagg
                               else rho_dec_fleet),
                "utilization_max": max(rhos) if rhos else None,
                "slo_ttv_min_s": min(ttvs) if ttvs else None,
            },
            "what_ifs": what_ifs,
        }

    def metrics_snapshot(self) -> dict:
        # refresh the derived gauges FIRST (publish_metrics order) so
        # the "fleet" section carries current health/goodput, not the
        # previous call's
        self.health()
        gp = self.fleet_goodput()
        sc = self.scaling_report()
        snap = self.registry.snapshot()
        out = {
            "iterations": self._iterations,
            "fleet": {**snap["counters"], **snap["gauges"]},
            "replicas": {name: {"role": self.roles[name],
                                "compiles": eng.compiles,
                                **eng.stats.snapshot()}
                         for name, eng in self.replicas.items()},
        }
        if gp is not None:
            out["goodput"] = gp
        if sc is not None:
            out["scaling"] = sc
        return out

    def requests_table(self) -> list:
        """Fleet-wide in-flight table: every replica's rows plus the
        pending-handoff residents, each labeled with its replica."""
        rows = []
        for name, eng in self.replicas.items():
            for row in eng.requests_table():
                row["replica"] = name
                rows.append(row)
        for req, _payload in self._handoffs:
            rows.append({"rid": req.rid, "state": "handoff", "slot": None,
                         "prompt_len": req.prompt_len,
                         "max_new": req.max_new,
                         "tokens": len(req.tokens),
                         "submit_t": req.submit_t, "admit_t": req.admit_t,
                         "deadline_ttft": req.deadline_ttft,
                         "deadline_total": req.deadline_total,
                         "status": req.status.value,
                         "attempts": req.attempts,
                         "trace": hop_trace(req),
                         # the SOURCE replica that produced the payload:
                         # a stuck handoff must be attributable
                         "replica": self._owner.get(req.rid)})
        return rows

    # ------------------------------------------------- distributed tracing
    def request_trace(self, rid: int) -> Optional[dict]:
        """One request's end-to-end hop-latency decomposition
        (``queue_wait/prefill/handoff_wait/import/decode/e2e`` — see
        :func:`~..observability.export.hop_trace`), wherever the request
        currently lives: the fleet results store, the pending-handoff
        buffer, or its owning replica (results or live). The non-null
        hops of a completed request tile ``[submit, finish]`` — their
        sum IS the e2e wall (the documented invariant, pinned on the
        fake clock). Works with tracing disabled — the hops come from
        host timestamps on the request, not from any span ring. None
        for an unknown (or evicted) rid."""
        owner = self._owner.get(rid)
        req = self.results.get(rid)
        state = None
        if req is None:
            for r, _payload in self._handoffs:
                if r.rid == rid:
                    req, state = r, "handoff"
                    break
        if req is not None:
            out = {"rid": rid, "status": req.status.value,
                   "finished": req.finished, "slot": req.slot,
                   "tokens": len(req.tokens), "hops": hop_trace(req)}
            if state is not None:
                out["state"] = state
        else:
            if owner not in self.replicas:
                return None
            out = self.replicas[owner].request_trace(rid)
            if out is None:
                return None
        out["replica"] = owner
        return out

    def merge_trace(self, job_name: str = "fleet") -> dict:
        """ONE Chrome/Perfetto trace for the whole fleet: every live
        replica's span ring under its own pid, the fleet ring (router
        decisions, handoff hops) under a ``router`` pid, and each
        cross-replica request stitched into a flow — see
        :func:`~..observability.export.merge_fleet_trace`. Empty when
        tracing is disabled (no rings exist)."""
        from ..observability.export import merge_fleet_trace

        rings = {n: e.spans.events() for n, e in self.replicas.items()
                 if e.spans is not None}
        return merge_fleet_trace(
            rings,
            self.spans.events() if self.spans is not None else None,
            job_name=job_name)

    # ----------------------------------------------------------- incidents
    def _incident_redirect(self, name: str, reason: str) \
            -> Optional[Path]:
        """The per-replica flight-recorder redirect hook: replica
        ``name`` is about to dump for ``reason``. The first trigger
        opens a shared incident (fanning the dump out to every
        sibling); a sibling asked to dump DURING the fan-out — or a
        second trigger within the same fleet iteration (one event,
        several tripwires) — gets the existing incident's per-replica
        subdirectory instead of opening a duplicate."""
        with self._incident_lock:
            if self._incident_open is not None:
                return self._incident_open / name
            last = self._incident_last
            if last is not None and last[1] == self._iterations:
                # join: this iteration's incident already captured the
                # fleet (this replica's fan-out dump included); a second
                # dump from the same replica lands beside it suffixed
                return last[0] / name
            d = self._open_incident(reason, trigger=name)
            return None if d is None else d / name

    def dump_incident(self, reason: str = "manual") -> Optional[Path]:
        """Correlated capture NOW: every replica's flight recorder dumps
        into one shared incident directory, alongside the fleet's own
        artifacts (router/handoff ring, route audit, merged trace).
        Returns the incident directory, or None when no replica carries
        a flight recorder (``serving.flight_dir`` unset)."""
        with self._incident_lock:
            if self._incident_open is not None:
                return self._incident_open
            return self._open_incident(reason, trigger=None)

    def _open_incident(self, reason: str,
                       trigger: Optional[str]) -> Optional[Path]:
        """Create ``<flight_dir>/incident_<stamp>_<reason>`` and fan the
        capture out: every replica except ``trigger`` (whose own dump is
        already in flight, redirected here) dumps into its subdirectory;
        the fleet writes ``incident.json`` (the shared incident id +
        which replicas were live), its ring, the route audit, and the
        merged cross-replica trace under ``fleet/``. Caller holds
        ``_incident_lock``."""
        from ..observability.flight import sanitize_reason, unique_dir

        if self._incident_base is None:
            return None
        stamp = time.strftime("%Y%m%d-%H%M%S")
        safe = sanitize_reason(reason, fallback="incident")
        d = unique_dir(self._incident_base / f"incident_{stamp}_{safe}")
        try:
            d.mkdir(parents=True)
        except OSError as e:
            log_dist(f"fleet incident capture: cannot create {d} "
                     f"({e!r})", ranks=[0], level="WARNING")
            return None
        self._incidents += 1
        self.registry.counter("Fleet/incidents").inc()
        if self.spans is not None:
            self.spans.emit(_spans.MARKER, self._clock(), name="incident",
                            reason=reason, incident=d.name,
                            trigger=trigger or "")
        self._incident_open = d
        dumped = []
        try:
            for n, eng in self.replicas.items():
                if n == trigger:
                    dumped.append(n)   # its dump is in flight, into d/n
                    continue
                if eng.flight is not None \
                        and eng.flight.dump(f"incident {reason}",
                                            into=d / n) is not None:
                    dumped.append(n)
            self._write_incident_artifacts(d, reason, trigger, dumped)
        finally:
            self._incident_open = None
            self._incident_last = (d, self._iterations)
        log_dist(f"fleet incident capture: {len(dumped)}/"
                 f"{len(self.replicas)} replicas dumped to {d} "
                 f"(reason: {reason})", ranks=[0], level="WARNING")
        return d

    def _write_incident_artifacts(self, d: Path, reason: str,
                                  trigger: Optional[str],
                                  dumped: list) -> None:
        """The fleet's half of an incident dir. Per-artifact write
        guards, like the flight recorder's: incident capture runs on
        failure paths — one bad artifact must not lose the rest."""
        from ..observability.flight import _json_default

        fd = d / "fleet"

        def _w(name, write):
            try:
                write()
            except Exception as e:
                try:
                    (d / (name + ".error")).write_text(repr(e),
                                                       encoding="utf-8")
                except OSError:
                    pass

        def _w_manifest():
            (d / "incident.json").write_text(json.dumps({
                "incident_id": d.name, "reason": reason,
                "trigger_replica": trigger,
                "wall_time": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "clock_now": self._clock(),
                "replicas_live": len(self.replicas),
                "replicas": list(self.replicas),
                "roles": dict(self.roles),
                "dumped": dumped,
                "handoff_pending": len(self._handoffs),
            }, indent=2, default=str), encoding="utf-8")

        def _w_fleet_events():
            fd.mkdir(exist_ok=True)
            with open(fd / "events.jsonl", "w", encoding="utf-8") as f:
                for ev in self.spans.events():
                    f.write(json.dumps(ev.as_dict(),
                                       separators=(",", ":"),
                                       default=_json_default) + "\n")

        def _w_audit():
            fd.mkdir(exist_ok=True)
            with open(fd / "route_audit.jsonl", "w",
                      encoding="utf-8") as f:
                for entry in self.route_audit():
                    f.write(json.dumps(entry, separators=(",", ":"),
                                       default=str) + "\n")

        def _w_trace():
            fd.mkdir(exist_ok=True)
            (fd / "trace_merged.json").write_text(
                json.dumps(self.merge_trace(), default=_json_default),
                encoding="utf-8")

        def _w_capture():
            fd.mkdir(exist_ok=True)
            (fd / "traffic_trace.jsonl").write_text(
                self.capture.tail_text(), encoding="utf-8")

        def _w_autoscale():
            fd.mkdir(exist_ok=True)
            (fd / "autoscale_audit.jsonl").write_text(
                self.autoscaler.audit_jsonl(), encoding="utf-8")

        _w("incident.json", _w_manifest)
        if self.spans is not None:
            _w("events.jsonl", _w_fleet_events)
            _w("route_audit.jsonl", _w_audit)
            _w("trace_merged.json", _w_trace)
        if self.capture is not None:
            # the capture ring's tail: the incident is replayable
            # standing alone (docs/OPERATIONS.md incident-replay runbook)
            _w("traffic_trace.jsonl", _w_capture)
        if self.autoscaler is not None:
            # the decision ring: WHY the fleet was the size it was when
            # the incident hit (docs/OPERATIONS.md autoscaler runbook)
            _w("autoscale_audit.jsonl", _w_autoscale)

    def publish_metrics(self, monitor, step: Optional[int] = None) -> int:
        """Push ``Fleet/*`` (health rollup + goodput refreshed first)
        through a monitor fan-out, same contract as the engines'."""
        from ..observability.metrics import publish_registry

        self.health()
        self.fleet_goodput()
        return publish_registry(self.registry, monitor, step,
                                default_step_counter="Fleet/iterations")

    def autoscale_audit(self) -> list:
        """The autoscaler's decision ring (oldest first, plain dicts);
        empty when no autoscaler is attached."""
        if self.autoscaler is None:
            return []
        return self.autoscaler.audit_entries()

    def serve_telemetry(self, host: str = "127.0.0.1", port: int = 0,
                        token: str = "") -> int:
        """Start the FLEET's ops surface (the router's view — distinct
        from any per-replica server): ``/metrics`` (``Fleet/*``),
        ``/healthz``-``/readyz`` (the health rollup), ``/scaling`` (the
        fleet scaling report), and — when the autoscaler is on —
        ``GET /autoscale`` (status + decision audit tail) and the
        token-gated ``POST /autoscale`` freeze/pin override. Returns
        the bound port; idempotent while running."""
        from ..observability.server import TelemetryHooks, TelemetryServer

        if getattr(self, "telemetry", None) is not None:
            return self.telemetry.port
        reg = self.registry

        def refresh():
            self.health()
            self.fleet_goodput()

        asc = self.autoscaler
        hooks = TelemetryHooks(
            registry=reg,
            step_fn=lambda: int(reg.counter("Fleet/iterations").value),
            refresh_fn=refresh,
            health_fn=self.health,
            scaling_fn=self.scaling_report,
            dump_fn=((lambda: self.dump_incident("manual"))
                     if self._incident_base is not None else None),
            autoscale_fn=(asc.status if asc is not None else None),
            autoscale_control_fn=(asc.control if asc is not None
                                  else None))
        server = TelemetryServer(hooks, host=host, port=port, token=token)
        bound = server.start()
        self.telemetry = server
        return bound

    def close(self) -> None:
        """Teardown every replica (telemetry listeners etc.) and the
        fleet's own telemetry server; the fleet object is not reusable
        afterwards."""
        if getattr(self, "telemetry", None) is not None:
            self.telemetry.close()
            self.telemetry = None
        for eng in self.replicas.values():
            eng.close()
