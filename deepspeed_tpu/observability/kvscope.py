"""KV residency observatory: eviction regret, session heat, host-tier math.

The ROADMAP's tiered-KV wall ("host-offloaded pages for million-session
residency") starts from a cost the paged cache pays silently today: when
``PagePool._evict`` reclaims tree-held pages under pressure, the NEXT
admission of the same prefix re-pays its prefill. ZeRO-Infinity's
memory-wall playbook (PAPERS.md) would demote those idle pages to pinned
host memory instead — but whether that trade wins depends on numbers
nothing measured yet. This module measures all three sides of it:

- **ghost-tree eviction-regret ledger** — evicted tree entries leave a
  bounded ARC-style *ghost list* of block keys (rolling-hash of the full
  token prefix, one entry per evicted block/tail) stamped with their
  eviction event and time. The admission-path probe (beside the
  ``workload.py`` hook) matches an incoming prompt's block boundaries
  against the ghosts: every prefill token re-paid *because of* a past
  eviction is counted (``Serve/eviction_regret_tokens``, capped at the
  tokens the admission actually recomputes) and attributed to the
  eviction event that caused it, with time-to-regret / reuse-interval
  histograms. Uniform traffic that never evicts reports exactly zero.
- **session-lifecycle heat tracking** — a per-``session_id`` state
  machine (active → idle → resumed / dead) on the injectable clock:
  idle-interval and resume-count histograms, plus the *HBM
  byte-seconds-held-while-idle* integral — the two costs a host tier
  trades (idle HBM residency vs regretted recompute). Transitions emit
  ``session_active``/``session_idle`` spans, rendered as per-session
  residency tracks in the Perfetto export.
- **measured host-tier inputs** — :func:`measure_copy_bandwidth` times a
  real host↔device transfer (the AIO/offload discipline: measured, or
  degraded to None with one warning — never a guess), and the engine
  joins it with the span ring's measured prefill throughput into the
  ``tiered_kv`` capacity-advisor lever (``capacity.py``): projected
  resume-TTFT via host-restore (page bytes ÷ measured copy bandwidth)
  vs measured prefill-recompute cost, scored by observed regret traffic.

Cost discipline, like every layer before it: everything here is
host-side Python over arrays the scheduler already holds — zero device
syncs, zero new compiled programs (``tests/unit/test_kvscope.py``
compares compile counts with the observatory on and off). Disabled (the default) the serving engine holds
``kvscope = None`` and the page pool ``on_evict = None``: one ``is not
None`` per admission/retirement/eviction, nothing else. The
copy-bandwidth probe runs only when a capacity report asks for it.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from ..utils.logging import warning_once
from .metrics import MetricsRegistry
from .workload import prefix_hashes, token_hash

__all__ = ["KVScope", "KVScopeConfig", "measure_copy_bandwidth"]

# session states (readout strings; the machine itself is live-set + stamps)
ACTIVE = "active"
IDLE = "idle"
DEAD = "dead"


@dataclasses.dataclass
class KVScopeConfig:
    """KV residency observatory knobs (``ServingConfig.kvscope``).
    Constructing one (or passing a dict) opts in; ``None`` on the serving
    config means none of the machinery is built."""

    enabled: bool = True
    # Bounded ghost list of recently evicted block keys (ARC-style: the
    # ghosts remember what the cache forgot). Each entry is one dict slot.
    ghost_entries: int = 4096
    # Idle sessions older than this are scored DEAD: their held pages are
    # pure waste a host tier would NOT need to keep either (they never
    # resume) — the advisor's idle distribution splits on it.
    dead_after_s: float = 300.0
    # LRU bound on tracked sessions; evicting one finalizes its stats.
    max_sessions: int = 4096
    # Bounded per-eviction-event attribution ring (regret per event).
    max_events: int = 512
    # Host↔device copy-bandwidth probe transfer size (bytes).
    probe_bytes: int = 1 << 23

    def __post_init__(self):
        for knob in ("ghost_entries", "max_sessions", "max_events",
                     "probe_bytes"):
            if getattr(self, knob) < 1:
                raise ValueError(f"kvscope {knob} must be >= 1, "
                                 f"got {getattr(self, knob)}")
        if self.dead_after_s <= 0:
            raise ValueError(f"kvscope dead_after_s must be > 0, "
                             f"got {self.dead_after_s}")

    @classmethod
    def from_any(cls, cfg: "KVScopeConfig | dict | None") \
            -> "KVScopeConfig | None":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown kvscope config keys: "
                             f"{sorted(unknown)}")
        return cls(**cfg)


def measure_copy_bandwidth(nbytes: int = 1 << 23, repeats: int = 3,
                           device=None,
                           clock: Callable[[], float] = time.perf_counter) \
        -> dict:
    """Measured host↔device copy bandwidth: time ``repeats`` blocking
    ``device_put`` (H2D — the host-tier RESTORE path) and ``device_get``
    (D2H — the demotion path) transfers of ``nbytes`` and report the
    best of each. Every field is PRESENT; a backend where the probe
    fails (or a clock that doesn't advance) degrades fields to None
    with one warning — never a raise, never an invented number."""
    out = {"bytes": int(nbytes), "repeats": int(repeats),
           "h2d_gbps": None, "d2h_gbps": None, "h2d_s": None, "d2h_s": None}
    try:
        import jax

        if device is None:
            device = jax.devices()[0]
        host = np.zeros(max(1, nbytes // 4), np.float32)
        buf = jax.device_put(host, device)         # warmup (alloc paths)
        jax.block_until_ready(buf)
        h2d, d2h = [], []
        for _ in range(repeats):
            t0 = clock()
            buf = jax.device_put(host, device)
            jax.block_until_ready(buf)
            h2d.append(clock() - t0)
            t0 = clock()
            np.asarray(jax.device_get(buf))
            d2h.append(clock() - t0)
        real = nbytes if nbytes >= 4 else 4
        if min(h2d) > 0:
            out["h2d_s"] = min(h2d)
            out["h2d_gbps"] = real / min(h2d) / 1e9
        if min(d2h) > 0:
            out["d2h_s"] = min(d2h)
            out["d2h_gbps"] = real / min(d2h) / 1e9
    except Exception as e:
        warning_once(f"kvscope copy-bandwidth probe failed on this "
                     f"backend ({e!r}) — host-tier lever degrades to "
                     "score 0 (unmeasured, not guessed)")
    return out


class _Session:
    """One tracked session's residency state."""

    __slots__ = ("live", "state", "start_t", "active_since", "idle_since",
                 "last_t", "resumes", "regret_tokens", "regret_resumes",
                 "held_tokens", "idle_token_s")

    def __init__(self, t: float):
        self.live: set = set()          # rids currently admitted/decoding
        self.state = ACTIVE
        self.start_t = t
        self.active_since = t
        self.idle_since: Optional[float] = None
        self.last_t = t
        self.resumes = 0
        self.regret_tokens = 0          # regretted re-prefill this session paid
        self.regret_resumes = 0
        self.held_tokens = 0            # longest registered prompt (tree-held)
        self.idle_token_s = 0.0         # closed idle integral, token-seconds


class KVScope:
    """The residency observatory an engine holds when
    ``serving.kvscope`` is set. Three hooks drive it:

    - ``on_evictions(entries)`` — the page pool's ``on_evict`` seam: one
      call per eviction EVENT, entries carrying the evicted block's full
      token prefix + its block token count;
    - ``on_admit(req)`` — beside the workload hook, once per admission:
      ghost probe + session resume accounting;
    - ``on_retire(req)`` — once per terminal request: session idle edge.

    ``clock`` is the engine's injectable clock (fake-clock tests drive
    the whole lifecycle); ``probe_clock`` times the REAL copy-bandwidth
    probe and stays wall time unless a test injects one."""

    def __init__(self, cfg: "KVScopeConfig | dict | None" = None,
                 registry: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 spans=None, page_size: int = 0,
                 per_token_bytes: Optional[int] = None,
                 tree_held_tokens: Optional[Callable[[], int]] = None,
                 probe_clock: Callable[[], float] = time.perf_counter):
        self.cfg = KVScopeConfig.from_any(cfg) or KVScopeConfig()
        # the pool-truth cap for "reclaimable now": per-session
        # held_tokens don't see which session a later eviction hit, so
        # their sum can exceed what the tree still holds — the engine
        # wires the pool's live tree-held token count here
        self.tree_held_tokens = tree_held_tokens
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.clock = clock if clock is not None else time.perf_counter
        self.spans = spans
        self.page_size = int(page_size)
        self.per_token_bytes = per_token_bytes
        self.probe_clock = probe_clock
        # ghost list: (prefix_len, prefix_hash) -> {block, t, event}
        self.ghosts: OrderedDict = OrderedDict()
        self.ghost_added = 0
        self.ghost_overflow = 0
        self.ghost_hits = 0
        self.stale_ghost_hits = 0       # ghost for a block the tree re-holds
        self.restored_ghost_hits = 0    # ghost popped by a host-tier restore
        self.host_restored_resumes = 0  # resumes served from the host tier
        # per-eviction-event attribution, bounded
        self._events: OrderedDict = OrderedDict()
        self._event_seq = 0
        # regret accounting
        self.regret_tokens = 0
        self.regret_admissions = 0
        self.prefill_tokens_paid = 0    # sum of (P - skip) over admissions
        # sessions
        self.sessions: "OrderedDict[object, _Session]" = OrderedDict()
        self.sessions_started = 0
        self.sessions_resumed = 0
        self.sessions_finalized = 0
        self.regret_resumes = 0
        self._idle_token_s_closed = 0.0  # finalized sessions' integrals
        # fleet seam (serving/fleet.py): called as (session_id,
        # regret_tokens) when a RESUME re-pays ghost-covered prefill —
        # the router checks whether the sticky replica is the one that
        # evicted the prefix (Fleet/affinity_regret). None outside a fleet.
        self.on_regret_resume = None
        self._copy_bw: Optional[dict] = None

    # ------------------------------------------------------------ evictions
    def on_evictions(self, entries: list) -> None:
        """One eviction EVENT (one ``PagePool._evict`` pass that freed
        tree entries): stamp every evicted block key into the ghost
        list. ``entries`` carry ``tokens`` (full token prefix from the
        tree root through the entry) and ``block`` (the entry's own
        token count — ``page_size`` for a full block, the tail length
        for a partial tail)."""
        if not entries:
            return
        t = self.clock()
        self._event_seq += 1
        eid = self._event_seq
        self._events[eid] = {"event": eid, "t": t, "ghosts": len(entries),
                             "regret_tokens": 0, "hits": 0}
        while len(self._events) > self.cfg.max_events:
            self._events.popitem(last=False)
        for e in entries:
            toks = e["tokens"]
            key = (len(toks), token_hash(toks))
            self.ghosts[key] = {"block": int(e["block"]), "t": t,
                                "event": eid}
            self.ghosts.move_to_end(key)
            self.ghost_added += 1
        while len(self.ghosts) > self.cfg.ghost_entries:
            self.ghosts.popitem(last=False)
            self.ghost_overflow += 1
        r = self.registry
        r.counter("Serve/kv_ghosts_added").inc(len(entries))
        r.gauge("Serve/kv_ghost_entries").set(float(len(self.ghosts)))

    # ------------------------------------------------------------ admission
    def _probe_ghosts(self, prompt: np.ndarray, shared: int, skip: int,
                      now: float, restored: int = 0) -> int:
        """Match the prompt's block boundaries against the ghost list
        and return the regret: re-paid prefill tokens this admission
        owes to past evictions. A hit at block ``b < shared`` means the
        tree holds that block again (a later registration) — the ghost
        is stale, dropped without regret. A hit at ``shared <= b <
        shared + restored`` is a block the host tier restored
        (serving/hostkv.py): the resume paid copy bytes, not prefill —
        the ghost pops WITHOUT booking regret tokens. The total is
        capped at the tokens the admission actually recomputes
        (``P - 1 - skip``: even a fully live tree re-runs the final
        token's forward)."""
        P = len(prompt)
        cap = max(0, P - 1 - skip)
        if not self.ghosts or not self.page_size \
                or (cap == 0 and not restored):
            return 0
        hits = []
        for b, (length, h) in enumerate(
                prefix_hashes(prompt, self.page_size)):
            g = self.ghosts.pop((length, h), None)
            if g is None:
                continue
            if b < shared:
                self.stale_ghost_hits += 1
                continue
            if b < shared + restored:
                self.restored_ghost_hits += 1
                self.registry.counter(
                    "Serve/kv_restored_ghost_hits").inc()
                continue
            hits.append(g)
        if cap == 0:
            self.registry.gauge("Serve/kv_ghost_entries").set(
                float(len(self.ghosts)))
            return 0
        if P % self.page_size:
            g = self.ghosts.pop((P, token_hash(prompt)), None)
            if g is not None:
                hits.append(g)
        if not hits:
            return 0
        r = self.registry
        regret = 0
        for g in hits:
            take = min(int(g["block"]), cap - regret)
            if take <= 0:
                break
            regret += take
            self.ghost_hits += 1
            ev = self._events.get(g["event"])
            if ev is not None:
                ev["regret_tokens"] += take
                ev["hits"] += 1
            r.histogram("Serve/kv_time_to_regret_s").observe(now - g["t"])
        r.gauge("Serve/kv_ghost_entries").set(float(len(self.ghosts)))
        return regret

    def on_admit(self, req) -> dict:
        """Score one admission: ghost-probe the prompt (regret) and
        advance the session machine (resume edge). Returns the
        per-admission readout (callers like benches may use it; the
        engine ignores it)."""
        t = self.clock()
        prompt = np.asarray(req.prompt).reshape(-1)
        P = len(prompt)
        alloc = getattr(req, "page_alloc", None)
        shared = alloc.shared if alloc is not None else 0
        skip = alloc.skip if alloc is not None else 0
        restored = getattr(alloc, "restored", 0) if alloc is not None else 0
        self.prefill_tokens_paid += P - skip
        regret = self._probe_ghosts(prompt, shared, skip, t,
                                    restored=restored)
        r = self.registry
        if regret:
            self.regret_tokens += regret
            self.regret_admissions += 1
            r.counter("Serve/eviction_regret_tokens").inc(regret)
            r.histogram("Serve/kv_regret_admission_tokens").observe(regret)
        if self.prefill_tokens_paid:
            r.gauge("Serve/eviction_regret_frac").set(
                self.regret_tokens / self.prefill_tokens_paid)
        resumed = self._session_admit(req, P, t, regret,
                                      restored=restored)
        return {"regret_tokens": regret, "resumed": resumed,
                "restored_blocks": restored, "prompt_len": P, "skip": skip}

    def _session_admit(self, req, P: int, t: float, regret: int,
                       restored: int = 0) -> bool:
        sid = getattr(req, "session_id", None)
        if sid is None:
            return False
        s = self.sessions.get(sid)
        r = self.registry
        resumed = False
        if s is None:
            s = self.sessions[sid] = _Session(t)
            self.sessions_started += 1
            r.counter("Serve/sessions_started").inc()
        elif not s.live:
            # resume edge: idle (or scored-dead) → active. The idle
            # interval is the reuse interval a host tier must bridge.
            idle = t - s.idle_since if s.idle_since is not None else 0.0
            s.idle_token_s += s.held_tokens * idle
            r.histogram("Serve/session_idle_s").observe(idle)
            r.histogram("Serve/kv_reuse_interval_s").observe(idle)
            s.resumes += 1
            self.sessions_resumed += 1
            r.counter("Serve/session_resumed").inc()
            if regret:
                s.regret_resumes += 1
                s.regret_tokens += regret
                self.regret_resumes += 1
                r.counter("Serve/session_regret_resumes").inc()
                if self.on_regret_resume is not None:
                    self.on_regret_resume(sid, regret)
            if restored:
                # the resume the host tier SAVED: its evicted prefix
                # came back at copy bandwidth — a hit, not a regret
                # (the fleet's affinity-regret ledger must not count it)
                self.host_restored_resumes += 1
                r.counter("Serve/session_host_restored_resumes").inc()
            if self.spans is not None and s.idle_since is not None:
                from . import spans as S

                self.spans.emit(S.SESSION_IDLE, s.idle_since, t,
                                session=str(sid), regret_tokens=regret)
            s.state = ACTIVE
            s.active_since = t
            s.idle_since = None
            resumed = True
        s.live.add(req.rid)
        if self.page_size:
            # the tree retains the longest registered prompt's blocks —
            # the HBM a host tier could demote while the session idles
            s.held_tokens = max(s.held_tokens, P)
        s.last_t = t
        self.sessions.move_to_end(sid)
        while len(self.sessions) > self.cfg.max_sessions:
            _osid, old = self.sessions.popitem(last=False)
            self._finalize_session(old, t)
        return resumed

    def on_import(self, req) -> None:
        """Disaggregated decode-side intake (``import_request``): take
        over the session residency WITHOUT regret probing or prefill
        accounting — a decode replica seating already-computed KV pays
        no prefill, but its tree now holds the session's blocks and its
        retirement must find the rid in the live set."""
        self._session_admit(req, len(np.asarray(req.prompt).reshape(-1)),
                            self.clock(), 0)

    def on_retire(self, req) -> None:
        """A request terminated: if it was its session's last live one,
        the session goes idle — the byte-seconds meter starts. The
        disaggregated prefill replica's ``release_request`` (the
        request moves on, the slot frees, the prompt blocks stay
        tree-held HERE) funnels through this too: for residency
        purposes a handoff ends the session's activity on the source
        replica exactly like a retirement would."""
        sid = getattr(req, "session_id", None)
        if sid is None:
            return
        s = self.sessions.get(sid)
        if s is None or req.rid not in s.live:
            return
        s.live.discard(req.rid)
        if not s.live:
            t = self.clock()
            if self.spans is not None:
                from . import spans as S

                self.spans.emit(S.SESSION_ACTIVE, s.active_since, t,
                                session=str(sid), resumes=s.resumes)
            s.state = IDLE
            s.idle_since = t
            s.last_t = t

    def _finalize_session(self, s: _Session, now: float) -> None:
        """Close one session's books (LRU eviction from the tracker):
        its resume count lands in the histogram, its idle integral in
        the closed total."""
        if s.idle_since is not None:
            s.idle_token_s += s.held_tokens * (now - s.idle_since)
        self._idle_token_s_closed += s.idle_token_s
        self.sessions_finalized += 1
        self.registry.histogram("Serve/session_resume_count").observe(
            s.resumes)

    # -------------------------------------------------------------- readout
    def _cap_held(self, tokens: int) -> int:
        """Cap a session-summed held-token figure at what the tree
        ACTUALLY holds right now: per-session ``held_tokens`` can't see
        which session a later eviction hit, so their sum overstates
        residency under churn — the pool's live count is the truth."""
        if self.tree_held_tokens is not None:
            return min(tokens, int(self.tree_held_tokens()))
        return tokens

    def idle_kv_tokens(self) -> int:
        """Tree-held prompt tokens of currently idle (incl. dead)
        sessions — what a host tier could demote right now, capped at
        the pool's live tree residency."""
        return self._cap_held(sum(s.held_tokens
                                  for s in self.sessions.values()
                                  if not s.live))

    def idle_kv_bytes(self) -> Optional[int]:
        """The host-tier ledger row: bytes reclaimable by demoting idle
        sessions' tree-held pages (None when the byte cost of a cached
        token is unknown — contiguous engines hold nothing per-session)."""
        if not self.per_token_bytes:
            return None
        return int(self.idle_kv_tokens() * self.per_token_bytes)

    def copy_bandwidth(self, device=None) -> dict:
        """The measured host↔device copy-bandwidth probe, run ONCE and
        cached (capacity reports re-read it for free)."""
        if self._copy_bw is None:
            self._copy_bw = measure_copy_bandwidth(
                self.cfg.probe_bytes, device=device, clock=self.probe_clock)
        return self._copy_bw

    def snapshot(self) -> dict:
        """The observatory's full readout: regret ledger, ghost state,
        per-event attribution, session heat — the ``kvscope`` section of
        the capacity report and the flight recorder's provider. Also
        refreshes the ``Serve/sessions_*`` gauges (the states are
        time-derived: an idle session crosses into DEAD by the clock,
        not by an event)."""
        now = self.clock()
        active = idle = dead = 0
        idle_token_s = self._idle_token_s_closed
        idle_tokens_now = 0
        hottest = []
        for sid, s in self.sessions.items():
            if s.live:
                active += 1
            else:
                gap = now - s.idle_since if s.idle_since is not None else 0.0
                if gap > self.cfg.dead_after_s:
                    s.state = DEAD
                    dead += 1
                else:
                    idle += 1
                idle_tokens_now += s.held_tokens
            idle_token_s += s.idle_token_s
            if s.idle_since is not None and not s.live:
                idle_token_s += s.held_tokens * (now - s.idle_since)
            if s.regret_tokens:
                hottest.append({"session": str(sid),
                                "regret_tokens": s.regret_tokens,
                                "regret_resumes": s.regret_resumes,
                                "resumes": s.resumes,
                                "held_tokens": s.held_tokens,
                                "state": s.state})
        hottest.sort(key=lambda d: d["regret_tokens"], reverse=True)
        # "now" is HBM truth (capped at live tree residency: eviction
        # may have already reclaimed a session's pages); the INTEGRAL
        # deliberately is not capped — it measures what a host tier
        # WOULD have held through the idle gaps (evicted-then-regretted
        # pages included), i.e. the tier's demand, not HBM's supply
        idle_tokens_now = self._cap_held(idle_tokens_now)
        ptb = self.per_token_bytes
        byte_s = idle_token_s * ptb if ptb else None
        self.registry.set_gauges({
            "Serve/sessions_active": float(active),
            "Serve/sessions_idle": float(idle),
            "Serve/sessions_dead": float(dead),
            "Serve/session_idle_kv_tokens": float(idle_tokens_now),
        })
        if byte_s is not None:
            self.registry.gauge("Serve/session_idle_kv_byte_s").set(byte_s)
        mean_regret = (self.regret_tokens / self.regret_admissions
                       if self.regret_admissions else None)
        events = sorted(self._events.values(),
                        key=lambda e: e["regret_tokens"], reverse=True)
        return {
            "enabled": True,
            "page_size": self.page_size,
            "per_token_bytes": ptb,
            "regret": {
                "regret_tokens": self.regret_tokens,
                "regret_admissions": self.regret_admissions,
                "prefill_tokens_paid": self.prefill_tokens_paid,
                "regret_frac": (self.regret_tokens
                                / self.prefill_tokens_paid
                                if self.prefill_tokens_paid else 0.0),
                "mean_regret_tokens": mean_regret,
                "ghost_hits": self.ghost_hits,
                "stale_ghost_hits": self.stale_ghost_hits,
                "restored_ghost_hits": self.restored_ghost_hits,
            },
            "ghosts": {
                "entries": len(self.ghosts),
                "capacity": self.cfg.ghost_entries,
                "added": self.ghost_added,
                "overflow": self.ghost_overflow,
            },
            "events": {
                "count": self._event_seq,
                "tracked": len(self._events),
                "top": events[:8],
            },
            "sessions": {
                "tracked": len(self.sessions),
                "active": active,
                "idle": idle,
                "dead": dead,
                "started": self.sessions_started,
                "resumed": self.sessions_resumed,
                "regret_resumes": self.regret_resumes,
                "host_restored_resumes": self.host_restored_resumes,
                "finalized": self.sessions_finalized,
                "idle_kv_tokens_now": idle_tokens_now,
                "idle_kv_bytes_now": (idle_tokens_now * ptb
                                      if ptb else None),
                "idle_kv_token_s": idle_token_s,
                "idle_kv_byte_s": byte_s,
                "hottest": hottest[:8],
            },
            "copy_bandwidth": self._copy_bw,
        }
