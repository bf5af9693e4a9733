"""Traffic capture & deterministic replay: record → replay → validate.

Every signal the observability stack produces today is *live-only*: an
incident dir tells you what happened but nothing can re-run it, and the
capacity advisor's what-if rankings have never been scored against a
real outcome. This module closes the loop, in the measurement discipline
the DeepSpeed-FastGen/ZeRO papers anchor every optimization claim to —
a reproducible workload:

- **Capture** (:class:`TrafficCapture`): a schema-versioned
  :class:`TrafficTrace` recording, per admitted request, the relative
  submit time on the injectable clock, the prompt token ids (or a
  generator seed for synthetic traffic), the sampling seed and
  per-request deadline overrides, the session id, plus every chaos
  event (replica kills/joins, drains) and every terminal result (the
  parity oracle's recorded outputs). Written live from hooks on
  ``ServingEngine.submit`` / ``FleetEngine.submit`` into a bounded
  host-side ring — zero device syncs, zero new programs; ``capture``
  off (the default) builds none of it.
- **Replay** (:class:`ReplayDriver`): re-runs a trace against a fresh
  :class:`~..serving.engine.ServingEngine` or
  :class:`~..serving.fleet.FleetEngine` under ANY config, on the
  injectable fake clock (time-compressed jumps or paced ticks),
  co-replaying the recorded chaos script (kills/joins/drains land at
  their recorded positions). Greedy/fp replay is bit-identical to the
  recorded outputs — the parity oracle — and divergence is reported
  per-request in the :class:`ReplayReport`, never raised as a crash.
- **Backtest** (:func:`advisor_backtest`): replays the same trace under
  what-if configs (prefix sharing on/off, int8 KV) and scores the
  capacity advisor's predictions (``CAPACITY_REPORT.json`` levers)
  against achieved prefill-tokens-saved / TTFT / goodput into a
  prediction-error report — the advisor finally gets a report card.

The request log upgrades into a trace too
(:func:`trace_from_request_log`): v2 request records carry the fields
replay needs (prompt ids, seed, session, deadline overrides), so an
existing ``*.requests.jsonl`` replays — without recorded outputs, the
parity oracle degrades to ``parity=None`` instead of lying.

``python -m deepspeed_tpu.observability.doctor`` grew a ``[replay]``
section (trace present/valid + the last replay parity verdict) and
flight/incident dumps bundle ``traffic_trace.jsonl`` (the capture ring's
tail), so every incident is replayable standing alone — see
docs/OPERATIONS.md "Reproducing an incident from its trace".
"""

from __future__ import annotations

import dataclasses
import json
import math
import threading
import time
from collections import OrderedDict, deque
from pathlib import Path
from typing import Callable, Iterable, Optional

import numpy as np

TRACE_SCHEMA = "dstpu.traffic_trace.v1"

# event kinds a trace line may carry ("header" is the first line only)
_KIND_REQUEST = "request"
_KIND_RESULT = "result"
_KIND_CHAOS = "chaos"
_KINDS = frozenset({_KIND_REQUEST, _KIND_RESULT, _KIND_CHAOS})

# chaos events the replay driver knows how to co-replay
_CHAOS_EVENTS = frozenset({"kill_replica", "remove_replica", "add_replica",
                           "begin_drain", "end_drain"})


class ReplayClock:
    """Settable fake clock for deterministic replay.

    Engines under replay and the :class:`ReplayDriver` share ONE of
    these: the driver jumps it to each event's recorded relative time
    (time-compressed replay), so deadline sweeps and goodput windows see
    the recorded timeline without any real waiting. ``dt`` (optional)
    makes every read tick forward — spans and goodput ledgers then see
    nonzero intervals, like the test suites' TickClock."""

    def __init__(self, t0: float = 0.0, dt: float = 0.0):
        self.t = float(t0)
        self.dt = float(dt)

    def __call__(self) -> float:
        t = self.t
        self.t += self.dt
        return t

    def advance(self, s: float) -> None:
        self.t += float(s)

    def advance_to(self, t: float) -> None:
        """Jump forward to ``t`` (never backward — a trace with jittered
        stamps must not rewind deadlines under a live engine)."""
        if t > self.t:
            self.t = float(t)


def resolve_prompt(entry: dict) -> np.ndarray:
    """An entry's prompt tokens: the recorded ids, or the deterministic
    regeneration of a synthetic ``gen`` spec (``{"seed", "len",
    "vocab"?}`` — the compact form benches record instead of shipping
    token arrays)."""
    if entry.get("prompt") is not None:
        return np.asarray(entry["prompt"], np.int32)
    gen = entry.get("gen")
    if not isinstance(gen, dict):
        raise ValueError(f"trace entry rid={entry.get('rid')} has neither "
                         "prompt ids nor a gen spec")
    rng = np.random.default_rng(int(gen["seed"]))
    return rng.integers(0, int(gen.get("vocab", 256)),
                        (int(gen["len"]),)).astype(np.int32)


class TrafficTrace:
    """One recorded traffic stream: a header (schema + capture meta) and
    an ordered event list (requests, results, chaos) — the JSONL form is
    one JSON object per line, header first.

    Construction is either programmatic (``add_request`` /
    ``add_result`` / ``add_chaos`` — synthetic traces for benches and
    tests) or from a capture ring (:meth:`TrafficCapture.trace`) or disk
    (:meth:`read`, torn-line tolerant like every other triage artifact).
    """

    def __init__(self, meta: Optional[dict] = None,
                 events: Optional[list] = None):
        self.meta = dict(meta or {})
        self.events: list[dict] = list(events or [])
        self.torn_lines = 0

    # ------------------------------------------------------------ building
    def add_request(self, rid: int, t_rel: float, prompt=None,
                    gen: Optional[dict] = None, max_new: int = 1,
                    seed: int = 0, session_id=None, tenant_id=None,
                    ttft_deadline_s: Optional[float] = None,
                    total_deadline_s: Optional[float] = None) -> dict:
        ev: dict = {"kind": _KIND_REQUEST, "t_rel": float(t_rel),
                    "rid": int(rid), "max_new": int(max_new),
                    "seed": int(seed)}
        if prompt is not None:
            ev["prompt"] = [int(t) for t in
                            np.asarray(prompt).reshape(-1).tolist()]
        elif gen is not None:
            ev["gen"] = {k: int(v) for k, v in gen.items()}
        if session_id is not None:
            ev["session_id"] = session_id
        if tenant_id is not None and str(tenant_id) != "default":
            # stored only when attribution is real: tenant-free traces
            # (and their byte layout) are unchanged
            ev["tenant_id"] = str(tenant_id)
        if ttft_deadline_s is not None:
            ev["ttft_deadline_s"] = float(ttft_deadline_s)
        if total_deadline_s is not None:
            ev["total_deadline_s"] = float(total_deadline_s)
        self.events.append(ev)
        return ev

    def add_result(self, rid: int, t_rel: float, status: str = "ok",
                   tokens: Iterable = (), attempts: int = 0) -> dict:
        ev = {"kind": _KIND_RESULT, "t_rel": float(t_rel), "rid": int(rid),
              "status": str(status),
              "tokens": [int(t) for t in tokens],
              "attempts": int(attempts)}
        self.events.append(ev)
        return ev

    def add_chaos(self, event: str, t_rel: float, replica: str = "",
                  role: str = "") -> dict:
        ev = {"kind": _KIND_CHAOS, "t_rel": float(t_rel),
              "event": str(event), "replica": str(replica)}
        if role:
            # disaggregated joins record the phase so an autoscaled run
            # replays its add_replica edges into the right role
            ev["role"] = str(role)
        self.events.append(ev)
        return ev

    # ------------------------------------------------------------- readout
    @property
    def requests(self) -> list[dict]:
        return [e for e in self.events if e.get("kind") == _KIND_REQUEST]

    @property
    def chaos_events(self) -> list[dict]:
        return [e for e in self.events if e.get("kind") == _KIND_CHAOS]

    @property
    def results(self) -> dict:
        """rid → result entry (the recorded outputs — the parity oracle's
        reference). Last write wins, matching the capture dedupe."""
        return {e["rid"]: e for e in self.events
                if e.get("kind") == _KIND_RESULT}

    def validate(self) -> list[str]:
        """Schema gate; returns the list of problems (empty = valid) —
        the same degrade-don't-crash contract every triage artifact
        follows. Checks the schema version, known event kinds, required
        request fields (prompt ids XOR gen spec, max_new >= 1), unique
        request rids, results referencing known rids, and non-decreasing
        ``t_rel`` (capture appends in clock order; a shuffled trace
        would replay a different scenario than it claims to record)."""
        problems: list[str] = []
        schema = self.meta.get("schema", TRACE_SCHEMA)
        if schema != TRACE_SCHEMA:
            problems.append(f"unknown trace schema {schema!r} "
                            f"(this build reads {TRACE_SCHEMA})")
        seen_rids: set = set()
        last_t = None
        for i, ev in enumerate(self.events):
            if not isinstance(ev, dict):
                problems.append(f"event {i}: not an object")
                continue
            kind = ev.get("kind")
            if kind not in _KINDS:
                problems.append(f"event {i}: unknown kind {kind!r}")
                continue
            t = ev.get("t_rel")
            if not isinstance(t, (int, float)) or t < 0:
                problems.append(f"event {i}: bad t_rel {t!r}")
                continue
            if last_t is not None and t < last_t:
                problems.append(f"event {i}: t_rel {t} < previous {last_t} "
                                "(events must be in capture order)")
            last_t = t
            if kind == _KIND_REQUEST:
                rid = ev.get("rid")
                if rid in seen_rids:
                    problems.append(f"event {i}: duplicate request "
                                    f"rid {rid}")
                seen_rids.add(rid)
                has_prompt = isinstance(ev.get("prompt"), list) \
                    and len(ev["prompt"]) > 0
                gen = ev.get("gen")
                has_gen = isinstance(gen, dict) and "seed" in gen \
                    and "len" in gen
                if not has_prompt and not has_gen:
                    problems.append(f"event {i}: request rid {rid} needs "
                                    "prompt ids or a gen{seed,len} spec")
                if not isinstance(ev.get("max_new"), int) \
                        or ev["max_new"] < 1:
                    problems.append(f"event {i}: request rid {rid} needs "
                                    f"max_new >= 1, got {ev.get('max_new')!r}")
            elif kind == _KIND_RESULT:
                if ev.get("rid") not in seen_rids:
                    problems.append(f"event {i}: result for unknown "
                                    f"rid {ev.get('rid')}")
                if not isinstance(ev.get("tokens"), list):
                    problems.append(f"event {i}: result rid {ev.get('rid')} "
                                    "needs a tokens list")
            elif kind == _KIND_CHAOS:
                if ev.get("event") not in _CHAOS_EVENTS:
                    problems.append(f"event {i}: unknown chaos event "
                                    f"{ev.get('event')!r}")
        return problems

    # ----------------------------------------------------------------- io
    def as_lines(self) -> list[str]:
        header = {"kind": "header", "schema": TRACE_SCHEMA,
                  **{k: v for k, v in self.meta.items() if k != "schema"}}
        return ([json.dumps(header, separators=(",", ":"), default=str)]
                + [json.dumps(ev, separators=(",", ":"), default=str)
                   for ev in self.events])

    def write(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("\n".join(self.as_lines()) + "\n", encoding="utf-8")
        return path

    @classmethod
    def read(cls, path) -> "TrafficTrace":
        """Load a trace from JSONL, skipping torn lines (the artifact may
        have been cut by the very crash it records — ``torn_lines``
        counts what was skipped)."""
        from .flight import load_jsonl_tolerant

        rows, skipped = load_jsonl_tolerant(path)
        meta: dict = {}
        events: list = []
        for row in rows:
            if not isinstance(row, dict):
                skipped += 1
                continue
            if row.get("kind") == "header":
                meta = {k: v for k, v in row.items() if k != "kind"}
            else:
                events.append(row)
        tr = cls(meta=meta, events=events)
        tr.torn_lines = skipped
        return tr


def capture_meta(cfg, engine: str = "serving", **extra) -> dict:
    """Trace-header meta from one :class:`ServingConfig` — the recorded
    config a faithful replay must match (sampling policy and ``max_len``
    are part of the sampled bit-stream; paging knobs size the what-if
    space). ONE builder shared by ``ServingEngine`` and ``FleetEngine``
    so the drift-check schema (:meth:`ReplayDriver._check_config`)
    cannot fork between the two surfaces. ``extra`` carries
    surface-specific fields (replica counts)."""
    return {"engine": engine, "slots": cfg.slots, "max_len": cfg.max_len,
            "prefill_chunk": cfg.prefill_chunk,
            "page_size": cfg.page_size,
            "kv_quant_bits": cfg.kv_quant_bits,
            "prefix_sharing": cfg.prefix_sharing,
            "sampling": {"temperature": cfg.temperature,
                         "top_k": cfg.top_k, "top_p": cfg.top_p,
                         "greedy": cfg.greedy},
            **extra}


class TrafficCapture:
    """The record half of record→replay: a bounded, thread-safe ring of
    trace events fed by the engine hooks.

    ``clock`` is the OWNER's injectable clock (the serving stats clock /
    the fleet clock), so capture timestamps, deadlines, and spans agree
    to the float; the first event anchors ``t_rel = 0``. ``ring`` bounds
    host memory — on overflow the oldest events drop and ``dropped``
    counts them (the flight-dump artifact is explicitly the ring's TAIL;
    a full standalone trace comes from :meth:`trace` before overflow or
    from a request-log upgrade). Results dedupe by rid: a request's
    terminal outcome is recorded once even when fleet adoption paths
    visit it twice."""

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 ring: int = 4096, meta: Optional[dict] = None):
        if ring < 1:
            raise ValueError(f"capture ring must be >= 1, got {ring}")
        self.clock = clock if clock is not None else time.perf_counter
        self.meta = dict(meta or {})
        self._ring: deque[dict] = deque(maxlen=int(ring))
        self._lock = threading.RLock()
        self._t0: Optional[float] = None
        self._last_t = 0.0
        self._appended = 0
        # bounded result-rid dedupe window (the double-visit paths are
        # all within a few events of each other; 4x ring is generous)
        self._result_rids: OrderedDict = OrderedDict()
        self._result_cap = 4 * int(ring)

    # ------------------------------------------------------------ recording
    def _append(self, ev: dict) -> None:
        """Stamp ``t_rel`` and append under ONE lock acquisition: two
        threads (the serving loop vs a telemetry-thread drain/dump hook)
        must not interleave between reading the clock and appending, or
        the ring would hold out-of-order events and the trace would fail
        its own order check on a healthy engine. ``t_rel`` is also
        clamped monotone against the last event as a second line of
        defense (an injected clock that steps backward)."""
        with self._lock:
            now = self.clock()
            if self._t0 is None:
                self._t0 = now
            t = max(0.0, now - self._t0, self._last_t)
            self._last_t = t
            ev["t_rel"] = t
            self._ring.append(ev)
            self._appended += 1

    def on_submit(self, req, session_id=None,
                  ttft_deadline_s: Optional[float] = None,
                  total_deadline_s: Optional[float] = None) -> None:
        """One admitted request into the ring (shed submits never ran and
        are not part of the trace). ``ttft_deadline_s`` /
        ``total_deadline_s`` are the PER-REQUEST overrides as passed to
        ``submit`` (None = the config default applied) — replay resubmits
        them so deadline semantics reproduce under the same config."""
        ev: dict = {"kind": _KIND_REQUEST,
                    "rid": int(req.rid), "max_new": int(req.max_new),
                    "seed": int(req.seed),
                    "prompt": [int(t) for t in
                               np.asarray(req.prompt).reshape(-1).tolist()]}
        sid = session_id if session_id is not None \
            else getattr(req, "session_id", None)
        if sid is not None:
            ev["session_id"] = sid
        tid = getattr(req, "tenant_id", None)
        if tid is not None and str(tid) != "default":
            # verbatim tenant attribution; the inert value stays
            # unrecorded so pre-tenant captures are byte-identical
            ev["tenant_id"] = str(tid)
        if ttft_deadline_s is not None:
            ev["ttft_deadline_s"] = float(ttft_deadline_s)
        if total_deadline_s is not None:
            ev["total_deadline_s"] = float(total_deadline_s)
        self._append(ev)

    def on_result(self, req) -> None:
        """One terminal outcome (status + the output tokens — the parity
        oracle's reference bits). Deduped by rid."""
        with self._lock:
            if req.rid in self._result_rids:
                return
            self._result_rids[req.rid] = True
            while len(self._result_rids) > self._result_cap:
                self._result_rids.popitem(last=False)
        status = getattr(req.status, "value", str(req.status))
        self._append({"kind": _KIND_RESULT,
                      "rid": int(req.rid), "status": status,
                      "tokens": [int(t) for t in req.tokens],
                      "attempts": int(getattr(req, "attempts", 0))})

    def on_chaos(self, event: str, replica: str = "",
                 role: str = "") -> None:
        """One fleet chaos event (replica kill/join, drain edge) — the
        chaos script replay co-replays at the recorded position.
        ``role`` (joins on a disaggregated fleet) rides along so replay
        re-adds the replica into the right phase."""
        ev = {"kind": _KIND_CHAOS,
              "event": str(event), "replica": str(replica)}
        if role:
            ev["role"] = str(role)
        self._append(ev)

    # -------------------------------------------------------------- readout
    @property
    def dropped(self) -> int:
        """Events evicted from the ring so far (0 = the ring still holds
        the full capture and :meth:`trace` is the complete stream)."""
        with self._lock:
            return max(0, self._appended - len(self._ring))

    def trace(self) -> TrafficTrace:
        with self._lock:
            events = list(self._ring)
            dropped = max(0, self._appended - len(self._ring))
        if dropped:
            # an overflowed ring may hold results whose request events
            # were evicted; they can neither replay nor compare, and a
            # tail trace carrying them would fail validate() (and the
            # doctor's [replay] gate) on a perfectly healthy engine —
            # drop the orphans, count them with the evicted
            rids = {e["rid"] for e in events
                    if e.get("kind") == _KIND_REQUEST}
            kept = [e for e in events if e.get("kind") != _KIND_RESULT
                    or e.get("rid") in rids]
            dropped += len(events) - len(kept)
            events = kept
        meta = dict(self.meta)
        meta["captured_events"] = len(events)
        meta["dropped_events"] = dropped
        return TrafficTrace(meta=meta, events=events)

    def tail_text(self) -> str:
        """The ring's current tail as trace JSONL text — the flight/
        incident-dump artifact (``traffic_trace.jsonl``), so every
        incident dir is replayable standing alone (up to the ring
        bound)."""
        return "\n".join(self.trace().as_lines()) + "\n"

    def write(self, path) -> Path:
        return self.trace().write(path)


def trace_from_request_log(rows: Iterable[dict]) \
        -> "tuple[TrafficTrace, int]":
    """Upgrade request-log records into a replayable
    :class:`TrafficTrace` — ``(trace, skipped)``.

    v2+ request records (``observability/export.py``) carry the fields
    replay needs: prompt token ids, sampling seed, session id, and the
    per-request deadline budgets; v3 adds ``tenant_id``. Rows missing
    the replay fields (v1 logs, or torn lines parsed to partial
    objects) are SKIPPED and counted, never guessed at. v2 rows (no
    tenant_id) upgrade to ``"default"`` — counted in the trace meta
    (``tenantless_rows``), never a crash. The request log does not
    carry output token ids (only counts), so the upgraded trace has no
    recorded outputs — replay runs but the parity oracle reports
    ``parity=None``."""
    usable = []
    skipped = 0
    for r in rows:
        if (isinstance(r, dict) and isinstance(r.get("prompt"), list)
                and r["prompt"] and r.get("seed") is not None
                and r.get("submit_t") is not None
                and r.get("rid") is not None and r.get("max_new")):
            usable.append(r)
        else:
            skipped += 1
    usable.sort(key=lambda r: (r["submit_t"], r["rid"]))
    t0 = usable[0]["submit_t"] if usable else 0.0
    tenantless = sum(1 for r in usable if r.get("tenant_id") is None)
    tr = TrafficTrace(meta={"source": "request_log",
                            "upgraded_rows": len(usable),
                            "skipped_rows": skipped,
                            # v2 rows carrying no tenant dimension —
                            # upgraded to "default", never dropped
                            "tenantless_rows": tenantless})
    for r in usable:
        tr.add_request(rid=r["rid"], t_rel=r["submit_t"] - t0,
                       prompt=r["prompt"], max_new=int(r["max_new"]),
                       seed=int(r["seed"]), session_id=r.get("session_id"),
                       tenant_id=r.get("tenant_id", "default"),
                       ttft_deadline_s=r.get("ttft_deadline_s"),
                       total_deadline_s=r.get("total_deadline_s"))
    return tr, skipped


# ------------------------------------------------------------------- replay
@dataclasses.dataclass
class ReplayReport:
    """One replay's outcome, per-request — divergence is DATA here, not
    an exception (the whole point of a parity oracle is to tell you
    exactly which requests' bits moved and where).

    ``parity`` is True when every recorded-OK request replayed
    bit-identical (status OK, same tokens), False when any diverged, and
    None when the trace carried no recorded outputs to compare against
    (e.g. a request-log upgrade)."""

    schema: str = "dstpu.replay_report.v1"
    requests: int = 0                 # request entries in the trace
    replayed: int = 0                 # successfully submitted + finished
    matched: int = 0                  # bit-identical to the recorded output
    diverged: list = dataclasses.field(default_factory=list)
    skipped_non_ok: int = 0           # recorded non-OK: excluded from parity
    failed_submits: list = dataclasses.field(default_factory=list)
    chaos_applied: int = 0
    chaos_skipped: list = dataclasses.field(default_factory=list)
    notes: list = dataclasses.field(default_factory=list)
    parity: Optional[bool] = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def write(self, path) -> Path:
        """Persist the verdict (``REPLAY_REPORT*.json`` is what the
        doctor's ``[replay]`` section reads)."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.as_dict(), indent=2, default=str),
                        encoding="utf-8")
        return path


class ReplayDriver:
    """Re-run one :class:`TrafficTrace` against a serving surface.

    ``engine`` is a :class:`~..serving.engine.ServingEngine` or
    :class:`~..serving.fleet.FleetEngine` built by the caller under
    WHATEVER config the experiment wants (the parity run uses the
    recorded config; a backtest run flips a lever). ``clock`` should be
    the SAME :class:`ReplayClock` the engine was built with: the driver
    advances it to each event's recorded ``t_rel`` (time-compressed —
    no waiting), or in ``paced_dt`` ticks with an engine step per tick
    (paced — deadline sweeps and watchdogs observe the recorded
    timeline). With no controllable clock the replay is order-only:
    events land in recorded order and time-derived behavior (deadlines)
    follows the engine's own clock.

    The recorded chaos script co-replays: ``kill_replica`` /
    ``remove_replica`` / ``add_replica`` / drain edges apply to a fleet
    engine at their recorded positions; on a single engine (or a fleet
    missing the named replica) they are counted in ``chaos_skipped``
    rather than failing the run — a what-if replay on a different
    topology is a legitimate experiment."""

    def __init__(self, engine, trace: TrafficTrace,
                 clock: Optional[ReplayClock] = None,
                 paced_dt: float = 0.0, max_iterations: int = 1_000_000):
        self.engine = engine
        self.trace = trace
        self.clock = clock
        self.paced_dt = float(paced_dt)
        self.max_iterations = int(max_iterations)
        self._fleet = hasattr(engine, "replicas")

    # ------------------------------------------------------------- helpers
    def _advance_to(self, t_rel: float, collected: dict) -> None:
        if self.clock is None:
            return
        if self.paced_dt > 0:
            # paced: tick toward the event time, stepping the engine so
            # the recorded inter-arrival gaps are really served
            while self.clock.t + self.paced_dt <= t_rel:
                self.clock.advance(self.paced_dt)
                self._pump(collected)
        self.clock.advance_to(t_rel)

    def _pump(self, collected: dict) -> None:
        for req in self.engine.step():
            if req.rid in collected or req.rid not in self._rid_map:
                continue
            collected[req.rid] = req
            self.engine.pop_result(req.rid)

    def _apply_chaos(self, ev: dict) -> None:
        event, name = ev.get("event"), ev.get("replica", "")
        try:
            if event in ("kill_replica", "remove_replica"):
                if not self._fleet or name not in self.engine.replicas:
                    raise LookupError(f"no replica {name!r} to remove")
                if event == "kill_replica":
                    self.engine.kill_replica(name)
                else:
                    self.engine.remove_replica(name)
            elif event == "add_replica":
                if not self._fleet:
                    raise LookupError("add_replica needs a fleet engine")
                # recorded role (disaggregated autoscaled joins) rides
                # along; a role the target fleet rejects is a topology
                # mismatch → counted-skip below
                self.engine.add_replica(name or None,
                                        role=ev.get("role") or None)
            elif event == "begin_drain":
                if name:
                    # replica-scoped drain edge (autoscaler-recorded):
                    # unknown name / non-fleet → counted-skip
                    if not self._fleet:
                        raise LookupError("replica drain needs a fleet")
                    self.engine.begin_drain_replica(name)
                else:
                    self.engine.begin_drain()
            elif event == "end_drain":
                if name:
                    if not self._fleet:
                        raise LookupError("replica drain needs a fleet")
                    self.engine.end_drain_replica(name)
                else:
                    self.engine.end_drain()
            else:
                raise LookupError(f"unknown chaos event {event!r}")
        except (LookupError, RuntimeError, KeyError, ValueError) as e:
            # a topology mismatch is an experiment, not a crash — the
            # report says which recorded faults could not be co-replayed
            self._report.chaos_skipped.append(
                {"event": event, "replica": name, "error": repr(e)})
            return
        self._report.chaos_applied += 1

    # ----------------------------------------------------------------- run
    def run(self) -> ReplayReport:
        from ..resilience.guards import QueueFullError

        rep = ReplayReport()
        self._report = rep
        self._rid_map: dict[int, int] = {}     # replay rid -> recorded rid
        recorded = self.trace.results
        timeline = sorted(
            [e for e in self.trace.events
             if e.get("kind") in (_KIND_REQUEST, _KIND_CHAOS)],
            key=lambda e: e.get("t_rel", 0.0))
        rep.requests = sum(1 for e in timeline
                           if e["kind"] == _KIND_REQUEST)
        self._check_config(rep)
        collected: dict[int, object] = {}
        for ev in timeline:
            self._advance_to(ev.get("t_rel", 0.0), collected)
            if ev["kind"] == _KIND_CHAOS:
                self._apply_chaos(ev)
                continue
            kw = {}
            if ev.get("ttft_deadline_s") is not None:
                kw["ttft_deadline_s"] = ev["ttft_deadline_s"]
            if ev.get("total_deadline_s") is not None:
                kw["total_deadline_s"] = ev["total_deadline_s"]
            if self._fleet and ev.get("session_id") is not None:
                kw["session_id"] = ev["session_id"]
            if ev.get("tenant_id") is not None:
                # engine and fleet submit both take tenant_id; absent
                # (pre-tenant trace) → scheduler default "default"
                kw["tenant_id"] = ev["tenant_id"]
            try:
                rid = self.engine.submit(resolve_prompt(ev),
                                         int(ev["max_new"]),
                                         seed=int(ev["seed"]), **kw)
            except (QueueFullError, ValueError) as e:
                # a shed (queue full / drained) OR a request the what-if
                # config cannot host at all (e.g. a smaller max_len) —
                # both are DATA about this replay, not a crash
                rep.failed_submits.append({"rid": ev["rid"],
                                           "error": str(e)})
                continue
            self._rid_map[rid] = ev["rid"]
            # one step per event: admission interleaves with intake the
            # way a live server's loop does
            self._pump(collected)
        it = 0
        while len(collected) < len(self._rid_map):
            self._pump(collected)
            it += 1
            if it > self.max_iterations:
                raise RuntimeError(
                    f"replay failed to finish in {self.max_iterations} "
                    f"iterations ({len(collected)}/{len(self._rid_map)} "
                    "collected) — engine stuck?")
        self._compare(rep, collected, recorded)
        return rep

    def _check_config(self, rep: ReplayReport) -> None:
        """Note (never fail on) engine-vs-trace config drift: a replay
        under a different sampling policy is a legitimate what-if, but
        the report must say why parity broke."""
        meta = self.trace.meta
        cfg = getattr(self.engine, "cfg", None)
        if cfg is None and self._fleet and self.engine.replicas:
            # a fleet holds no .cfg of its own; every replica carries
            # the same serving config — drift notes must not go silent
            # on exactly the multi-replica replays that need them
            cfg = next(iter(self.engine.replicas.values())).cfg
        if cfg is None:
            return
        rec = meta.get("sampling")
        if isinstance(rec, dict):
            live = {"temperature": cfg.temperature, "top_k": cfg.top_k,
                    "top_p": cfg.top_p, "greedy": cfg.greedy}
            drift = {k: (rec.get(k), v) for k, v in live.items()
                     if rec.get(k) is not None and rec.get(k) != v}
            if drift:
                rep.notes.append({"config_drift": {
                    k: {"recorded": a, "replay": b}
                    for k, (a, b) in drift.items()}})
        if meta.get("max_len") is not None and cfg.max_len != meta["max_len"]:
            # the cache width is part of the sampled bit-stream — this
            # drift breaks parity even at identical sampling knobs
            rep.notes.append({"config_drift": {"max_len": {
                "recorded": meta["max_len"], "replay": cfg.max_len}}})

    def _compare(self, rep: ReplayReport, collected: dict,
                 recorded: dict) -> None:
        had_oracle = False
        replayed_rec = set(self._rid_map.values())
        for rid, rec_rid in self._rid_map.items():
            req = collected.get(rid)
            if req is None:
                continue
            rep.replayed += 1
            want = recorded.get(rec_rid)
            if want is None:
                continue                    # no recorded output: no oracle
            had_oracle = True
            if want.get("status") != "ok":
                rep.skipped_non_ok += 1
                continue
            got = [int(t) for t in req.tokens]
            exp = [int(t) for t in want.get("tokens", [])]
            status = getattr(req.status, "value", str(req.status))
            if got == exp and status == "ok":
                rep.matched += 1
            else:
                first = next((i for i, (a, b) in enumerate(zip(got, exp))
                              if a != b), min(len(got), len(exp)))
                rep.diverged.append({
                    "rid": rec_rid, "first_diff": first,
                    "recorded_tokens": len(exp), "replayed_tokens": len(got),
                    "recorded_status": "ok", "replayed_status": status,
                })
        # a recorded-OK request that never replayed (submit failed/shed
        # under this config) is a parity failure, not a free pass: the
        # verdict must not claim "bit-identical" over requests that
        # never ran
        for e in self.trace.requests:
            rec_rid = e.get("rid")
            if rec_rid in replayed_rec:
                continue
            want = recorded.get(rec_rid)
            if want is None:
                continue
            had_oracle = True
            if want.get("status") != "ok":
                rep.skipped_non_ok += 1
                continue
            rep.diverged.append({
                "rid": rec_rid, "first_diff": None,
                "recorded_tokens": len(want.get("tokens", [])),
                "replayed_tokens": 0, "recorded_status": "ok",
                "replayed_status": "not_replayed",
            })
        rep.parity = (not rep.diverged) if had_oracle else None


# ----------------------------------------------------------------- backtest
BACKTEST_SCHEMA = "dstpu.advisor_backtest.v1"


def _lever_prediction(lever: str, capacity_report: Optional[dict],
                      trace: TrafficTrace, page_size: int) \
        -> "tuple[Optional[float], str]":
    """The advisor's prediction for one lever — from a
    ``CAPACITY_REPORT.json`` dict when given (the real report card),
    else recomputed from the trace through the PR-6 estimator (the
    standalone form benches use) — ``(predicted, source)``."""
    if isinstance(capacity_report, dict):
        levers = (capacity_report.get("advisor") or {}).get("levers") or []
        for lv in levers:
            if isinstance(lv, dict) and lv.get("name") == lever:
                est = lv.get("estimate") or {}
                if lever == "prefix_sharing":
                    v = est.get("shared_prefix_fraction")
                    if isinstance(v, (int, float)):
                        return float(v), "capacity_report"
                break
    if lever == "prefix_sharing":
        from .workload import WorkloadAnalyzer

        wl = WorkloadAnalyzer({"block": page_size})
        for e in trace.requests:
            wl.on_admit(resolve_prompt(e))
        return wl.prefix_overlap, "workload_estimator"
    return None, "none"


def _speculation_prediction(trace: TrafficTrace, ngram: int) \
        -> "tuple[Optional[float], str]":
    """Predicted first-draft acceptance for the self-speculation lever:
    the shared n-gram helper (the SAME implementation the live drafter
    runs) scored over each recorded request's prompt + reference output,
    restricted to the decode region and CONDITIONED on the table having
    a prediction — exactly what the live drafter's per-step first-draft
    accept rate measures (it only proposes when the table has an
    entry). Pooled over the trace. None when no recorded output is long
    enough to score."""
    from ..inference.speculation import acceptance_stats

    results = trace.results
    hits = predicted = 0
    for e in trace.requests:
        prompt = resolve_prompt(e).tolist()
        ref = (results.get(e["rid"]) or {}).get("tokens") or []
        if not ref:
            continue
        full = acceptance_stats(prompt + [int(t) for t in ref], ngram)
        if full is None:
            continue
        head = acceptance_stats(prompt, ngram) \
            or {"hits": 0, "predicted": 0}
        hits += full["hits"] - head["hits"]
        predicted += full["predicted"] - head["predicted"]
    if not predicted:
        return None, "ngram_estimator"
    return hits / predicted, "ngram_estimator"


def advisor_backtest(trace: TrafficTrace, engine, serving: dict,
                     levers=("prefix_sharing", "kv_quantization"),
                     capacity_report: Optional[dict] = None,
                     page_size: int = 8,
                     speculation: Optional[dict] = None) -> dict:
    """Score the capacity advisor against reality: replay ``trace``
    under each lever's what-if config and compare the advisor's
    prediction to the achieved outcome — the prediction-error report.

    ``engine`` is the shared :class:`InferenceEngine`; ``serving`` is
    the base ServingConfig dict (sampling knobs, slots, max_len) every
    run starts from — the backtest owns the paged/lever fields. Each run
    is a fresh ServingEngine on its own :class:`ReplayClock` (goodput
    ledger on, so achieved goodput/TTFT ride the report alongside
    prefill-tokens-saved).

    Levers scored:

    - ``prefix_sharing`` — predicted shared-prefix fraction (the
      ``CAPACITY_REPORT.json`` lever estimate when given, else the PR-6
      estimator on the trace) vs ACHIEVED prefill-tokens-saved fraction
      with the radix tree on; ``abs_error_pts`` is the headline number
      (held to ±10 points in ``tests/unit/test_replay.py``).
    - ``kv_quantization`` — predicted int8/fp KV bytes-per-token ratio
      (the ledger math) vs the achieved ledger ratio in the int8 replay.
    - ``speculative_decoding`` — predicted first-draft acceptance (the
      shared n-gram helper scored over each recorded request's decode
      region, conditioned on the table proposing) vs the ACHIEVED live
      first-draft accept rate from the spec-on replay's engine
      snapshot. The what-if forces ``greedy: True`` (self-speculation
      requires it); ``speculation`` overrides the lever's config
      (default ``{"ngram": 3, "max_draft": 4}``). Where either side has
      no draft to count (outputs of a few tokens), the entry carries
      ``abstained`` with the reason and no ``abs_error_pts``.
    """
    from ..serving.engine import ServingEngine

    def run(extra: dict) -> "tuple[ReplayReport, dict]":
        clock = ReplayClock(dt=1e-4)
        srv = ServingEngine(engine, {**serving, "goodput": True,
                                     **extra}, clock=clock)
        rep = ReplayDriver(srv, trace, clock=clock).run()
        snap = srv.stats.snapshot()
        pool = srv.pool.snapshot() if srv.pool is not None else None
        ledger = srv.hbm_ledger()
        gp = srv.goodput.snapshot() if srv.goodput is not None else {}
        achieved = {
            "replayed": rep.replayed,
            "prefill_tokens_saved": (pool or {}).get(
                "prefill_tokens_saved", 0),
            "ttft_p50_s": (snap.get("ttft_s") or {}).get("p50"),
            "goodput_frac": gp.get("goodput_frac"),
            "kv_per_token_bytes": ledger.get("kv_per_token_bytes"),
            "speculation": srv.spec_snapshot(),
        }
        srv.close()
        return rep, achieved

    total_prompt = int(sum(
        len(resolve_prompt(e)) for e in trace.requests))
    out: dict = {"schema": BACKTEST_SCHEMA,
                 "trace": {"requests": len(trace.requests),
                           "prompt_tokens": total_prompt,
                           "chaos_events": len(trace.chaos_events)},
                 "levers": {}}
    base_rep, base = run({"page_size": page_size,
                          "prefix_sharing": False})
    out["baseline"] = {**base, "parity": base_rep.parity}
    if "prefix_sharing" in levers:
        predicted, source = _lever_prediction(
            "prefix_sharing", capacity_report, trace, page_size)
        rep, ach = run({"page_size": page_size, "prefix_sharing": True})
        achieved = (ach["prefill_tokens_saved"] / total_prompt
                    if total_prompt else 0.0)
        entry = {"predicted": predicted, "source": source,
                 "achieved": achieved, "what_if": ach,
                 "parity": rep.parity}
        if predicted is not None:
            entry["abs_error_pts"] = abs(predicted - achieved) * 100.0
        out["levers"]["prefix_sharing"] = entry
    if "kv_quantization" in levers:
        from ..inference.config import ServingConfig
        from .capacity import kv_cache_bytes

        # config validation alone resolves pool_pages=0 → auto; no
        # engine (and no device slot state) needed for the ledger math
        cfg_probe = ServingConfig.from_any({**serving,
                                            "page_size": page_size})
        fp = kv_cache_bytes(engine.model.cfg, cfg_probe.slots,
                            cfg_probe.max_len, engine.compute_dtype,
                            page_size=page_size,
                            pool_pages=cfg_probe.pool_pages)
        q8 = kv_cache_bytes(engine.model.cfg, cfg_probe.slots,
                            cfg_probe.max_len, engine.compute_dtype,
                            page_size=page_size,
                            pool_pages=cfg_probe.pool_pages,
                            kv_quant_bits=8)
        predicted = (q8["per_token_bytes"] / fp["per_token_bytes"]
                     if fp.get("per_token_bytes") else None)
        rep, ach = run({"page_size": page_size, "prefix_sharing": True,
                        "kv_quant_bits": 8})
        achieved = (ach["kv_per_token_bytes"]
                    / base["kv_per_token_bytes"]
                    if base.get("kv_per_token_bytes") else None)
        entry = {"predicted": predicted, "source": "ledger_math",
                 "achieved": achieved, "what_if": ach,
                 "parity": rep.parity}
        if predicted is not None and achieved is not None:
            entry["abs_error_pts"] = abs(predicted - achieved) * 100.0
        out["levers"]["kv_quantization"] = entry
    if "speculative_decoding" in levers:
        spec_cfg = dict(speculation or {"ngram": 3, "max_draft": 4})
        predicted, source = _speculation_prediction(
            trace, int(spec_cfg.get("ngram", 3)))
        rep, ach = run({"page_size": page_size, "prefix_sharing": True,
                        "greedy": True, "speculation": spec_cfg})
        spec_snap = ach.get("speculation") or {}
        achieved = spec_snap.get("first_accept_rate")
        entry = {"predicted": predicted, "source": source,
                 "achieved": achieved, "what_if": ach,
                 "parity": rep.parity}
        if predicted is not None and achieved is not None:
            entry["abs_error_pts"] = abs(predicted - achieved) * 100.0
        else:
            # nothing to score is an answer, not an error: say which side
            # had no draft to count
            entry["abstained"] = (
                "no n-gram repeats inside any recorded decode region: the "
                "table never predicts" if predicted is None else
                "the live drafter never proposed in the spec-on replay")
        out["levers"]["speculative_decoding"] = entry
    return out


def write_backtest_report(report: dict, path) -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=2, default=str),
                    encoding="utf-8")
    return path


# -------------------------------------------------- scaling backtest (PR 17)
SCALING_BACKTEST_SCHEMA = "dstpu.scaling_backtest.v1"


def make_diurnal_trace(*, duration_s: float, base_rate: float,
                       peak_rate: Optional[float] = None,
                       period_s: Optional[float] = None,
                       burst_factor: float = 1.0, burst_duty: float = 0.5,
                       burst_period_s: Optional[float] = None,
                       prompt_len: int = 8, max_new: int = 8,
                       vocab: int = 256, seed: int = 0) -> TrafficTrace:
    """Synthesize a schema-valid diurnal × bursty request stream.

    A non-homogeneous Poisson process (thinning against the rate
    envelope's peak) whose instantaneous rate is a diurnal sinusoid —
    ``base_rate`` at the trough, ``peak_rate`` at the crest, one full
    period per ``period_s`` (default: one period over the whole trace)
    — multiplied by an on/off burst square wave (``burst_factor`` for
    the first ``burst_duty`` of every ``burst_period_s``). The default
    ``burst_factor=1`` degenerates to the pure sinusoid; cranking it
    raises the interarrival CV above Poisson's 1.0, which is exactly
    what the loadscope burstiness estimator must detect. Requests carry
    compact ``gen`` specs (deterministic per-rid prompts), so the trace
    stays a few bytes per event at any scale. Fully deterministic in
    ``seed``."""
    import random as _random

    if duration_s <= 0 or base_rate <= 0:
        raise ValueError("make_diurnal_trace needs duration_s > 0 and "
                         f"base_rate > 0, got {duration_s}/{base_rate}")
    peak = float(peak_rate) if peak_rate is not None else float(base_rate)
    if peak < base_rate:
        raise ValueError(f"peak_rate {peak} < base_rate {base_rate}")
    period = float(period_s) if period_s is not None else float(duration_s)
    bperiod = float(burst_period_s) if burst_period_s is not None \
        else float(duration_s) / 6.0
    duty = min(max(float(burst_duty), 0.0), 1.0)

    def rate(t: float) -> float:
        diurnal = base_rate + (peak - base_rate) * 0.5 * (
            1.0 - math.cos(2.0 * math.pi * t / period))
        bursting = duty > 0 and (t % bperiod) < duty * bperiod
        return diurnal * (burst_factor if bursting else 1.0)

    lam_max = peak * max(1.0, float(burst_factor))
    rng = _random.Random(int(seed))
    tr = TrafficTrace(meta={
        "source": "make_diurnal_trace", "duration_s": float(duration_s),
        "base_rate": float(base_rate), "peak_rate": peak,
        "period_s": period, "burst_factor": float(burst_factor),
        "burst_duty": duty, "burst_period_s": bperiod, "seed": int(seed)})
    t, rid = 0.0, 0
    while True:
        t += rng.expovariate(lam_max)      # thinning: candidate at peak rate
        if t >= duration_s:
            break
        if rng.random() * lam_max > rate(t):
            continue                       # thinned out of the lull
        tr.add_request(rid, t,
                       gen={"seed": int(seed) * 100003 + rid,
                            "len": int(prompt_len), "vocab": int(vocab)},
                       max_new=int(max_new), seed=rid)
        rid += 1
    return tr


def _drive_timeline(engine, trace: TrafficTrace, clock: ReplayClock,
                    max_iterations: int = 2_000_000) \
        -> "tuple[dict, int]":
    """Replay ``trace`` on ``engine`` so fake time advances ONLY through
    the shared ticking clock (``dt`` per read) plus idle jumps to the
    next arrival. That makes the queueing timeline self-consistent with
    the span-measured service rates (a step's span duration IS the fake
    time the step consumed), which is the whole point of the scaling
    backtest: utilization ρ measured by loadscope and the achieved
    queue waits live on the same clock. Returns ``(rid → finished
    Request, shed_count)``."""
    from ..resilience.guards import QueueFullError

    pending = sorted(trace.requests, key=lambda e: e.get("t_rel", 0.0))
    done: dict = {}
    i = submitted = shed = it = 0
    while i < len(pending) or len(done) < submitted:
        while i < len(pending) and pending[i]["t_rel"] <= clock.t:
            ev = pending[i]
            i += 1
            try:
                engine.submit(resolve_prompt(ev), int(ev["max_new"]),
                              seed=int(ev["seed"]))
                submitted += 1
            except (QueueFullError, ValueError):
                shed += 1                  # a shed is data, not a crash
        for req in engine.step():
            done[req.rid] = req
            engine.pop_result(req.rid)
        if i < len(pending) and len(done) >= submitted:
            # nothing in flight and the next arrival is in the future:
            # jump there (underload must not burn iterations — or fake
            # seconds — spinning on an empty engine)
            clock.advance_to(pending[i]["t_rel"])
        it += 1
        if it > max_iterations:
            raise RuntimeError(
                f"scaling backtest stuck: {len(done)}/{submitted} "
                f"finished after {max_iterations} iterations")
    return done, shed


def _achieved(done: dict, trace: TrafficTrace, horizon_s: float) -> dict:
    """Measured outcome of one backtest run: mean queue wait (admit −
    submit on the shared fake clock) and goodput points — decode tokens
    of requests that FINISHED inside the trace window, as a percentage
    of every decode token the trace offered (sheds and late finishers
    count against it)."""
    waits = [float(r.admit_t) - float(r.submit_t) for r in done.values()
             if r.admit_t is not None and r.submit_t is not None]
    offered = sum(int(e["max_new"]) for e in trace.requests)
    served = sum(len(r.tokens) for r in done.values()
                 if r.finish_t is not None and r.finish_t <= horizon_s)
    return {
        "finished": len(done),
        "queue_wait_mean_s": (sum(waits) / len(waits)) if waits else None,
        "offered_decode_tokens": int(offered),
        "served_by_horizon": int(served),
        "goodput_pts": (100.0 * served / offered) if offered else None,
    }


def scaling_backtest(engine, serving: dict, *, sizes=(1, 2),
                     requests_target: int = 48, prompt_len: int = 6,
                     max_new: int = 8, overload: float = 1.5,
                     burst_factor: float = 3.0, seed: int = 0,
                     tolerance_pts: float = 10.0,
                     programs=None) -> dict:
    """Backtest the loadscope scaling advisor against replayed reality.

    Self-calibrating: a probe run on ONE replica measures the fleet's
    fake-time decode capacity from its span ring, then a diurnal ×
    bursty trace is synthesized whose offered decode-token rate is
    ``overload`` × that capacity — so one replica is genuinely
    saturated and two are comfortably inside the knee, whatever the
    host's clock granularity. For each fleet size ``n`` in ``sizes``
    the trace replays at ``n`` and ``n+1`` replicas on a shared
    :class:`ReplayClock`; the advisor's add-replica what-if from the
    ``n``-replica run (predicted ρ, queue wait, goodput after scaling)
    is scored against the MEASURED ``n+1`` outcome:

    - ``goodput_error_pts`` — |predicted − achieved| goodput, in
      percentage points of offered decode tokens;
    - ``wait_error_pts`` — |predicted − achieved| post-scale queue
      wait, normalized by the larger of the pre-scale measured wait and
      one request's service time (so a near-zero wait on both sides
      scores near-zero, and an overloaded baseline isn't penalized for
      absolute seconds).

    The run passes when every size's both errors are within
    ``tolerance_pts``. Degradation contract: if the probe cannot
    measure capacity (spans off, no decode steps), the report carries
    ``unmeasured`` reasons and ``pass: None`` — never an exception."""
    from collections import OrderedDict as _OD

    from ..serving.fleet import FleetEngine

    progs = programs if programs is not None else _OD()
    base = {**serving, "spans": True}
    base.pop("loadscope", None)

    def _fleet(n: int, scope: dict, clock: ReplayClock) -> FleetEngine:
        return FleetEngine(engine, {**base, "loadscope": scope},
                           replicas=n, clock=clock, programs=progs)

    # ---- probe: measure fake-time capacity on one saturated replica.
    # The span ring alone cannot price the fake timeline: on a ticking
    # clock most reads land OUTSIDE the compute spans (on hardware the
    # compute dominates wall time; here every read costs dt), so the
    # probe floods one replica and measures REALIZED tokens per fake
    # second, then installs that as the loadscope service calibration
    # (``LoadScope.service_override``) for every backtest run. The
    # span-vs-realized ratio also rescales the prefill rate.
    probe_trace = TrafficTrace()
    probe_n = 24
    for rid in range(probe_n):
        probe_trace.add_request(rid, 0.0,
                                gen={"seed": rid, "len": prompt_len,
                                     "vocab": 256},
                                max_new=max_new, seed=rid)
    clock = ReplayClock(dt=1e-4)
    fl = _fleet(1, {"window_s": 1e9}, clock)
    done, _ = _drive_timeline(fl, probe_trace, clock)
    replica = next(iter(fl.replicas.values()))
    snap = replica.scaling_snapshot()
    svc = (snap or {}).get("service") or {}
    span_per_slot = svc.get("decode_tokens_per_slot_s")
    span_prefill = svc.get("prefill_tokens_per_s")
    slots = int(svc.get("slots") or 0)
    wall = clock.t
    fl.close()
    if span_per_slot is None or slots < 1 or wall <= 0 or not done:
        return {"schema": SCALING_BACKTEST_SCHEMA, "pass": None,
                "unmeasured": ["probe run measured no decode service rate "
                               "(spans ring empty?) — backtest degraded"],
                "sizes": []}
    serviceable = probe_n * max_new / wall         # tokens/fake-s, 1 replica
    per_slot = serviceable / slots
    alpha = per_slot / float(span_per_slot)        # loop time per span time
    calibration = {
        "slots": slots,
        "decode_tokens_per_slot_s": per_slot,
        "decode_tokens_per_s": serviceable,
        "prefill_tokens_per_s": (float(span_prefill) * alpha
                                 if span_prefill is not None else None),
    }
    mean_service_s = max_new / per_slot            # one request in a slot

    # ---- the offered stream: mean decode-token rate = `overload` × the
    # one-replica capacity. The diurnal shape (base 0.6×, peak 1.1× of
    # the reference rate → mean 0.85×) and the burst square wave (mean
    # multiplier 1 + duty·(factor−1)) both inflate the mean, so the
    # reference rate divides them back out.
    duty = 0.3
    shape_mean = 0.5 * (0.6 + 1.1) * (1.0 + duty * (burst_factor - 1.0))
    rate_req = overload * serviceable / max_new / shape_mean
    duration_s = requests_target / (rate_req * shape_mean)
    trace = make_diurnal_trace(
        duration_s=duration_s, base_rate=0.6 * rate_req,
        peak_rate=1.1 * rate_req, burst_factor=burst_factor,
        burst_duty=duty, prompt_len=prompt_len, max_new=max_new,
        seed=seed)
    problems = trace.validate()
    if problems:
        raise ValueError(f"synthesized trace failed validation: {problems}")

    # ---- replay at every needed fleet size (each size once, reused).
    # One shared clock serializes the replicas' steps, so a round over n
    # replicas costs n× the reads of one — but real replicas run in
    # PARALLEL. dt/n makes a full fleet round cost the same fake time as
    # one replica's step, so fleet capacity scales n× like hardware's.
    need = sorted({int(n) for n in sizes} | {int(n) + 1 for n in sizes})
    runs: dict = {}
    for n in need:
        clock = ReplayClock(dt=1e-4 / n)
        fl = _fleet(n, {"window_s": 1e9}, clock)
        for rep_eng in fl.replicas.values():
            rep_eng.loadscope.service_override = calibration
        done, shed = _drive_timeline(fl, trace, clock)
        rep = fl.scaling_report() or {}
        runs[n] = {
            "replicas": n,
            "rho": (rep.get("fleet") or {}).get("rho"),
            "what_ifs": rep.get("what_ifs") or [],
            "shed": shed,
            **_achieved(done, trace, duration_s),
        }
        fl.close()

    # ---- score the advisor: prediction at n vs measurement at n+1
    out_sizes = []
    all_pass: Optional[bool] = True
    for s in sorted({int(n) for n in sizes}):
        now, after = runs[s], runs[s + 1]
        wi = next((w for w in now["what_ifs"]
                   if w.get("action") == "add_replica"), None)
        entry: dict = {"replicas": s, "measured_now": {
            "rho": now["rho"], "queue_wait_mean_s": now["queue_wait_mean_s"],
            "goodput_pts": now["goodput_pts"], "shed": now["shed"]}}
        if wi is None or wi.get("rho_after") is None:
            entry["unmeasured"] = ["no add_replica what-if at this size "
                                   "(utilization unmeasured)"]
            entry["pass"] = None
            all_pass = None
            out_sizes.append(entry)
            continue
        pred_good = wi.get("goodput_after")
        pred_good_pts = 100.0 * pred_good if pred_good is not None else None
        pred_wait = wi.get("predicted_queue_wait_s_after")
        meas_good_pts = after["goodput_pts"]
        meas_wait = after["queue_wait_mean_s"]
        entry["predicted_after"] = {
            "rho": wi.get("rho_after"), "queue_wait_s": pred_wait,
            "goodput_pts": pred_good_pts}
        entry["measured_after"] = {
            "rho": after["rho"], "queue_wait_s": meas_wait,
            "goodput_pts": meas_good_pts, "shed": after["shed"]}
        t_ref = max(now["queue_wait_mean_s"] or 0.0, mean_service_s)
        entry["goodput_error_pts"] = (
            abs(pred_good_pts - meas_good_pts)
            if pred_good_pts is not None and meas_good_pts is not None
            else None)
        entry["wait_error_pts"] = (
            100.0 * abs(pred_wait - meas_wait) / t_ref
            if pred_wait is not None and meas_wait is not None else None)
        errs = [entry["goodput_error_pts"], entry["wait_error_pts"]]
        if any(e is None for e in errs):
            entry["pass"] = None
            all_pass = None
        else:
            ok = all(e <= tolerance_pts for e in errs)
            entry["pass"] = ok
            if all_pass is True and not ok:
                all_pass = False
        out_sizes.append(entry)

    return {
        "schema": SCALING_BACKTEST_SCHEMA,
        "serviceable_tokens_per_s": serviceable,
        "mean_service_s": mean_service_s,
        "trace": {"requests": len(trace.requests),
                  "duration_s": duration_s,
                  "offered_req_per_s_peak": 1.1 * rate_req,
                  "overload": overload, "seed": seed},
        "runs": {str(n): r for n, r in runs.items()},
        "tolerance_pts": float(tolerance_pts),
        "sizes": out_sizes,
        "pass": all_pass,
    }
