"""Span export: Chrome-trace-event JSON (Perfetto) + per-request JSONL log.

Machine-readable views of the span ring (``spans.py``):

- :func:`to_chrome_trace` renders the events in the Chrome trace-event
  format Perfetto loads directly: the serving process as one pid with
  the queue, the prefill lane, the decode step, and every slot as its
  own named track; requests as complete (``X``) spans nested on their
  tracks; queue depth / slot occupancy as counter (``C``) tracks; SLO /
  anomaly / watchdog markers as instant (``i``) events. Training spans
  land under a second pid. ``ts`` is microseconds relative to the
  earliest event, per the spec.
- :func:`merge_fleet_trace` stitches a FLEET of rings into ONE trace:
  every replica's serving ring becomes its own pid (named after the
  replica), the fleet-level ring (router decisions, handoff hops —
  serving/fleet.py) lands under a ``router`` pid, and each request that
  crossed replicas gets a flow (``s``/``t``/``f`` arrows, id = rid)
  connecting its hops — the Dapper-style end-to-end timeline of a
  distributed request.
- :func:`hop_trace` is the per-request hop-latency decomposition
  (queue_wait/prefill/handoff_wait/import/decode/e2e) derived from the
  host timestamps the schedulers and the fleet stamp on the request —
  no span ring needed, which is why the request log can carry it.
- :class:`RequestLogSink` is a MonitorMaster-compatible writer that
  additionally accepts whole request records (one JSON object per
  retired request) — the request-level ground truth the scalar
  ``(name, value, step)`` event contract cannot carry.

:func:`validate_chrome_trace` is the schema gate the tests (and the
flight recorder's own smoke assertion) run over every generated trace:
required keys, known phases, non-negative durations, sorted timestamps,
matched B/E nesting, matched flow ids, and (for traces that name their
processes) no events under an unnamed pid.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Optional

import numpy as np

from . import spans as S
from .sinks import JsonlSink

# pids in the exported trace: one "process" per engine kind.
PID_SERVING = 1
PID_TRAIN = 2
PID_PROCESS = 3     # the process's lifecycle (spans.lifecycle()): one track,
                    # an engine's build with its programs' stages nested

# merged fleet traces: the router/handoff ring fronts the trace, replicas
# follow in fleet order (pid 10 + i, each named after its replica).
PID_FLEET = 1
_PID_REPLICA0 = 10
_FLEET_TID_ROUTER = 1
_FLEET_TID_HANDOFF = 2
_FLEET_TID_MARKERS = 3

# Fixed serving tids; slots start at _TID_SLOT0 (slot k → tid k + 10).
_TID_QUEUE = 1
_TID_PREFILL = 2
_TID_STEP = 3
_TID_MARKERS = 4
_TID_LOOP = 5       # the host loop: srv.step, its phases nested, srv.submit
_TID_SLOT0 = 10
# per-session residency tracks (kvscope lifecycle spans) allocate from
# here in first-seen order — high enough that slot tids can never reach
_TID_SESSION0 = 1000

_TRAIN_TIDS = {"train_step": 1}   # phases allocate 2.. in first-seen order

# the communication observatory's tracks (observability/commscope.py),
# fixed high so dynamically-allocated phase tids can never collide:
# collective ops in flight, and the exposed gaps (collective time NOT
# hidden behind compute — the T3 number, visible as a track)
_TID_COMM = 98
_TID_COMM_EXPOSED = 99


def _sec_to_us(t: float, origin: float) -> float:
    return max(0.0, (t - origin) * 1e6)


def _slot_tid(slot) -> int:
    return _TID_SLOT0 + int(slot)


def to_chrome_trace(events: Iterable[S.SpanEvent],
                    job_name: str = "deepspeed_tpu",
                    origin: Optional[float] = None) -> dict:
    """Span events → a Chrome trace-event JSON object (Perfetto-loadable).

    Events are emitted sorted by ``ts`` and every span uses the complete
    (``X``) phase — no B/E pairing for a ring buffer whose head may have
    evicted a B while keeping its E. ``origin`` pins the t=0 reference
    (``merge_fleet_trace`` passes one shared origin so every replica's
    timestamps land on the same axis); None = this ring's earliest event."""
    evs = list(events)
    if not evs:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"job": job_name}}
    if origin is None:
        origin = min(e.t0 for e in evs)
    out: list[dict] = []
    used_tids: dict[int, set] = {PID_SERVING: set(), PID_TRAIN: set(),
                                 PID_PROCESS: set()}
    train_tids = dict(_TRAIN_TIDS)
    session_tids: dict[str, int] = {}    # residency tracks, first-seen

    def add(pid, tid, ph, name, ts, dur=None, args=None):
        ev = {"name": name, "ph": ph, "pid": pid, "tid": tid,
              "ts": round(ts, 3)}
        if dur is not None:
            ev["dur"] = round(max(0.0, dur), 3)
        if ph == "i":
            ev["s"] = "p"             # process-scoped instant
        if args:
            ev["args"] = args
        used_tids[pid].add(tid)
        out.append(ev)

    for e in evs:
        ts = _sec_to_us(e.t0, origin)
        dur = None if e.t1 is None else (e.t1 - e.t0) * 1e6
        args = {k: v for k, v in e.meta.items()}
        if e.rid is not None:
            args["rid"] = e.rid
        if e.step is not None:
            args["step"] = e.step
        if e.kind == S.QUEUED:
            add(PID_SERVING, _TID_QUEUE, "X", f"queued rid={e.rid}", ts,
                dur or 0.0, args)
        elif e.kind == S.PREFILL_CHUNK:
            add(PID_SERVING, _TID_PREFILL, "X",
                f"prefill rid={e.rid} chunk={e.meta.get('chunk', '?')}",
                ts, dur or 0.0, args)
        elif e.kind == S.PLACED:
            add(PID_SERVING, _slot_tid(e.slot), "i",
                f"placed rid={e.rid}", ts, None, args)
        elif e.kind == S.DECODE_RESIDENCY:
            add(PID_SERVING, _slot_tid(e.slot), "X",
                f"decode rid={e.rid}", ts, dur or 0.0, args)
        elif e.kind == S.RETIRED:
            add(PID_SERVING,
                _slot_tid(e.slot) if e.slot is not None and e.slot >= 0
                else _TID_QUEUE, "i",
                f"retired rid={e.rid} [{e.meta.get('status', '?')}]",
                ts, None, args)
        elif e.kind == S.DECODE_STEP:
            add(PID_SERVING, _TID_STEP, "X", "decode_step", ts,
                dur or 0.0, args)
        elif e.kind.startswith("srv."):
            add(PID_SERVING, _TID_LOOP, "X", e.kind[4:], ts, dur or 0.0,
                args)
        elif e.kind == S.OCCUPANCY:
            # one counter track per sample name — Perfetto draws them as
            # stacked value timelines
            for k, v in e.meta.items():
                out.append({"name": k, "ph": "C", "pid": PID_SERVING,
                            "tid": 0, "ts": round(ts, 3),
                            "args": {k: v}})
        elif e.kind == S.RETRACE:
            add(PID_SERVING, _TID_MARKERS, "i",
                f"retrace:{e.meta.get('program', '?')}", ts, None, args)
        elif e.kind == S.MARKER:
            nm = e.meta.get("name", "marker")
            add(PID_SERVING, _TID_MARKERS, "i", f"marker:{nm}", ts, None,
                args)
        elif e.kind == S.TRAIN_STEP:
            add(PID_TRAIN, train_tids["train_step"], "X", "train_step",
                ts, dur or 0.0, args)
        elif e.kind == S.TRAIN_PHASE:
            phase = e.meta.get("phase", "phase")
            tid = train_tids.setdefault(phase, len(train_tids) + 1)
            add(PID_TRAIN, tid, "X", phase, ts, dur or 0.0, args)
        elif e.kind in (S.SESSION_ACTIVE, S.SESSION_IDLE):
            # per-session residency track (kvscope): active bursts and
            # the idle gaps between them on one line per session — the
            # host-tier trade (idle HBM vs regretted recompute) readable
            # straight off the timeline
            sid = str(e.meta.get("session", "?"))
            tid = session_tids.setdefault(
                sid, _TID_SESSION0 + len(session_tids))
            nm = "active" if e.kind == S.SESSION_ACTIVE else "idle"
            add(PID_SERVING, tid, "X", nm, ts, dur or 0.0, args)
        elif e.kind == S.COMM_OP:
            add(PID_TRAIN, _TID_COMM, "X",
                str(e.meta.get("collective", "collective")), ts,
                dur or 0.0, args)
        elif e.kind == S.COMM_EXPOSED:
            add(PID_TRAIN, _TID_COMM_EXPOSED, "X", "exposed", ts,
                dur or 0.0, args)
        elif e.kind == S.COMPILE:
            add(PID_PROCESS, 1, "X",
                f"{e.meta.get('stage')}:{e.meta.get('program')}", ts,
                dur or 0.0, args)
        elif e.kind == S.INIT:
            add(PID_PROCESS, 1, "X", f"init.{e.meta.get('phase')}", ts,
                dur or 0.0, args)
        else:   # unknown kind: keep it visible rather than dropping it
            add(PID_SERVING, _TID_MARKERS, "i", f"event:{e.kind}", ts,
                None, args)

    out.sort(key=lambda ev: ev["ts"])
    meta: list[dict] = []

    def name_meta(pid, name):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "ts": 0.0, "args": {"name": name}})

    def thread_meta(pid, tid, name):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "ts": 0.0, "args": {"name": name}})

    if used_tids[PID_SERVING] or any(ev["pid"] == PID_SERVING
                                     for ev in out):
        name_meta(PID_SERVING, f"{job_name}:serving")
        for tid, nm in ((_TID_QUEUE, "queue"), (_TID_PREFILL, "prefill"),
                        (_TID_STEP, "decode-step"),
                        (_TID_MARKERS, "markers"), (_TID_LOOP, "loop")):
            if tid in used_tids[PID_SERVING]:
                thread_meta(PID_SERVING, tid, nm)
        for tid in sorted(t for t in used_tids[PID_SERVING]
                          if _TID_SLOT0 <= t < _TID_SESSION0):
            thread_meta(PID_SERVING, tid, f"slot {tid - _TID_SLOT0}")
        for sid, tid in session_tids.items():
            thread_meta(PID_SERVING, tid, f"session {sid}")
    if used_tids[PID_TRAIN]:
        name_meta(PID_TRAIN, f"{job_name}:train")
        for phase, tid in train_tids.items():
            if tid in used_tids[PID_TRAIN]:
                thread_meta(PID_TRAIN, tid, phase)
        for tid, nm in ((_TID_COMM, "comm"),
                        (_TID_COMM_EXPOSED, "comm-exposed")):
            if tid in used_tids[PID_TRAIN]:
                thread_meta(PID_TRAIN, tid, nm)
    if used_tids[PID_PROCESS]:
        name_meta(PID_PROCESS, f"{job_name}:process")
        thread_meta(PID_PROCESS, 1, "lifecycle")
    return {"traceEvents": meta + out, "displayTimeUnit": "ms",
            "otherData": {"job": job_name}}


def write_chrome_trace(events: Iterable[S.SpanEvent], path,
                       job_name: str = "deepspeed_tpu") -> Path:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(to_chrome_trace(events, job_name)),
                    encoding="utf-8")
    return path


# ------------------------------------------------------------- fleet merge
def merge_fleet_trace(replica_events: "dict[str, Iterable[S.SpanEvent]]",
                      fleet_events: Optional[Iterable[S.SpanEvent]] = None,
                      job_name: str = "fleet") -> dict:
    """Merge N replica span rings + the fleet-level ring into ONE
    Chrome/Perfetto trace.

    Every replica renders exactly as :func:`to_chrome_trace` would —
    queue/prefill/decode-step/slot tracks — but under its OWN pid
    (``10 + i`` in fleet order, process-named ``{job}:{replica}``),
    against one shared time origin so all timelines align. The fleet
    ring (router decisions, requeues, handoff export/pending/import —
    ``serving/fleet.py``) fronts the trace as a ``{job}:router`` process.
    Each request whose ``X`` slices land on more than one pid is
    stitched into a flow (``s``/``t``/``f``, ``id`` = rid): Perfetto
    draws the arrows that make the cross-replica causal chain —
    admission on the prefill replica, the handoff hop on the router
    track, decode residency on the decode replica — readable as one
    request."""
    fleet_evs = list(fleet_events or [])
    rings = {str(name): list(evs) for name, evs in replica_events.items()}
    all_evs = fleet_evs + [e for evs in rings.values() for e in evs]
    if not all_evs:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"job": job_name, "replicas": list(rings)}}
    origin = min(e.t0 for e in all_evs)
    meta: list[dict] = []
    out: list[dict] = []
    # ---- replicas: the single-engine exporter, remapped to a fleet pid
    for i, (name, evs) in enumerate(rings.items()):
        pid = _PID_REPLICA0 + i
        sub = to_chrome_trace(evs, job_name=job_name, origin=origin)
        for ev in sub["traceEvents"]:
            ev = dict(ev)
            ev["pid"] = pid        # serving AND (unexpected) train events
            if ev.get("ph") == "M":
                if ev["name"] == "process_name":
                    ev["args"] = {"name": f"{job_name}:{name}"}
                meta.append(ev)
            else:
                args = dict(ev.get("args") or {})
                args["replica"] = name
                ev["args"] = args
                out.append(ev)
    # ---- fleet ring: router decisions + handoff hops under PID_FLEET
    used_fleet: set = set()

    def fadd(tid, ph, nm, ts, dur=None, args=None):
        ev = {"name": nm, "ph": ph, "pid": PID_FLEET, "tid": tid,
              "ts": round(ts, 3)}
        if dur is not None:
            ev["dur"] = round(max(0.0, dur), 3)
        if ph == "i":
            ev["s"] = "p"
        if args:
            ev["args"] = args
        used_fleet.add(tid)
        out.append(ev)

    for e in fleet_evs:
        ts = _sec_to_us(e.t0, origin)
        dur = None if e.t1 is None else (e.t1 - e.t0) * 1e6
        args = dict(e.meta)
        if e.rid is not None:
            args["rid"] = e.rid
        if e.kind in (S.ROUTE, S.REQUEUE):
            fadd(_FLEET_TID_ROUTER, "i",
                 f"{e.kind} rid={e.rid} -> {e.meta.get('replica', '?')}",
                 ts, None, args)
        elif e.kind in (S.HANDOFF_EXPORT, S.HANDOFF_PENDING,
                        S.HANDOFF_IMPORT):
            fadd(_FLEET_TID_HANDOFF, "X",
                 f"{e.kind.replace('handoff_', '')} rid={e.rid}",
                 ts, dur or 0.0, args)
        elif e.kind == S.MARKER:
            fadd(_FLEET_TID_MARKERS, "i",
                 f"marker:{e.meta.get('name', 'marker')}", ts, None, args)
        else:
            fadd(_FLEET_TID_MARKERS, "i", f"event:{e.kind}", ts, None,
                 args)
    if used_fleet:
        meta.append({"name": "process_name", "ph": "M", "pid": PID_FLEET,
                     "tid": 0, "ts": 0.0,
                     "args": {"name": f"{job_name}:router"}})
        for tid, nm in ((_FLEET_TID_ROUTER, "router"),
                        (_FLEET_TID_HANDOFF, "handoff"),
                        (_FLEET_TID_MARKERS, "markers")):
            if tid in used_fleet:
                meta.append({"name": "thread_name", "ph": "M",
                             "pid": PID_FLEET, "tid": tid, "ts": 0.0,
                             "args": {"name": nm}})
    # ---- flows: one arrow chain per request that crossed pids
    anchors: dict = {}
    for ev in out:
        if ev.get("ph") == "X":
            rid = (ev.get("args") or {}).get("rid")
            if rid is not None:
                anchors.setdefault(rid, []).append(
                    (ev["ts"], ev["pid"], ev["tid"]))
    for rid in sorted(anchors):
        pts = anchors[rid]
        if len({p for _, p, _ in pts}) < 2:
            continue      # never left one replica: no arrow to draw
        pts.sort()
        for j, (ts, pid, tid) in enumerate(pts):
            ph = "s" if j == 0 else ("f" if j == len(pts) - 1 else "t")
            fe = {"name": f"rid {rid}", "cat": "request", "ph": ph,
                  "id": int(rid), "pid": pid, "tid": tid, "ts": ts}
            if ph != "s":
                fe["bp"] = "e"     # bind to the ENCLOSING slice
            out.append(fe)
    # flows sort behind slices at the same ts ("f" last), so the
    # validator's per-id s→f order holds even on coincident stamps
    rank = {"s": 1, "t": 1, "f": 2}
    out.sort(key=lambda ev: (ev["ts"], rank.get(ev["ph"], 0)))
    return {"traceEvents": meta + out, "displayTimeUnit": "ms",
            "otherData": {"job": job_name, "replicas": list(rings)}}


# ----------------------------------------------------------------- validator
_KNOWN_PHASES = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s",
                 "t", "f"}


def validate_chrome_trace(trace: dict) -> list[str]:
    """Schema gate over a trace-event JSON object; returns the list of
    problems (empty = valid). Checks: the ``traceEvents`` envelope,
    per-event required keys, known phases, non-negative ``ts``/``dur``,
    timestamps sorted among non-metadata events, matched B/E nesting
    per (pid, tid), matched flow chains per id (``s`` first, ``f``
    present — a dangling flow draws no arrow in Perfetto), and — when
    the trace names any process — no timeline event under an unnamed
    pid (merged fleet traces name every replica; an unknown pid means
    a ring was merged without its identity)."""
    problems: list[str] = []
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing or non-list traceEvents"]
    last_ts: Optional[float] = None
    stacks: dict[tuple, list] = {}
    named_pids: set = set()
    seen_pids: set = set()
    flows: dict = {}
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        missing = [k for k in ("name", "ph", "pid", "tid", "ts")
                   if k not in ev]
        if missing:
            problems.append(f"event {i}: missing keys {missing}")
            continue
        if ph not in _KNOWN_PHASES:
            problems.append(f"event {i}: unknown phase {ph!r}")
            continue
        ts = ev["ts"]
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"event {i}: bad ts {ts!r}")
            continue
        if ph == "M":
            if ev["name"] == "process_name":
                named_pids.add(ev["pid"])
            continue                  # metadata: outside the timeline
        seen_pids.add(ev["pid"])
        if last_ts is not None and ts < last_ts:
            problems.append(f"event {i}: ts {ts} < previous {last_ts} "
                            "(events must be sorted)")
        last_ts = ts
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i}: X event needs dur >= 0, "
                                f"got {dur!r}")
        elif ph == "B":
            stacks.setdefault((ev["pid"], ev["tid"]), []).append(ev["name"])
        elif ph == "E":
            stack = stacks.get((ev["pid"], ev["tid"]), [])
            if not stack:
                problems.append(f"event {i}: E without matching B on "
                                f"(pid={ev['pid']}, tid={ev['tid']})")
            else:
                stack.pop()
        elif ph in ("s", "t", "f"):
            fid = ev.get("id")
            if fid is None:
                problems.append(f"event {i}: flow event without id")
                continue
            seq = flows.setdefault(fid, [])
            if not seq and ph != "s":
                problems.append(f"event {i}: flow id {fid!r} {ph} "
                                "without a preceding s")
            seq.append(ph)
    for (pid, tid), stack in stacks.items():
        if stack:
            problems.append(f"unclosed B events on (pid={pid}, tid={tid}): "
                            f"{stack}")
    for fid in sorted(flows, key=str):
        seq = flows[fid]
        if "s" in seq and "f" not in seq:
            problems.append(f"dangling flow id {fid!r}: s without f")
    if named_pids:
        for pid in sorted(seen_pids - named_pids, key=str):
            problems.append(f"unknown pid {pid}: events under a pid with "
                            "no process_name metadata")
    return problems


# --------------------------------------------------------------- hop trace
# the hop names, in causal order; hop_trace() keys are these + "_s"
HOP_NAMES = ("queue_wait", "prefill", "handoff_wait", "import", "decode")


def hop_trace(req) -> dict:
    """Per-request hop-latency decomposition, derived from the host
    timestamps the schedulers and the fleet stamp on the request — no
    span ring required (which is why the request log carries it).

    Hops, on the owner's injectable clock:

    - ``queue_wait_s``   — submit → admission (covers EVERY earlier
      attempt plus the requeue delay when the request was failed over);
    - ``prefill_s``      — admission → first token (chunked prefill);
    - ``handoff_wait_s`` — first token → the start of the import that
      seated it on a decode replica (page export + host-held pending);
      a request that DIED in the handoff buffer (deadline, cancel)
      closes this hop at its finish instead — the wait is a handoff
      wait, never decode time; None outside disaggregated serving;
    - ``import_s``       — the import program's wall window; None
      outside disaggregated serving;
    - ``decode_s``       — decode residency → retirement; None for a
      request that never reached a decode slot after its handoff;
    - ``e2e_s``          — submit → retirement.

    The non-null hops TILE ``[submit_t, finish_t]`` — their sum equals
    ``e2e_s`` exactly (the fake-clock tests pin it to within 1% as the
    documented invariant). ``requeue_delay_s`` (kill → re-admission,
    None unless the request was requeued) OVERLAPS ``queue_wait_s`` —
    it separates TTFT from failover cost, it is not an extra hop."""
    st = req.submit_t
    at = getattr(req, "admit_t", None)
    ft = req.first_token_t
    fin = req.finish_t
    ex = getattr(req, "export_t", None)
    i0 = getattr(req, "import_t0", None)
    i1 = getattr(req, "import_t1", None)
    out: dict = {f"{h}_s": None for h in HOP_NAMES}
    out["e2e_s"] = None
    if at is not None:
        out["queue_wait_s"] = at - st
        if ft is not None:
            out["prefill_s"] = ft - at
    if ft is not None:
        if i0 is not None:
            out["handoff_wait_s"] = i0 - ft
            if i1 is not None:
                out["import_s"] = i1 - i0
            if fin is not None:
                out["decode_s"] = fin - (i1 if i1 is not None else i0)
        elif ex is not None:
            # exported but never imported: the request died in the
            # handoff buffer — that time is handoff wait, NOT decode
            if fin is not None:
                out["handoff_wait_s"] = fin - ft
        elif fin is not None:
            out["decode_s"] = fin - ft
    if fin is not None:
        out["e2e_s"] = fin - st
    out["attempts"] = int(getattr(req, "attempts", 0))
    rq = getattr(req, "requeue_t", None)
    out["requeue_delay_s"] = (at - rq) if (rq is not None
                                          and at is not None) else None
    return out


# ------------------------------------------------------------- request log
# v2 grew the fields deterministic replay needs (observability/replay.py
# trace_from_request_log): prompt token ids, sampling seed, session id,
# and the per-request deadline BUDGETS (relative seconds, recomputed from
# the absolute stamps) — an existing request log upgrades cleanly into a
# TrafficTrace. v3 adds `tenant_id` (the cost-attribution dimension,
# observability/tenantscope.py). Old rows still parse everywhere: v2 rows
# upgrade with tenant_id="default" (counted, never a crash); v1 rows (no
# schema key) just cannot replay.
REQUEST_RECORD_SCHEMA = "dstpu.request_record.v3"


def request_record(req, queue_wait_s: Optional[float] = None) -> dict:
    """One retired serving request → a flat JSON-able record (the
    per-request row of the request log and of flight dumps)."""
    status = getattr(req.status, "value", str(req.status))
    admit_t = getattr(req, "admit_t", None)
    if queue_wait_s is None and admit_t is not None:
        queue_wait_s = admit_t - req.submit_t
    ttft = (req.first_token_t - req.submit_t
            if req.first_token_t is not None else None)
    tpot = None
    n = len(req.tokens)
    if (req.finish_t is not None and req.first_token_t is not None
            and n > 1):
        tpot = (req.finish_t - req.first_token_t) / (n - 1)
    dl_ttft = getattr(req, "deadline_ttft", None)
    dl_total = getattr(req, "deadline_total", None)
    prompt = getattr(req, "prompt", None)
    # session ids are opaque hashables (fleet affinity); the record must
    # stay json.dumps-able by every sink, so exotic types stringify
    sid = getattr(req, "session_id", None)
    if sid is not None and not isinstance(sid, (str, int, float, bool)):
        sid = str(sid)
    return {
        "schema": REQUEST_RECORD_SCHEMA,
        "rid": req.rid, "status": status, "prompt_len": req.prompt_len,
        # replay fields: the (prompt, seed) pair IS the request's bit
        # stream (per-request RNG folds from the seed), session_id keys
        # fleet affinity, the deadline budgets are the submit overrides
        "prompt": ([int(t) for t in np.asarray(prompt).reshape(-1)
                    .tolist()] if prompt is not None else None),
        "seed": int(getattr(req, "seed", 0)),
        "session_id": sid,
        "tenant_id": str(getattr(req, "tenant_id", "default") or "default"),
        "ttft_deadline_s": (dl_ttft - req.submit_t
                            if dl_ttft is not None else None),
        "total_deadline_s": (dl_total - req.submit_t
                             if dl_total is not None else None),
        "max_new": req.max_new, "tokens": n, "slot": req.slot,
        "submit_t": req.submit_t, "first_token_t": req.first_token_t,
        "finish_t": req.finish_t, "ttft_s": ttft, "tpot_s": tpot,
        "queue_wait_s": queue_wait_s, "error": req.error or None,
        # failover visibility: >0 means the fleet router moved this
        # request to a surviving replica (REQUEUED transitions)
        "attempts": getattr(req, "attempts", 0),
        # the hop-latency decomposition (hop_trace): offline analysis of
        # where a request's wall time went — queue / prefill / handoff /
        # import / decode — needs no span ring. Handoff hops are null
        # outside disaggregated serving.
        "trace": hop_trace(req),
    }


class RequestLogSink(JsonlSink):
    """Per-request JSONL log riding the MonitorMaster fan-out.

    A :class:`~.sinks.JsonlSink` whose payload is whole request records
    (engines call :meth:`log_request`), not scalar events — so it
    inherits the persistent handle, flush boundaries, and ``rotate_mb``
    rotation. Implements the writer contract so MonitorMaster owns its
    lifecycle like every other sink."""

    SUFFIX = ".requests.jsonl"
    FLUSH_EVERY = 16

    def log_request(self, record: dict) -> None:
        self._write_line(json.dumps(record, separators=(",", ":")))

    def write_events(self, events) -> None:
        """Scalar metric events are not this sink's payload (the JSONL
        event log already carries them) — accept and drop."""
