"""Request-lifecycle span events: the time-attribution layer.

The PR-2 metrics answer *aggregate* questions ("what is p99 TTFT"); a
span ring answers *attribution* questions ("which request blew its TTFT
SLO and where did the time go — queue, chunked prefill, or decode
co-tenancy"), the same transparent-tracking need T3 motivates for
compute/collective overlap. Every lifecycle edge the serving scheduler
and training engine already stamp (``submit_t`` / ``first_token_t`` /
retirement, the wall-clock-breakdown timers) becomes a typed
:class:`SpanEvent` in a bounded, thread-safe ring buffer.

Cost discipline: recording is host-side floats into a deque under a
lock — no device buffers, no host↔device syncs, no new compiled
programs. Engines hold ``spans = None`` when the operator has not asked
for a ring, and a ring adds no program (``tests/unit/test_observability.py``
compares compile counts with spans on and off). Timestamps come from the owner's injectable clock (the same
one ``ServingStats`` fakes in tests).

The ring is the substrate for two consumers: the Chrome-trace/Perfetto
export (``export.py``) and the crash/stall flight recorder
(``flight.py``), which snapshots the last-N events into a post-mortem
artifact.

The seam (:func:`span`, :func:`emit`) is the one way engine code times a
piece of host work. It has two sinks: the owner's ring, when the operator
set ``spans: true``, and, while a ``jax.profiler`` capture is live, both a
``TraceAnnotation`` named ``ds.<name>`` (the span on the capture's own
timeline, beside the device's) and the process's capture ring, which
:func:`captured` hands out afterwards. With neither, a site costs one
``TraceAnnotation.is_enabled()``.

Three kinds are the process's and not an iteration's: ``COMPILE`` (a
program traced, lowered, or compiled or loaded by the backend; made here
from ``jax.monitoring``'s own events, which carry the function's name),
``INIT`` (an engine built, the package imported) and ``RETRACE``. By
construction none occurs on a steady hot path (a compile inside a
measured window already makes the run invalid), so they are ALWAYS
recorded, into one bounded process-level ring that :func:`lifecycle`
hands out, whatever ``ring`` and the capture say (and into those too).
That ring has one clock, ``time.perf_counter``, whatever clock an owner
fakes: what a replica's cold start was made of is read from it
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import dataclasses
import functools
import re
import threading
import time
from collections import deque
from typing import Callable, Optional

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation

from .metrics import get_registry

# ------------------------------------------------------------- event kinds
# Serving request lifecycle (rid-carrying):
QUEUED = "queued"                  # span: submit → admission (queue wait)
PREFILL_CHUNK = "prefill_chunk"    # span: one bucket-shaped chunk dispatch
PLACED = "placed"                  # instant: request occupied a slot
DECODE_RESIDENCY = "decode"        # span: first token → retirement, in slot
RETIRED = "retired"                # instant: terminal status lands
# Serving engine cadence (no rid):
DECODE_STEP = "decode_step"        # span: one slot decode step (all slots):
                                   # dispatch + read-back, the watchdog's
                                   # window (meta: slots, queue; what the
                                   # cache kind's ``step_meta`` adds,
                                   # inference/kinds — a cca trunk's:
                                   # cache_bytes_per_token,
                                   # state_bytes_per_slot, live_positions,
                                   # experts_touched, moe_rows_over_routed,
                                   # moe_rows_routed, moe_load_max_over_mean,
                                   # router_top_p)
OCCUPANCY = "occupancy"            # counter: slots occupied / queue depth
# One serving iteration and its phases (``step`` = the iteration; the
# phases are disjoint and lie inside SRV_STEP, so the iteration's self
# time is its span less theirs; docs/OBSERVABILITY.md has the table):
SRV_SUBMIT = "srv.submit"          # span: one submit() (rid-carrying)
SRV_STEP = "srv.step"              # span: one ServingEngine.step()
SRV_DEADLINES = "srv.deadlines"    # span: the deadline sweep
SRV_ADMIT = "srv.admit"            # span: pop_next → the prefill lane set,
                                   # cache init/hydrate/restore dispatched
SRV_PREFILL_READBACK = "srv.prefill_readback"  # span: the blocking read
                                   # of a final chunk's first token
SRV_PLACE = "srv.place"            # span: slot taken, insert dispatched
SRV_DECODE_DISPATCH = "srv.decode_dispatch"    # span: the step enqueued
SRV_DECODE_READBACK = "srv.decode_readback"    # span: the fused read-back
SRV_RETIRE = "srv.retire"          # span: tokens accounted, rows retired
SRV_TAIL = "srv.tail"              # span: demotes, stats, results stored
# Training engine cadence:
TRAIN_STEP = "train_step"          # span: one train_batch() call
TRAIN_PHASE = "train_phase"        # span: a part of it (meta: phase =
                                   # batch_prep/step_dispatch/step_sync,
                                   # bwd/host_step under offload)
# Fleet request hops (serving/fleet.py — recorded in the FLEET-level
# ring, rid-carrying; the cross-replica half of a distributed trace):
ROUTE = "route"                    # instant: router picked an admission
                                   # target (meta: replica)
REQUEUE = "requeue"                # instant: failover moved the request
                                   # onto a survivor (meta: replica,
                                   # attempt)
HANDOFF_EXPORT = "handoff_export"  # span: prefill pages gathered to host
HANDOFF_PENDING = "handoff_pending"  # span: payload host-held, waiting
                                   # for a decode slot/pool
HANDOFF_IMPORT = "handoff_import"  # span: scatter into the decode replica
# KV residency observatory (observability/kvscope.py — rendered as
# per-session residency tracks in the Perfetto export; meta carries
# ``session``):
SESSION_ACTIVE = "session_active"  # span: first admit/resume → last retire
SESSION_IDLE = "session_idle"      # span: idle gap closed by a resume
                                   # (meta: regret_tokens the resume
                                   # re-paid — 0 when the prefix survived)
# Communication observatory (observability/commscope.py — rendered as a
# `comm` track beside the train pid in the Perfetto export):
COMM_OP = "comm_op"                # span: one collective op in flight
                                   # (meta: kind, op, device)
COMM_EXPOSED = "comm_exposed"      # span: an exposed gap — collective
                                   # time NOT hidden behind compute
RETRACE = "retrace"                # instant: a built serving program met a
                                   # new argument signature (meta:
                                   # program, signatures, module, why).
                                   # Not a MARKER: warm-up has them and
                                   # is no incident
# The process's lifecycle (always recorded, see ``lifecycle``):
COMPILE = "compile"                # span: one stage of one program's way
                                   # to an executable (meta: program =
                                   # the module's name, ``jit__step_impl``;
                                   # stage = trace | lower | backend; on a
                                   # backend span cache_hit, retrieval_s)
INIT = "init"                      # span: the package imported, an engine
                                   # built (meta: phase = import |
                                   # inference | serving | train |
                                   # compile_train_step)
# Cross-cutting:
MARKER = "marker"                  # instant: SLO burn, anomaly, watchdog,
                                   # compile storm — the "why" of a dump

_COUNTER_KINDS = frozenset({OCCUPANCY})
_LIFECYCLE_KINDS = frozenset({COMPILE, INIT, RETRACE})
_INSTANT_KINDS = frozenset({PLACED, RETIRED, MARKER, ROUTE, REQUEUE})


@dataclasses.dataclass
class SpanEvent:
    """One typed lifecycle event. ``t1 is None`` marks an instant event;
    counters carry their samples in ``meta``."""

    kind: str
    t0: float
    t1: Optional[float] = None
    rid: Optional[int] = None
    slot: Optional[int] = None
    step: Optional[int] = None
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0

    @property
    def instant(self) -> bool:
        return self.t1 is None

    def as_dict(self) -> dict:
        out = {"kind": self.kind, "t0": self.t0}
        if self.t1 is not None:
            out["t1"] = self.t1
        for k in ("rid", "slot", "step"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        if self.meta:
            out["meta"] = self.meta
        return out


class SpanRecorder:
    """Bounded thread-safe ring of :class:`SpanEvent`.

    ``capacity`` bounds host memory for the life of the process (a busy
    replica emits a handful of events per iteration; 4096 covers minutes
    of context around a fault, which is what a post-mortem needs — the
    JSONL sinks carry the unbounded history). ``clock`` is only used by
    the convenience emitters that stamp "now" themselves; callers that
    already hold timestamps (the scheduler's ``submit_t``, the decode
    window's ``t0``) pass them explicitly so spans and metrics agree to
    the exact float."""

    def __init__(self, capacity: int = 4096,
                 clock: Callable[[], float] = time.perf_counter):
        if capacity <= 0:
            raise ValueError(f"span ring capacity must be > 0, "
                             f"got {capacity}")
        self.capacity = int(capacity)
        self.clock = clock
        self._ring: deque[SpanEvent] = deque(maxlen=self.capacity)
        # RLock, not Lock: the PreemptionGuard SIGTERM handler notes a
        # marker from the MAIN thread — which may be interrupted inside
        # emit() holding this very lock; a non-reentrant lock would
        # deadlock the handler through the whole grace window
        self._lock = threading.RLock()
        self._emitted = 0

    # ------------------------------------------------------------ recording
    def emit(self, kind: str, t0: float, t1: Optional[float] = None, *,
             rid: Optional[int] = None, slot: Optional[int] = None,
             step: Optional[int] = None, **meta) -> SpanEvent:
        ev = SpanEvent(kind=kind, t0=float(t0),
                       t1=None if t1 is None else float(t1),
                       rid=rid, slot=slot, step=step, meta=meta)
        self.append(ev)
        return ev

    def append(self, ev: SpanEvent) -> None:
        with self._lock:
            self._ring.append(ev)
            self._emitted += 1

    def marker(self, name: str, t: Optional[float] = None,
               **meta) -> SpanEvent:
        """Instant MARKER event ("why" annotations: SLO burn, anomaly,
        watchdog stall, compile storm)."""
        return self.emit(MARKER, self.clock() if t is None else t,
                         name=name, **meta)

    def counter(self, t: Optional[float] = None, **samples) -> SpanEvent:
        """OCCUPANCY counter sample (queue depth, slots occupied, ...)."""
        return self.emit(OCCUPANCY, self.clock() if t is None else t,
                         **samples)

    # -------------------------------------------------------------- readout
    def events(self) -> list[SpanEvent]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def emitted(self) -> int:
        """Total events ever emitted (ring evictions included)."""
        with self._lock:
            return self._emitted

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


# ----------------------------------------------------------------- the seam
# Events recorded while a profiler capture was live, whichever engine made
# them: in a capture's seconds an engine with ``spans`` unset has no ring
# of its own, and a reader outside the engine (a benchmark's reducer) has
# no handle on it. Cleared when the next capture starts.
_CAPTURE = SpanRecorder(capacity=1 << 15)
_capture_live = False


def _capturing() -> bool:
    global _capture_live
    live = TraceAnnotation.is_enabled()
    if live != _capture_live:
        _capture_live = live
        if live:
            _CAPTURE.clear()
    return live


# The process's lifecycle: every COMPILE, INIT and RETRACE since the package
# was imported, spans on or off, capture or none. A GPT-2 serving set-up
# leaves some 120 events here and the widest cell some 330; a process that
# compiles on without end keeps the newest 4096.
_LIFECYCLE = SpanRecorder(capacity=4096)


def now() -> float:
    """The lifecycle ring's clock: ``time.perf_counter`` unless a test set
    another on the ring. Every site that stamps a lifecycle kind reads
    this one, whatever clock its engine was given."""
    return _LIFECYCLE.clock()


def lifecycle() -> list[SpanEvent]:
    """Every ``COMPILE``, ``INIT`` and ``RETRACE`` of this process, in the
    order they closed, on ``time.perf_counter``: what was traced, lowered,
    compiled or loaded under which name, and which engine's build it fell
    into (by time: a ``COMPILE`` span lies inside the ``INIT`` span that
    caused it)."""
    return _LIFECYCLE.events()


def captured() -> list[SpanEvent]:
    """What the seam recorded during the newest profiler capture, in the
    order the spans closed. A capture that starts and stops between two
    iterations holds whole iterations."""
    return _CAPTURE.events()


def emit(ring: Optional[SpanRecorder], kind: str, t0: float,
         t1: Optional[float] = None, **fields) -> None:
    """A span from stamps the caller already holds (a request's lifecycle,
    the watchdog's window), or an instant or counter: to ``ring`` if there
    is one, to the capture ring while a capture is live, and a lifecycle
    kind to the lifecycle ring always. No annotation: one cannot be opened
    in the past."""
    _record(ring, _capturing(), kind, t0, t1, fields)


def _record(ring, live, kind, t0, t1, fields) -> Optional[SpanEvent]:
    ev = None
    if ring is not None:
        ev = ring.emit(kind, t0, t1, **fields)
        if live:
            _CAPTURE.append(ev)
    elif live:
        ev = _CAPTURE.emit(kind, t0, t1, **fields)
    if kind in _LIFECYCLE_KINDS:
        if ev is None:
            ev = _LIFECYCLE.emit(kind, t0, t1, **fields)
        else:
            _LIFECYCLE.append(ev)
    return ev


def instant(ring: Optional[SpanRecorder], clock: Callable[[], float],
            kind: str, **fields) -> None:
    """An instant or a counter sample (OCCUPANCY, RETRACE) stamped now.
    The clock is read only when something records: an engine on a
    counting test clock keeps its stamps with spans off. A lifecycle kind
    always records; its site passes :func:`now`."""
    live = _capturing()
    if ring is not None or live or kind in _LIFECYCLE_KINDS:
        _record(ring, live, kind, clock(), None, fields)


class _Off:
    """What :func:`span` hands out when nothing records."""

    __slots__ = ()
    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **fields) -> None:
        pass

    amend = note


_OFF = _Off()


class _Span:
    __slots__ = ("ring", "clock", "kind", "fields", "t0", "_annotation",
                 "event")
    recording = True

    def __init__(self, ring, clock, kind, annotation, fields):
        self.ring, self.clock, self.kind = ring, clock, kind
        self.fields = fields
        self._annotation = annotation

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self.t0 = self.clock()
        return self

    def __exit__(self, *exc):
        t1 = self.clock()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        # in the capture ring exactly when it is in the capture
        self.event = _record(self.ring, self._annotation is not None,
                             self.kind, self.t0, t1, self.fields)
        return False

    def note(self, **fields) -> None:
        """What is known only inside the span (the rid ``submit`` drew)."""
        self.fields.update(fields)

    def amend(self, **meta) -> None:
        """Meta known only after the span closed: a count the device held
        until a later read-back brought it (a chunk's expert rows)."""
        if self.event is not None:
            self.event.meta.update(meta)


def span(ring: Optional[SpanRecorder], clock: Callable[[], float],
         kind: str, *, name: Optional[str] = None, **fields):
    """Time a piece of host code: ``with span(ring, clock, KIND, step=n):``.
    Records a :class:`SpanEvent` of ``kind`` as :func:`emit` does, stamped
    with ``clock`` (the owner's, so spans and metrics agree), and while a
    capture is live wraps the block in ``TraceAnnotation("ds.<name>")``
    (``name`` defaults to ``kind``). ``fields`` are ``rid`` / ``slot`` /
    ``step`` and meta. With no ring and no capture it returns a shared
    no-op, unless ``kind`` is a lifecycle kind."""
    live = _capturing()
    if ring is None and not live and kind not in _LIFECYCLE_KINDS:
        return _OFF
    return _Span(ring, clock, kind,
                 TraceAnnotation("ds." + (name or kind)) if live else None,
                 fields)


# ------------------------------------------------------- the process's life
def timed_init(phase: str):
    """Decorator for an engine's ``__init__`` (or any build that happens
    once): the call is an ``INIT`` span ``phase`` in the lifecycle ring,
    annotated ``ds.init.<phase>`` in a live capture. Stamped with the
    ring's clock (:func:`now`) whatever clock the engine is given, as the
    ``COMPILE`` spans that fall inside it are."""
    def wrap(build):
        @functools.wraps(build)
        def timed(*args, **kwargs):
            with span(None, now, INIT, name="init." + phase, phase=phase):
                return build(*args, **kwargs)
        return timed
    return wrap


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_STAGES = {_TRACE: "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "backend"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")
_traces = 0                        # trace events of the process (traces())


class _Open(threading.local):
    """Per thread: how many stage events are open, and what the
    persistent cache said inside the backend event that is."""
    depth = 0
    cache: dict = {}


_open = _Open()


def traces() -> int:
    """How many functions this process has traced for a ``jax.jit`` so far.
    Every new signature of a built program starts with one, so a reader of
    the programs' own caches (``ServingEngine._count_retraces``) need look
    only when this has moved."""
    return _traces


def module_name(fun_name: str, stage: str) -> str:
    """One spelling for a program in every stage: the compiled module's,
    as the device trace prints it and ``benchmark/reduce.py``'s program
    patterns match it (``jit__step_impl``). JAX names the trace by the
    function (``_step_impl``) and the lowering and the backend by
    ``jit(_step_impl)``, which it cleans the same way for the module."""
    if stage == "trace":
        fun_name = f"jit({fun_name})"
    return _NOT_IN_A_MODULE_NAME.sub("_", fun_name).rstrip("_")


def _stage_opens(event: str, *_, **__) -> None:
    if event in _STAGES:
        _open.depth += 1
        if event == _TRACE:
            global _traces
            _traces += 1


def _cache_said(event: str, **_) -> None:
    if event == _CACHE_HIT or event == _CACHE_MISS:
        _open.cache = dict(_open.cache, cache_hit=event == _CACHE_HIT)
        if event == _CACHE_MISS:
            get_registry().counter("Compile/cache_misses").inc()


def _cache_read_took(event: str, secs: float, **_) -> None:
    if event == _CACHE_READ:
        _open.cache = dict(_open.cache, retrieval_s=float(secs))


def _stage_closes(event: str, start: float, end: float, *,
                  fun_name: str = "?", **_) -> None:
    """A ``COMPILE`` span from one of JAX's own stage events. **The
    clock:** JAX stamps ``start`` and ``end`` with the wall clock
    (``time.time``); the seam, the engines and a benchmark's process start
    are on ``time.perf_counter``. This callback runs as the stage closes,
    so the span ends at the ring's clock read here (:func:`now`) and starts
    ``end - start`` before it: no offset between the two clocks is kept,
    and a wall clock that is stepped meanwhile moves nothing
    (``test_compile_spans_lie_on_perf_counter`` holds a span inside a
    ``perf_counter`` bracket around its call). A trace or a lowering that
    happens inside another stage (the ``jnp`` functions a traced function
    calls are traced for a ``jit`` of their own) is part of that stage's
    time and leaves no span; a backend event always does."""
    stage = _STAGES.get(event)
    if stage is None:
        return
    _open.depth = max(0, _open.depth - 1)
    if stage != "backend" and _open.depth:
        return
    t1 = now()
    seconds = float(end) - float(start)
    meta = {}
    reg = get_registry()
    if stage == "backend":
        meta, _open.cache = _open.cache, {}
        reg.counter("Compile/programs").inc()
        reg.counter("Compile/backend_s").inc(seconds)
    else:
        reg.counter("Compile/trace_lower_s").inc(seconds)
    emit(None, COMPILE, t1 - seconds, t1,
         program=module_name(fun_name, stage), stage=stage, **meta)


def compiled_since(t: float, program: str) -> str:
    """What the stages after a trace did for ``program`` since ``t``, in
    words, for a ``RETRACE``'s ``why``: JAX 0.9 says which argument made a
    signature new only through ``jax_explain_cache_misses``' log, which
    costs at every miss; what the new signature COST is here for nothing.
    A trace alone means the types were known and only the argument's
    placement (committed-ness, sharding, a numpy array for a device one)
    was new to the call's fast path."""
    mine = [e for e in _LIFECYCLE.events() if e.kind == COMPILE
            and e.t0 >= t and e.meta.get("program") == program]
    if not mine:
        return "no stage event under this name"
    said = []
    for e in mine:
        part = f"{e.meta['stage']} {e.duration:.3f} s"
        if "cache_hit" in e.meta:
            part += " (loaded from the cache)" if e.meta["cache_hit"] \
                else " (compiled: a cache miss)"
        said.append(part)
    if all(e.meta["stage"] == "trace" for e in mine):
        said.append("no lowering: an executable it had")
    return ", ".join(said)


def _listen() -> None:
    """Once, when this module is first imported (the package imports it):
    JAX has no way to ask whether a listener is registered, and a second
    set would double every span."""
    _monitoring.register_scalar_listener(_stage_opens)
    _monitoring.register_event_listener(_cache_said)
    _monitoring.register_event_duration_secs_listener(_cache_read_took)
    _monitoring.register_event_time_span_listener(_stage_closes)
    for name in ("Compile/programs", "Compile/cache_misses",
                 "Compile/trace_lower_s", "Compile/backend_s"):
        get_registry().counter(name)     # readable at 0: none, not unkept


_listen()
