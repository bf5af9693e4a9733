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

One thing is ALWAYS on beside them: a serving iteration's row
(:class:`Iteration`, :func:`iterations`, :func:`explain`): wall, the
thread's CPU seconds, the seconds inside its blocking waits on the device,
the collector's passes, compiles and what it dispatched, one fixed-width
row a ``step()`` in a preallocated ring of the process. It costs four clock
reads, a bracket around each wait and one row assignment an iteration:
2.6 us where the thread's CPU clock is cheap; where that clock is a system
call into a sandbox's kernel (the chip machine's host: 6 us a read in a
bare loop, 30-40 us behind a wait, in ticks of 10 ms) its two reads are the
whole cost, 15.7 us in a bare loop and 60-90 us in the serving loop
(``examples/iteration_record_microbench.py``; PERF.md, PR 53). A live span
costs ~5.8 us for each of an iteration's fifteen sites.

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
import gc
import re
import threading
import time
from collections import deque
from typing import Callable, Optional

import numpy as np
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


# ------------------------------------------------- the iteration's record
# One row a ``ServingEngine.step()``, always (spans on or off, capture or
# none), so that a long iteration can say why after the fact: a window of
# 40 s holds a handful of them and a traced tail of 4 s most often none.
# Process-wide, as the capture and lifecycle rings are (a benchmark's reducer
# has no handle on the engine); the newest rows win. On the lifecycle
# ring's clock (:func:`now`) whatever clock the engine was given: the
# fake-clock suites count the reads of theirs.
ROW = np.dtype([
    ("step", "i8"),        # the iteration's number
    ("t0", "f8"),          # beside the ``srv.step`` span's own stamps: one
    ("t1", "f8"),          # clock read inside them
    ("cpu_s", "f8"),       # the serving thread's CPU seconds
                           # (``time.thread_time``): wall less this is time
                           # the thread did not run
    ("wait_s", "f8"),      # inside the blocking waits on the device (the
                           # fused read-back, the first token's read, the
                           # seat's wait in admission, speculation's read,
                           # the demote drain's)
    ("gc_s", "f8"),        # the collector's passes that fell inside it,
    ("gc_gen", "i1"),      # and the highest generation among them (-1: none)
    ("compiles", "i2"),    # functions traced by the process + programs the
                           # engine built inside it
    ("chunks", "i2"),      # dispatched: chunk programs that are not final,
    ("finals", "i2"),      # final ones,
    ("seats", "i2"),       # inserts,
    ("stepped", "i1"),     # a decode step (0/1),
    ("ahead", "i1"),       # which went out with one in flight (0/1)
    ("read_step", "i1"),   # read: a step's fused read-back (0/1),
    ("read_first", "i1"),  # a first token (0/1)
    ("slots", "i4"),       # running at the step's dispatch (at the end of
                           # an iteration that dispatched none)
    ("queue", "i4"),       # waiting at the end
    ("tokens", "i4"),      # booked to requests by this call
])
ROWS = 1 << 14             # ~57 s of the steady chat cell's 3.47 ms
_rows = np.zeros(ROWS, ROW)
_rows_written = 0          # ever; the newest is at (_rows_written - 1) % ROWS
_thread_time = time.thread_time

# The collector, from one pair of entries in ``gc.callbacks``: seconds in
# passes, and the passes weighted by generation (1 a pass of generation 0,
# 2**16 one of 1, 2**32 one of 2), so that one difference over an iteration
# says the highest generation that ran in it. The callbacks only add.
_gc_s = 0.0
_gc_weighted = 0
_gc_began = 0.0
_gc_counters: list = []    # Host/gc_s, Host/gc_passes_gen2 of the registry
CAUSES = ("compile", "gc", "prefill", "on_cpu", "device_wait", "off_cpu")
PROGRAM = ("compile", "gc", "on_cpu")     # what a change can take away
MACHINE = ("device_wait", "off_cpu")      # what none moves


def _gc_starts(phase: str, info: dict) -> None:
    if phase == "start":
        global _gc_began
        _gc_began = now()


def _gc_stops(phase: str, info: dict) -> None:
    if phase == "stop":
        global _gc_s, _gc_weighted
        seconds = now() - _gc_began
        _gc_s += seconds
        _gc_weighted += 1 << (16 * info["generation"])
        _gc_counters[0].inc(seconds)
        if info["generation"] == 2:
            _gc_counters[1].inc()


_gc_starts.of_the_seam = _gc_stops.of_the_seam = True


def _watch_gc() -> None:
    """The start stamp FIRST in ``gc.callbacks`` and the stop stamp LAST, so
    that what other callbacks do at both ends of a pass lies inside the
    pass: JAX hangs one there when it is imported (``_xla_gc_callback``:
    ``collect_garbage()`` at the start and the stop of every pass of every
    generation), and that is the collector's cost to the loop too. Whatever
    pair an earlier import of this module left is taken out first; the
    other entries keep their places."""
    global _gc_counters
    _gc_counters = [get_registry().counter(name)     # readable at 0
                    for name in ("Host/gc_s", "Host/gc_passes_gen2")]
    gc.callbacks[:] = [cb for cb in gc.callbacks
                       if not getattr(cb, "of_the_seam", False)]
    gc.callbacks.insert(0, _gc_starts)
    gc.callbacks.append(_gc_stops)


class Iteration:
    """One engine's open iteration: what ``step()`` counts while it runs,
    and, closed, one row of the ring. An engine keeps one and opens it
    again every iteration, so nothing is allocated but the row's tuple."""

    __slots__ = ("step", "t0", "t1", "wait_s", "chunks", "finals", "seats",
                 "stepped", "ahead", "read_step", "read_first", "slots",
                 "_cpu0", "_cpu1", "_gc_s", "_gc_weighted", "_built",
                 "_emitted")

    def __init__(self):
        self.open(0, 0, 0)

    def open(self, step: int, built: int, emitted: int) -> None:
        """Right behind the ``srv.step`` span's first stamp. ``built``: the
        engine's ``compiles``; ``emitted``: its count of tokens booked."""
        self.t0 = _LIFECYCLE.clock()
        self.step = step
        self.wait_s = 0.0
        self.chunks = self.finals = self.seats = self.stepped = self.ahead \
            = self.read_step = self.read_first = self.slots = 0
        self._built, self._emitted = built + _traces, emitted
        self._gc_s, self._gc_weighted = _gc_s, _gc_weighted
        self._cpu0 = _thread_time()

    def wait(self, fetch, on):
        """``fetch(on)``, a blocking wait on the device
        (``jax.device_get``, ``jax.block_until_ready``), timed into
        ``wait_s``."""
        t = _LIFECYCLE.clock()
        out = fetch(on)
        self.wait_s += _LIFECYCLE.clock() - t
        return out

    def close(self) -> None:
        """Right before the ``srv.step`` span's last stamp."""
        self._cpu1 = _thread_time()
        self.t1 = _LIFECYCLE.clock()

    def _row(self, built: int, running: int, queue: int,
             emitted: int) -> tuple:
        passes = _gc_weighted - self._gc_weighted
        return (self.step, self.t0, self.t1, self._cpu1 - self._cpu0,
                self.wait_s, _gc_s - self._gc_s,
                -1 if not passes else 2 if passes >> 32
                else 1 if passes >> 16 else 0,
                min(built + _traces - self._built, 32767),
                self.chunks, self.finals, self.seats, self.stepped,
                self.ahead, self.read_step, self.read_first,
                self.slots if self.stepped else running, queue,
                emitted - self._emitted + self.read_first)

    def write(self, built: int, running: int, queue: int,
              emitted: int) -> None:
        """The closed iteration's row into the ring, outside the span: what
        the assignment costs is not the iteration's."""
        global _rows_written
        n = _rows_written
        _rows[n % ROWS] = self._row(built, running, queue, emitted)
        _rows_written = n + 1

    def cause(self, built: int) -> str:
        """Why the open iteration has been long so far, by :func:`explain`'s
        precedence against the ring's rows: for a note written inside it
        (the watchdog's). Stamps the row as of now; ``close`` does again."""
        self.close()
        row = np.array([self._row(built, 0, 0, self._emitted)], ROW)
        rows = np.concatenate([iterations(), row])
        return _causes(rows, [len(rows) - 1])[0]


def iterations(t0: Optional[float] = None,
               t1: Optional[float] = None) -> np.ndarray:
    """The ring's rows, oldest first, as a structured array (:data:`ROW`;
    a copy): those that began in ``t0 <= row.t0 <= t1`` where given, on
    :func:`now`'s clock, which is a benchmark window's too."""
    n = _rows_written
    rows = _rows[:n].copy() if n <= ROWS \
        else np.roll(_rows, -(n % ROWS))
    if t0 is not None:
        rows = rows[rows["t0"] >= t0]
    if t1 is not None:
        rows = rows[rows["t0"] <= t1]
    return rows


def _causes(rows: np.ndarray, which) -> list:
    """One of :data:`CAUSES` for each row of ``rows`` at an index in
    ``which``, each decided by the row alone against the rows' medians
    (:func:`explain` has the precedence)."""
    wall = rows["t1"] - rows["t0"]
    own = rows["cpu_s"] - rows["gc_s"]
    median, median_own, median_wait = (
        float(np.median(v)) for v in (wall, own, rows["wait_s"]))
    # the thread clock's grain as the rows show it, the smallest step
    # between two readings: nanoseconds as a rule, 10 ms where the kernel
    # charges CPU time by the tick (the chip machine's host, PERF.md PR 53);
    # a reading says no more than that
    steps = np.diff(np.unique(np.round(rows["cpu_s"], 9)))
    grain = float(steps.min()) if len(steps) else 0.0
    fed = rows["chunks"] + rows["finals"] > 0
    fed[1:] |= fed[:-1].copy()
    # what a wait behind a chunk comes to, nineteen times in twenty
    usual = float(np.percentile(rows["wait_s"][fed], 95)) if fed.any() \
        else 0.0
    out = []
    for i in which:
        half, r = (wall[i] - median) / 2, rows[i]
        out.append(
            "compile" if r["compiles"] > 0
            else "gc" if r["gc_s"] >= half
            else "prefill" if fed[i] and half <= r["wait_s"] < usual + half
            else "on_cpu" if own[i] - median_own - grain >= half
            else "device_wait" if r["wait_s"] - median_wait >= half
            else "off_cpu")
    return out


def explain(rows: np.ndarray, over: float = 2.0) -> dict:
    """Every row longer than ``over`` x the rows' median in exactly one
    cause, whole. In this order, each by the row alone; the excess is the
    row's wall less the median:

    1. ``compile``      ``compiles`` > 0;
    2. ``gc``           ``gc_s`` >= half the excess;
    3. ``prefill``      the wait was for more than a step: a chunk went out
                        in this row or the one before it (with a step in
                        flight the chunk behind it is read an iteration
                        later) and ``wait_s`` >= half the excess, but not
                        half the excess over what nineteen in twenty of the
                        waits behind a chunk come to (a freeze inside such a
                        wait is not the chunk's). Sound;
    4. ``on_cpu``       ``cpu_s - gc_s`` over its own median by >= half
                        the excess and the thread clock's grain: the
                        loop's own Python;
    5. ``device_wait``  ``wait_s`` over its median by >= half the excess
                        with nothing sound in front to wait for: the
                        device or the runtime stood, or the thread inside
                        the wait;
    6. ``off_cpu``      the rest: outside every wait, no CPU, no pass: the
                        thread was not running.

    ``program_ms`` = compile + gc + on_cpu (what a change can take away),
    ``machine_ms`` = device_wait + off_cpu; with ``prefill`` they add up
    to ``long_ms``. ``longest``: the five longest of them, every field."""
    out = {"rows": len(rows), "over": over, "median_ms": 0.0, "long": 0,
           "long_ms": 0.0, "program_ms": 0.0, "machine_ms": 0.0,
           "causes": {c: {"ms": 0.0, "count": 0} for c in CAUSES},
           "longest": []}
    if not len(rows):
        return out
    wall = rows["t1"] - rows["t0"]
    median = float(np.median(wall))
    long = np.nonzero(wall > over * median)[0]
    why = _causes(rows, long)
    for i, cause in zip(long, why):
        out["causes"][cause]["ms"] += 1e3 * float(wall[i])
        out["causes"][cause]["count"] += 1
    out["median_ms"], out["long"] = 1e3 * median, len(long)
    for key, names in (("long_ms", CAUSES), ("program_ms", PROGRAM),
                       ("machine_ms", MACHINE)):
        out[key] = sum(out["causes"][c]["ms"] for c in names)
    for k in np.argsort(-wall[long])[:5]:
        i = long[k]
        out["longest"].append({
            **{name: rows[name][i].item() for name in ROW.names},
            "ms": 1e3 * float(wall[i]), "cause": why[k]})
    return out


def long_iterations() -> dict:
    """:func:`explain` of the ring as it stands: a flight recorder's
    ``long_iterations`` (a watchdog dump then says which cause fired it)."""
    return explain(iterations())


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
_watch_gc()
