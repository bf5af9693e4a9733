"""Goodput/badput attribution: where did the wall time actually go?

Every perf number shipped so far (tokens/s, MBU, MFU, step time) rates
the work that *ran*; none of them says what fraction of the process's
wall time was productive at all. That decomposition — DeepSpeed's
monitor + flops-profiler split, T3's insistence that time be
*attributed* before overlap work can be trusted — is what the fleet
router needs to tell "slow engine" from "starved engine".

:class:`GoodputLedger` is an interval accountant on the owner's
injectable clock. Engines feed it the windows they already measure
(the serving iteration, the decode window the watchdog times, the train
step dispatch) and it attributes **every second between the first and
the latest observation** to exactly one bucket:

- ``productive`` — decode steps with >= 1 live slot, prefill chunk
  dispatch, train step dispatch;
- ``compile`` — iterations that built a new XLA program (detected via
  the engine's compile counter, never a guess);
- ``queue_empty`` — idle: no request anywhere (serving), inter-step
  host/data time (training);
- ``stall`` — the portion of a decode step beyond the watchdog budget;
- ``checkpoint`` — checkpoint commit windows;
- ``drain`` — idle time while intake is closed for a drain;
- ``preempt`` — the SIGTERM grace window (PreemptionGuard handler);
- ``other`` — host scheduling overhead inside a working iteration.

The invariant — pinned by the fake-clock tests of
``tests/unit/test_telemetry.py`` — is ``productive + sum(badput) ==
wall`` to within float tolerance: attribution that doesn't sum to wall
time is attribution that silently dropped a failure mode.

Cost discipline matches the rest of the stack: disabled engines hold
``goodput = None`` (one ``is not None`` per iteration, zero clock
reads, zero programs, zero syncs); enabled, the serving ledger adds two
host clock reads per iteration and pure-Python float math.
"""

from __future__ import annotations

import math
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

# Badput buckets, in the order reports print them. "productive" is not
# in this tuple: it is the goodput side of the ledger.
BADPUT_BUCKETS = ("compile", "queue_empty", "stall", "checkpoint",
                  "drain", "preempt", "other")
PRODUCTIVE = "productive"


class GoodputLedger:
    """Wall-time accountant: every interval lands in exactly one bucket.

    ``account(bucket, t0, t1)`` is the primitive: it first charges any
    gap since the previous attributed instant to the ledger's current
    *idle bucket* (``queue_empty`` by default; ``drain`` while the owner
    reports draining), then charges ``[t0, t1]`` to ``bucket``. Engines
    call the typed helpers (:meth:`on_serving_iteration`,
    :meth:`on_train_step`, :meth:`window`) which encode the attribution
    policy; the primitive keeps the sum-to-wall invariant true by
    construction — there is no instant between ``start_t`` and
    ``last_t`` that belongs to no bucket.

    Thread-safe (the telemetry server snapshots from its own thread);
    ``clock`` is the owner's injectable clock so fake-clock tests drive
    attribution deterministically.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 registry=None, prefix: str = "Serve"):
        self.clock = clock
        self.registry = registry
        self.prefix = prefix
        self._lock = threading.RLock()
        self._buckets: dict[str, float] = {PRODUCTIVE: 0.0}
        for b in BADPUT_BUCKETS:
            self._buckets[b] = 0.0
        self._start: Optional[float] = None   # first attributed instant
        self._last: Optional[float] = None    # latest attributed instant
        self._idle_bucket = "queue_empty"

    # ------------------------------------------------------------ primitive
    def account(self, bucket: str, t0: float, t1: float) -> None:
        """Charge ``[t0, t1]`` to ``bucket``; the gap since the previous
        attributed instant goes to the current idle bucket. Out-of-order
        or zero-length windows degrade to no-ops rather than corrupting
        the wall sum."""
        if bucket not in self._buckets:
            raise ValueError(f"unknown goodput bucket {bucket!r} "
                             f"(have {sorted(self._buckets)})")
        t0, t1 = float(t0), float(t1)
        if t1 < t0:
            return
        with self._lock:
            if self._start is None:
                self._start = t0
                self._last = t0
            if t0 > self._last:
                self._buckets[self._idle_bucket] += t0 - self._last
                self._last = t0
            lo = max(t0, self._last)
            if t1 > lo:
                self._buckets[bucket] += t1 - lo
                self._last = t1

    def set_idle_reason(self, draining: bool) -> None:
        """What the NEXT inter-observation gap means: ``drain`` while
        intake is closed, ``queue_empty`` otherwise."""
        with self._lock:
            self._idle_bucket = "drain" if draining else "queue_empty"

    # --------------------------------------------------------- typed feeds
    def on_serving_iteration(self, t0: float, t1: float, *,
                             decode_s: float = 0.0, ran_decode: bool = False,
                             ran_chunk: bool = False, compiled: bool = False,
                             stall_excess_s: float = 0.0,
                             draining: bool = False,
                             idle: bool = False) -> None:
        """Attribute one ``ServingEngine.step()`` window ``[t0, t1]``.

        Policy: an iteration that built a new XLA program is a COMPILE
        window end to end — the build may have happened inside the
        decode dispatch itself (the cold engine's first decode step),
        so splitting it would book compile time as productive or, with
        a watchdog set, as a phantom stall. Otherwise the decode window
        splits into productive time (up to the watchdog budget) and
        ``stall`` excess; the rest of the iteration is host-overhead
        ``other`` when work ran, and idle (``drain`` / ``queue_empty``)
        when the engine had nothing to do."""
        span = max(0.0, float(t1) - float(t0))
        decode_s = min(max(0.0, float(decode_s)), span)
        stall = min(max(0.0, float(stall_excess_s)), decode_s)
        parts: list[tuple[str, float]] = []
        if compiled:
            # the whole window is compile badput: decode_s/stall split
            # below would misattribute the program build that ran
            # INSIDE the decode dispatch (the watchdog fires on it too)
            parts.append(("compile", span))
            decode_s = stall = 0.0
        rest = span - decode_s if not compiled else 0.0
        if ran_decode and decode_s > 0:
            parts.append((PRODUCTIVE, decode_s - stall))
            if stall > 0:
                parts.append(("stall", stall))
        if rest > 0:
            if ran_chunk or ran_decode:
                # host scheduling overhead around real work: close to
                # zero on a healthy engine, and worth seeing when not
                parts.append(("other", rest))
            elif draining:
                parts.append(("drain", rest))
            elif idle:
                parts.append(("queue_empty", rest))
            else:
                parts.append(("other", rest))
        cur = float(t0)
        for bucket, dur in parts:
            if dur > 0:
                self.account(bucket, cur, cur + dur)
                cur += dur
        if cur < t1:   # float dust / empty parts: close the window
            self.account("other" if not (draining or idle) else
                         ("drain" if draining else "queue_empty"), cur, t1)
        self.set_idle_reason(draining)

    def on_train_step(self, t0: float, t1: float,
                      compiled: bool = False) -> None:
        """Attribute one ``train_batch`` window: ``compile`` when this
        call built the step program (its wall time is dominated by the
        XLA compile), else ``productive``. The inter-step gap — data
        loading, host optimizer work outside the window — lands in
        ``queue_empty`` via the gap rule."""
        self.account("compile" if compiled else PRODUCTIVE, t0, t1)

    @contextmanager
    def window(self, bucket: str):
        """Bracket a code region into one bucket (checkpoint commits,
        the preemption grace window): ``with ledger.window("checkpoint"):
        ...``."""
        t0 = self.clock()
        try:
            yield
        finally:
            self.account(bucket, t0, self.clock())

    # -------------------------------------------------------------- readout
    def snapshot(self) -> dict:
        """Machine-readable decomposition; ``unattributed_s`` is the float
        dust between ``wall_s`` and the bucket sum (0 by construction, a
        bug if ever material)."""
        with self._lock:
            wall = 0.0 if self._start is None else self._last - self._start
            buckets = dict(self._buckets)
        badput = {b: buckets[b] for b in BADPUT_BUCKETS}
        total = buckets[PRODUCTIVE] + sum(badput.values())
        return {
            "wall_s": wall,
            "productive_s": buckets[PRODUCTIVE],
            "badput_s": badput,
            "badput_total_s": sum(badput.values()),
            "goodput_frac": (buckets[PRODUCTIVE] / wall) if wall > 0
            else math.nan,
            "unattributed_s": wall - total,
        }

    def export(self, registry=None, prefix: Optional[str] = None) -> dict:
        """Write the decomposition as ``<prefix>/goodput_*`` gauges
        (``Serve/goodput_frac``, ``Serve/goodput_badput_stall_s``, ...)
        into ``registry`` (default: the ledger's own); returns the
        snapshot. Called from ``publish_metrics`` and before every
        ``/metrics`` render so scrapes always see current numbers."""
        reg = registry if registry is not None else self.registry
        snap = self.snapshot()
        if reg is None:
            return snap
        p = prefix if prefix is not None else self.prefix
        gauges = {
            f"{p}/goodput_wall_s": snap["wall_s"],
            f"{p}/goodput_productive_s": snap["productive_s"],
            f"{p}/goodput_badput_total_s": snap["badput_total_s"],
        }
        if not math.isnan(snap["goodput_frac"]):
            gauges[f"{p}/goodput_frac"] = snap["goodput_frac"]
        for b, v in snap["badput_s"].items():
            gauges[f"{p}/goodput_badput_{b}_s"] = v
        reg.set_gauges(gauges)
        return snap


def weighted_goodput_frac(pairs) -> "float | None":
    """Wall-weighted mean over ``(goodput_frac, wall_s)`` pairs — THE
    fleet goodput definition, shared by the in-process rollup below and
    the scrape aggregator (``fleet_scrape.py``) so the two surfaces
    cannot drift. A replica that has lived 10x longer carries 10x the
    weight (a freshly joined replica must not mask fleet-wide badput);
    None/NaN fractions drop out, and a zero/unknown wall falls back to
    weight 1.0 (the replica still counts, it just cannot dominate).
    None when no replica has a usable fraction."""
    wsum = fsum = 0.0
    for frac, wall in pairs:
        if frac is None or (isinstance(frac, float) and math.isnan(frac)):
            continue
        w = wall if wall and wall > 0 else 1.0
        wsum += w
        fsum += frac * w
    return (fsum / wsum) if wsum > 0 else None


def rollup_goodput(snaps: list) -> dict:
    """Fleet rollup over per-replica ledger snapshots — the SAME math
    the ``fleet_scrape`` aggregator applies to scraped gauges (both go
    through :func:`weighted_goodput_frac`), applied to in-process
    :meth:`GoodputLedger.snapshot` dicts: per-bucket seconds sum plus
    the wall-weighted fleet fraction."""
    out = {"replicas": len(snaps), "wall_s": 0.0, "productive_s": 0.0,
           "badput_s": {b: 0.0 for b in BADPUT_BUCKETS},
           "badput_total_s": 0.0, "goodput_frac": None}
    pairs = []
    for s in snaps:
        if not s:
            continue
        out["wall_s"] += s.get("wall_s", 0.0)
        out["productive_s"] += s.get("productive_s", 0.0)
        for b, v in (s.get("badput_s") or {}).items():
            out["badput_s"][b] = out["badput_s"].get(b, 0.0) + v
        out["badput_total_s"] += s.get("badput_total_s", 0.0)
        pairs.append((s.get("goodput_frac"), s.get("wall_s", 0.0)))
    out["goodput_frac"] = weighted_goodput_frac(pairs)
    return out
