"""From a profiler trace, the program's stamps and the generator's own clock
to per-layer metrics. The yardstick: no later PR may change this file.

``load_trace`` reads the ``.xplane.pb`` the JAX profiler wrote (with
``jax.profiler.ProfileData``, nothing else) into plain tuples. The generic
reducers below are named in ``benchmark/layer_metrics/<metric>.json``; a
reducer that finds nothing to read returns ``None`` and the harness leaves
the metric out of the line. A reducer of a new kind is a new file
``benchmark/reducers/<name>.py`` with a ``reduce(facts, **args)``.

Interval arithmetic (``merge``, ``subtract``, ``clip``) is copied from
``deepspeed_tpu/observability/commscope.py``; see PERF.md, Open questions.

What a v5e trace looks like (looked at by hand, PR 24, jax 0.9.0): one
plane ``/device:TPU:<n>`` per chip. Its line ``XLA Modules`` has one event
per program execution, named ``jit_<fn>(<fingerprint>)``. Its line ``XLA
Ops`` has one event per HLO instruction executed, named by the instruction's
whole text (``%fusion.379 = bf16[16,1024,1280]{...} fusion(...)``), control
flow (``while``, ``conditional``, ``call``) as parents around their bodies; a
Pallas kernel is a ``custom-call`` whose instruction name is the kernel's
``name=`` plus a number (``%flash_attention_fwd.14``). ``Async XLA Ops`` has
one event per asynchronous pair, from its ``-start`` to its ``-done``
(copies, and across chips the collectives). Host threads are lines of the
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans are events of the
``python3`` line there, on the same clock as the device planes: the
benchmark's own (``bench.<name>``) and, since PR 25, the program's
(``ds.<name>``, ``observability/spans.py``), nested inside them.
"""

from __future__ import annotations

import glob
import importlib
import os
import re
import statistics
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"^%(\S+) = (\(?[a-z0-9]+\[[^\]]*\])?")
HOST_PLANE = "/host:CPU"
# what the host spans of the benchmark's own files start with, and what the
# program's own start with; both are kept, so that an idle gap is named by the
# innermost of them (``ds.srv.admit`` inside ``bench.engine_step``)
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "ds."
TRACED_WINDOW = SPAN_PREFIX + "traced_window"
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.\d+)?$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?(\.\d+)?$")


# ------------------------------------------------------------ intervals
def merge(iv: Iterable[tuple]) -> list:
    """Sorted union of (t0, t1) intervals; empty and inverted ones dropped."""
    out: list = []
    for a, b in sorted((float(a), float(b)) for a, b in iv if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(iv: Iterable[tuple]) -> float:
    return sum(b - a for a, b in iv)


def subtract(a: Iterable[tuple], b: Iterable[tuple]) -> list:
    """``a - b``: the parts of ``a`` that no interval of ``b`` covers."""
    a, b = merge(a), merge(b)
    out: list = []
    j = 0
    for a0, a1 in a:
        cur = a0
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < a1:
            b0, b1 = b[k]
            if b0 > cur:
                out.append((cur, b0))
            cur = max(cur, b1)
            if cur >= a1:
                break
            k += 1
        if cur < a1:
            out.append((cur, a1))
    return out


def clip(iv: Iterable[tuple], t0: float, t1: float) -> list:
    return [(max(a, t0), min(b, t1)) for a, b in iv
            if min(b, t1) > max(a, t0)]


def gaps(iv: Iterable[tuple], t0: float, t1: float) -> list:
    """The idle intervals of [t0, t1]: what ``iv`` leaves uncovered."""
    return subtract([(t0, t1)], iv)


# ---------------------------------------------------------------- trace
class Trace:
    """Plain data. Times are seconds on the profiler's clock.

    ``ops[device]``, ``async_ops[device]``, ``modules[device]``: lists of
    (name, t0, t1), sorted by t0; an op's name is its instruction's name
    (``fusion.379``) and ``shapes[name]`` its first result's shape.
    ``spans``: the benchmark's and the program's host annotations,
    (name, t0, t1).
    ``window``: (t0, t1) of the traced window."""

    def __init__(self, ops: dict, modules: dict, spans: list,
                 window: Optional[tuple] = None,
                 async_ops: Optional[dict] = None,
                 shapes: Optional[dict] = None):
        def by_start(d):
            return {k: sorted(v, key=lambda e: e[1]) for k, v in d.items()}
        self.ops = by_start(ops)
        self.async_ops = by_start(async_ops or {})
        self.modules = by_start(modules)
        self.shapes = shapes or {}
        self.spans = sorted(spans, key=lambda e: e[1])
        if window is None:
            win = [s for s in self.spans if s[0] == TRACED_WINDOW]
            every = [e for v in self.ops.values() for e in v]
            if win:
                window = (win[0][1], win[0][2])
            elif every:
                window = (min(e[1] for e in every), max(e[2] for e in every))
            else:
                window = (0.0, 0.0)
        self.window = window

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def leaf_ops(self, device: str) -> list:
        """Ops that are not control flow: the ones that occupy the device."""
        return [e for e in self.ops[device] if not CONTROL_FLOW.match(e[0])]

    def busy(self, device: str) -> list:
        """Merged intervals, inside the window, in which an op ran."""
        return merge(clip(((a, b) for _, a, b in self.leaf_ops(device)),
                          *self.window))

    def to_json(self, t0: float, t1: float) -> dict:
        """The events that overlap [t0, t1), for a recorded cut."""
        def cut(evs):
            return [[n, a, b] for n, a, b in evs if a < t1 and b > t0]
        ops = {d: cut(v) for d, v in self.ops.items()}
        seen = {e[0] for v in ops.values() for e in v}
        return {"ops": ops,
                "async_ops": {d: cut(v) for d, v in self.async_ops.items()},
                "modules": {d: cut(v) for d, v in self.modules.items()},
                "shapes": {k: v for k, v in self.shapes.items() if k in seen},
                "spans": cut(self.spans), "window": [t0, t1]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def tup(evs):
            return [(n, a, b) for n, a, b in evs]
        return cls({k: tup(v) for k, v in d["ops"].items()},
                   {k: tup(v) for k, v in d["modules"].items()},
                   tup(d["spans"]), tuple(d["window"]),
                   {k: tup(v) for k, v in d.get("async_ops", {}).items()},
                   d.get("shapes", {}))


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_trace(trace_dir: str) -> Optional[Trace]:
    """The newest capture under ``trace_dir``, or None if there is none or
    it holds no device plane (a CPU capture)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    ops: dict = {}
    async_ops: dict = {}
    modules: dict = {}
    shapes: dict = {}
    spans: list = []
    names: dict = {}        # instruction text -> its name, parsed once

    def instruction(text: str) -> str:
        name = names.get(text)
        if name is None:
            m = INSTRUCTION.match(text)
            name = names[text] = m.group(1) if m else text
            if m and m.group(2):
                shapes[name] = m.group(2)
        return name

    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                dest = {OPS_LINE: ops, ASYNC_LINE: async_ops,
                        MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dest = dest.setdefault(plane.name, [])
                parse = (lambda t: t) if line.name == MODULES_LINE \
                    else instruction
                for e in line.events:
                    a = e.start_ns * 1e-9
                    dest.append((parse(e.name), a, a + e.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        a = e.start_ns * 1e-9
                        spans.append((e.name, a, a + e.duration_ns * 1e-9))
    if not ops:
        return None
    return Trace(ops, modules, spans, None, async_ops, shapes)


def describe_xplane(trace_dir: str, per_line: int = 12) -> list:
    """Planes, lines and the commonest event names of a capture: what to look
    at by hand before trusting a pattern."""
    from collections import Counter

    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    out = []
    if path is None:
        return out
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names: Counter = Counter()
            dur: Counter = Counter()
            first = None
            n = 0
            for e in line.events:
                n += 1
                names[e.name] += 1
                dur[e.name] += e.duration_ns
                if first is None:
                    first = {"name": e.name, "start_ns": e.start_ns,
                             "duration_ns": e.duration_ns,
                             "stats": [[k, str(v)[:200]] for k, v in e.stats]}
            out.append({"plane": plane.name, "line": line.name, "events": n,
                        "first": first,
                        "top": [[k, names[k], dur[k]]
                                for k, _ in dur.most_common(per_line)]})
    return out


# ------------------------------------------------------------- breakdown
def self_times(events: list) -> dict:
    """Per op name, seconds in which it and none of its children ran, for
    one device's properly nested events."""
    out: dict = {}
    stack: list = []          # [name, end, start, seconds of children]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, start, kids = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - kids
            if stack:
                stack[-1][3] += end - start

    for name, a, b in events:
        close(a)
        stack.append([name, b, a, 0.0])
    close(float("inf"))
    return out


def base_name(name: str) -> str:
    """``flash_attention_fwd.14`` -> ``flash_attention_fwd``: the compiler
    numbers the instances of one kernel or op kind."""
    return re.sub(r"(\.\d+)+$", "", name) or name


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (self time, summed over the
    window and averaged over devices) and the longest idle gaps of the first
    device, each named by the innermost span, the benchmark's or the
    program's, that the host was in meanwhile."""
    t0, t1 = trace.window
    per: dict = {}
    for d in trace.devices:
        inside = [e for e in trace.ops[d] if e[2] > t0 and e[1] < t1]
        for name, s in self_times(inside).items():
            if not CONTROL_FLOW.match(name):
                # as the trace prints it, with the result's shape: the
                # number changes with a recompile, the shape tells what it is
                key = f"{name} {trace.shapes.get(name, '')}".strip()
                per[key] = per.get(key, 0.0) + s / len(trace.devices)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle: dict = {}
    if trace.devices:
        idle = idle_by_host_span(
            gaps(trace.busy(trace.devices[0]), t0, t1), trace.spans)
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:top]]}


def innermost_segments(spans: list) -> list:
    """Disjoint (t0, t1, name) pieces of one thread's nested spans: at each
    time, the innermost span that is open."""
    out: list = []
    stack: list = []          # [name, end]
    t = 0.0

    def emit(upto: float) -> None:
        if stack and upto > t:
            out.append((t, upto, stack[-1][0]))

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            t = max(t, stack.pop()[1])
        emit(a)
        stack.append([name, b])
        t = a
    while stack:
        emit(stack[-1][1])
        t = max(t, stack.pop()[1])
    return out


def idle_by_host_span(idle: list, spans: list) -> dict:
    """Seconds of the device's idle intervals by what the host was doing:
    each part of a gap goes to the innermost span open then."""
    segs = innermost_segments([s for s in spans if s[0] != TRACED_WINDOW])
    out: dict = {}
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            part = min(b, segs[k][1]) - max(a, segs[k][0])
            if part > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + part
                covered += part
            k += 1
        if b - a - covered > 1e-12:
            out["host:outside-any-span"] = out.get(
                "host:outside-any-span", 0.0) + (b - a - covered)
    return out


# ------------------------------------------------------- a window's stalls
# an iteration longer than this many of the run's median iterations is a
# stall: the longest sound ones (a chunk and a final program before the
# step) are 1.2x, the chip machine's shortest stalls 2.1x. The one place the
# threshold is written: the reducers and the kinds' notes all come here
STALL_OVER = 2.0


def stalls(durations: list) -> tuple:
    """Of the iterations longer than ``STALL_OVER`` times the run's median
    iteration: (the seconds spent in them, the seconds by which they
    outlasted a median iteration, how many). Time the loop stood still,
    whoever's fault."""
    if not durations:
        return 0.0, 0.0, 0
    median = statistics.median(durations)
    long = [d for d in durations if d > STALL_OVER * median]
    return float(sum(long)), float(sum(long)) - median * len(long), len(long)


# -------------------------------------------------------------- reducers
def _trace(facts) -> Optional[Trace]:
    """The run's capture if it has a device timeline, else None."""
    tr = facts.get("trace")
    return tr if tr is not None and tr.devices else None


def _matching(events: list, pattern: str) -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def _stat(values: list, statistic: str) -> Optional[float]:
    if not values:
        return None
    if statistic == "median":
        return statistics.median(values)
    if statistic == "mean":
        return statistics.fmean(values)
    if statistic == "sum":
        return float(sum(values))
    if statistic.startswith("p"):
        return percentile(values, float(statistic[1:]))
    raise ValueError(f"unknown statistic {statistic!r}")


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or under it."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, -(-len(s) * q // 100) - 1))
    return float(s[int(k)])


def program_time(facts, *, program: str, measure: str = "duration",
                 statistic: str = "median", scale: float = 1e3):
    """Device time of the executions of the programs whose module name
    matches ``program``, in ms: their ``duration``, or the ``busy`` time of
    the ops inside them."""
    tr = _trace(facts)
    if tr is None:
        return None
    d = tr.devices[0]
    t0, t1 = tr.window
    runs = [e for e in _matching(tr.modules.get(d, []), program)
            if e[1] >= t0 and e[2] <= t1]
    if measure == "busy":
        busy = tr.busy(d)
        vals = [total(clip(busy, a, b)) for _, a, b in runs]
    else:
        vals = [b - a for _, a, b in runs]
    v = _stat(vals, statistic)
    return None if v is None else v * scale


def gap_after(facts, *, program: str, statistic: str = "median",
              scale: float = 1e3):
    """Device-idle time between the end of each execution of ``program`` and
    the start of the next program of any name, in ms."""
    tr = _trace(facts)
    if tr is None:
        return None
    d = tr.devices[0]
    t0, t1 = tr.window
    mods = [e for e in tr.modules.get(d, []) if e[1] >= t0 and e[2] <= t1]
    rx = re.compile(program)
    vals = [max(0.0, nxt[1] - cur[2])
            for cur, nxt in zip(mods, mods[1:]) if rx.search(cur[0])]
    v = _stat(vals, statistic)
    return None if v is None else v * scale


def idle_share(facts):
    """1 - busy/window in %, averaged over the devices used."""
    tr = _trace(facts)
    if tr is None:
        return None
    t0, t1 = tr.window
    if t1 <= t0:
        return None
    busy = statistics.fmean(total(tr.busy(d)) for d in tr.devices)
    return 100.0 * (1.0 - busy / (t1 - t0))


def collective_exposed(facts):
    """Collective time during which no compute op runs on the same device,
    over the window, in %, averaged over devices."""
    tr = _trace(facts)
    if tr is None:
        return None
    t0, t1 = tr.window
    if t1 <= t0:
        return None
    shares = []
    for d in tr.devices:
        leaf = tr.leaf_ops(d)
        # a synchronous collective is an op; an asynchronous one is in
        # flight from its -start to its -done, which the async line spans
        coll = clip([(a, b) for n, a, b in leaf if COLLECTIVE.match(n)]
                    + [(a, b) for n, a, b in tr.async_ops.get(d, [])
                       if COLLECTIVE.match(n)], t0, t1)
        comp = clip(((a, b) for n, a, b in leaf if not COLLECTIVE.match(n)),
                    t0, t1)
        shares.append(total(subtract(coll, comp)) / (t1 - t0))
    return 100.0 * statistics.fmean(shares)


def kernel_roofline(facts, *, kernel: str):
    """Least time the chip could take for the traced calls of the kernels
    that ``benchmark/kernels/<kernel>.py`` describes, over the device time
    they took, in %. The least time of a call is the larger of FLOPs/peak
    and bytes/peak; ``facts['notes']`` gets which of the two bound it."""
    tr = _trace(facts)
    if tr is None:
        return None
    calls = importlib.import_module(
        f"benchmark.kernels.{kernel}").calls(facts)
    peaks = facts["peaks"]
    t0, t1 = tr.window
    least = took = 0.0
    for name, (flops, nbytes) in calls.items():
        by_flops = flops / peaks["bf16_flops_per_s"]
        by_bytes = nbytes / peaks["hbm_bytes_per_s"]
        for d in tr.devices:
            evs = [e for e in tr.ops[d]
                   if base_name(e[0]) == name and e[1] >= t0 and e[2] <= t1]
            least += len(evs) * max(by_flops, by_bytes)
            took += sum(b - a for _, a, b in evs)
        facts.setdefault("notes", []).append(
            f"{name}: bound by {'compute' if by_flops >= by_bytes else 'memory'}"
            f" ({flops:.4g} FLOP, {nbytes:.4g} B a call)")
    return 100.0 * least / took if took > 0 else None


def request_stat(facts, *, field: str, minus: Optional[str] = None,
                 statistic: str = "p95", scale: float = 1e3):
    """A statistic over the requests due in the window of one stamp, or of
    the difference of two, in ms."""
    vals = []
    for r in facts.get("requests", []):
        a, b = r.get(field), r.get(minus) if minus else 0.0
        if a is not None and b is not None:
            vals.append(a - b)
    v = _stat(vals, statistic)
    return None if v is None else v * scale


def model_flops_utilisation(facts, *, rate: str):
    """The end-to-end rate ``rate`` (tokens/s over all chips) times the
    family's training FLOPs per token, over chips x peak, in %. An
    end-to-end utilisation: it says nothing of any kernel or of idle time."""
    tps = facts.get("end_to_end", {}).get(rate)
    if tps is None:
        return None
    fam = importlib.import_module(f"benchmark.models.{facts['family']}")
    per_token = fam.train_flops_per_token(facts["model"], facts["seq_len"])
    return 100.0 * tps * per_token / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def window_rate(facts, *, less_stalls: bool = False):
    """Output tokens per second of the measured window from the kind's own
    record of it: all its tokens over all its time (what the end-to-end
    ``serve_tokens_per_s`` is), or, with ``less_stalls``, over its time less
    what its stalls (``stalls``) took beyond a median iteration each."""
    win = facts.get("window")
    if not win or win["t1"] <= win["t0"]:
        return None
    seconds = win["t1"] - win["t0"]
    if less_stalls:
        seconds -= stalls(win["durations"])[1]
    return sum(win["counts"]) / seconds


def stall_time(facts, *, scale: float = 1e3):
    """Time spent in the window's stalls (``stalls``), in ms; 0 where there
    was none."""
    win = facts.get("window")
    if not win or not win["durations"]:
        return None
    return stalls(win["durations"])[0] * scale


def token_gap_stat(facts, *, statistic: str = "p95", scale: float = 1e3):
    """A statistic over the window's gaps between successive output tokens
    of one request, as the serving kinds booked them, in ms."""
    v = _stat(facts.get("token_gaps") or [], statistic)
    return None if v is None else v * scale


def run_reducer(name: str, facts: dict, args: dict):
    """A generic reducer of this file, else ``benchmark/reducers/<name>.py``."""
    fn = globals().get(name) if name in GENERIC else None
    if fn is None:
        fn = importlib.import_module(f"benchmark.reducers.{name}").reduce
    return fn(facts, **args)


GENERIC = ("program_time", "kernel_roofline", "gap_after", "idle_share",
           "collective_exposed", "request_stat", "model_flops_utilisation",
           "window_rate", "stall_time", "token_gap_stat")
