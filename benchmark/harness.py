"""What every cell shares: the cell's files, the device check, the compile
cache, compile counting, the traced window and the result line.

A traffic kind is a module ``benchmark/kinds/<kind>.py`` with
``run(cell) -> Outcome``; nothing here knows a kind, a configuration, a mix
or a metric by name.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
import tempfile
from typing import Optional

from . import reduce as R
from .traffic import load_mix

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def say(msg: str) -> None:
    """Progress and reasons go to stderr: stdout's last line is the result."""
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One run of one cell: what the kind's runner is handed."""

    name: str
    chips: int
    config: dict            # benchmark/configs/<configuration>.json
    published: dict         # its sizes as run (rehearsal: the tiny ones)
    mix: dict               # benchmark/traffic/<mix>.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process: float        # perf_counter at process start
    devices: list = dataclasses.field(default_factory=list)
    watch: "Optional[CompileWatch]" = None
    trace_dir: Optional[str] = None

    @property
    def family(self):
        return importlib.import_module(
            f"benchmark.models.{self.config['family']}")

    @property
    def reference(self):
        return importlib.import_module(
            f"benchmark.reference.{self.config['family']}")

    def jax_seed(self) -> int:
        # jax.random.PRNGKey takes 32 bits; --seed may be a little more
        return self.seed % (2 ** 31 - 1)


@dataclasses.dataclass
class Outcome:
    """What a kind's runner hands back."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict                  # metric name -> value
    setup_s: float
    facts: dict                       # what the per-layer reducers read
    samples: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)


def load_cell(spec: dict, workload: str, seed: int, seconds: float,
              trace: bool, rehearse: bool, t_process: float) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    with open(os.path.join(ROOT, files[w["config"]])) as f:
        config = json.load(f)
    published = dict(config["config"])
    if rehearse:
        published.update(config.get("rehearsal", {}))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                published=published, mix=load_mix(w["traffic"], rehearse),
                seed=seed, seconds=seconds, trace=trace, rehearse=rehearse,
                t_process=t_process)


# ------------------------------------------------------------ the device
def place_compile_cache() -> str:
    """Before the first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX reads it itself; otherwise the cache sits at a fixed path inside the
    checkout (the path is part of the key). Every program is kept, however
    quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_devices(cell: Cell) -> dict:
    """The chips the cell asks for, or exit 2 with no result: there is no
    CPU fallback. A rehearsal takes whatever there is and says so."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": cell.chips}
    if cell.rehearse:
        info["count"] = min(cell.chips, len(devs))
        if cell.chips > len(devs):
            raise SystemExit(f"benchmark: rehearsal of a {cell.chips}-chip "
                             f"cell needs {cell.chips} devices "
                             "(XLA_FLAGS=--xla_force_host_platform_device_"
                             f"count={cell.chips})")
    elif info["platform"] != "tpu" or len(devs) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
              f"found {len(devs)} {info['platform']} device(s). "
              "Nothing was measured.", file=sys.stderr)
        raise SystemExit(2)
    cell.devices = devs[:cell.chips]
    return info


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table or kind.startswith("_"):
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         "benchmark/peaks.json; add them with their source")
    return table[kind]


class CompileWatch:
    """Counts backend compiles and persistent-cache hits and misses through
    ``jax.monitoring``: the program's own events, no wrapper around jit
    (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event.endswith("/backend_compile_duration"):
            self.compiles += 1
            self.compile_s += secs

    def mark(self) -> tuple:
        return (self.hits, self.misses, self.compiles)

    def since(self, mark: tuple) -> dict:
        return {"cache_hits": self.hits - mark[0],
                "cache_misses": self.misses - mark[1],
                "programs_built": self.compiles - mark[2]}


def span(name: str):
    """A host span of the benchmark's own, in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(R.SPAN_PREFIX + name)


class TraceTail:
    """Profiles the last ``trace_seconds`` of the window of a ``--trace 1``
    run: ``tick(elapsed)`` at every iteration starts the capture when its
    time has come, ``stop()`` after the window ends it. Starting is cheap;
    stopping writes the capture and would stall a serving loop, so it never
    happens inside the window. The Python tracer is off (it would slow the
    host loop it watches); ``TraceAnnotation`` spans are kept. The capture
    goes to a directory under TMPDIR that is read once and removed."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.start_at = max(0.0, cell.seconds - float(
            cell.mix.get("trace_seconds", cell.seconds))) \
            if cell.trace else float("inf")
        self.on = False
        self._span = None

    def tick(self, elapsed: float) -> None:
        if self.on or elapsed < self.start_at:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.cell.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.cell.trace_dir, profiler_options=opts)
        self._span = span("traced_window")
        self._span.__enter__()
        self.on = True
        self.start_at = float("inf")

    def stop(self) -> None:
        if self.on:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def settle_host() -> None:
    """Right before the window: collect what set-up left behind and move
    everything that survives (JAX's caches, the compiled programs' wrappers,
    the planned traffic) out of the collector's sight, as a long-running
    server does after start-up. A full collection over those objects in the
    middle of the window is a pause of the benchmark's making, not the
    system's. The collector stays on for what the window allocates."""
    import gc

    gc.collect()
    gc.freeze()


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


# ------------------------------------------------------------- the result
def metrics_for(spec: dict, group: str, cell: str) -> list:
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_metrics(spec: dict, cell: Cell, facts: dict) -> dict:
    """Each per-layer metric of this cell through the reducer its file
    names; a reducer with nothing to read leaves its metric out."""
    out = {}
    for m in metrics_for(spec, "per_layer", cell.name):
        reader = load_json("layer_metrics", f"{m['name']}.json")
        value = R.run_reducer(reader["reducer"], facts,
                              reader.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def keep_for_a_look(trace_dir: str, trace) -> None:
    """``BENCH_LOOK=<file>[:<seconds>]`` writes what a capture holds (planes,
    lines, the commonest events) and a cut of its first seconds as the
    reducers see it: how the trace was looked at by hand before any pattern
    was trusted, and how ``benchmark/tests/data`` was recorded. Unset, as in
    every measured run, nothing is written."""
    look = os.environ.get("BENCH_LOOK")
    if not look:
        return
    path, _, seconds = look.partition(":")
    cut = None
    if trace is not None:
        t0 = trace.window[0]
        cut = trace.to_json(t0, t0 + float(seconds or 0))
    with open(path, "w") as f:
        json.dump({"lines": R.describe_xplane(trace_dir), "cut": cut}, f)


def result_line(spec: dict, cell: Cell, device: dict, out: Outcome) -> dict:
    facts = out.facts
    facts.setdefault("end_to_end", out.end_to_end)
    facts.setdefault("notes", out.notes)
    facts.update(model=cell.published, family=cell.config["family"],
                 chips=cell.chips,
                 # a rehearsal only walks the arithmetic: v5e's row stands in
                 peaks=peaks_for("TPU v5 lite" if cell.rehearse
                                 else device["kind"]))
    device = dict(device, memory_peak_bytes=max(
        memory_peak_bytes(cell.devices), int(facts.get("program_bytes", 0))))
    line = {"correct": bool(out.correct) and not cell.rehearse,
            "attempted": out.attempted, "failed": out.failed}
    if cell.trace:
        trace = None
        if cell.trace_dir:
            trace = R.load_trace(cell.trace_dir)
            keep_for_a_look(cell.trace_dir, trace)
            shutil.rmtree(cell.trace_dir, ignore_errors=True)
        facts["trace"] = trace
        if trace is not None:
            t0, t1 = trace.window
            device["busy_s"] = sum(
                R.total(trace.busy(d)) for d in trace.devices) / len(
                    trace.devices)
            device["window_s"] = t1 - t0
            line["breakdown"] = R.breakdown(trace)
        elif not cell.rehearse:
            out.notes.append("the profiler's capture holds no device plane")
            line["correct"] = False
        line["metrics"] = per_layer_metrics(spec, cell, facts)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = dict(out.end_to_end, setup_s=out.setup_s)
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": units[m["name"]],
                        **({"samples": out.samples[m["name"]]}
                           if m["name"] in out.samples else {})}
            for m in metrics_for(spec, "end_to_end", cell.name)
            if values.get(m["name"]) is not None}
    if cell.rehearse:
        # a CPU run proves paths and counts; its times are not the device's
        line["rehearsal"] = {"passed": bool(out.correct),
                             "not_device_metrics": line.pop("metrics")}
        line["metrics"] = {}
    line["device"] = device
    line["notes"] = facts["notes"]
    return line


def main(t_process: float, argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once, on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is, to find "
                         "wrong paths; never reports a device metric")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds if args.seconds is not None \
        else float(spec["run_seconds"])
    cell = load_cell(spec, args.workload, args.seed, seconds,
                     bool(args.trace), args.rehearse, t_process)
    cache = place_compile_cache()
    device = require_devices(cell)
    say(f"{cell.name} seed {cell.seed} on {device}; compile cache {cache}")
    cell.watch = CompileWatch()
    runner = importlib.import_module(f"benchmark.kinds.{cell.mix['kind']}")
    out = runner.run(cell)
    w = cell.watch
    out.notes.append(f"in all {w.compiles} programs built or loaded "
                     f"({w.hits} cache hits, {w.misses} misses, "
                     f"{w.compile_s:.1f} s in the backend's compiler)")
    line = result_line(spec, cell, device, out)
    for note in line["notes"]:
        say(note)
    print(json.dumps(line), flush=True)
    return 0
