"""Per-layer metrics from the program's own record of every iteration.

``host.stall_ms`` times the loop from outside (the kind's clock around
``step()`` and its own booking) and can only say how long an iteration was.
Since PR 53 the program keeps one row for every ``ServingEngine.step()``,
always, spans on or off: wall, the thread's CPU seconds, the seconds inside
its blocking waits on the device, the collector's passes, compiles, and what
it dispatched (``deepspeed_tpu.observability.spans.iterations()``), and puts
every row over twice the rows' median into one of six causes
(``spans.explain``). It is read here as ``program_lifecycle.py`` reads
``lifecycle()``: through the process's memory, since ``facts`` holds no
handle on an engine. The rows' clock and the kinds' ``window.t0`` / ``t1``
are both ``time.perf_counter()``, so the rows of the WHOLE window are the
ones that began inside it, not those of the traced tail alone. A program
without the accessor (the parent of the PR that added it) has nothing to
read: ``None``.
"""

from __future__ import annotations

from ..reduce import STALL_OVER, _stat, stalls


def _explained(facts):
    try:
        from deepspeed_tpu.observability import spans
    except ImportError:
        return None
    read = getattr(spans, "iterations", None)
    win = facts.get("window")
    if read is None or not win:
        return None
    rows = read(win["t0"], win["t1"])
    if not len(rows):
        return None
    return rows, spans.explain(rows, over=STALL_OVER)


def reduce(facts, *, part: str, statistic=None):
    """Of the window's rows, by ``part``:

    ``inside``   ms in the rows longer than ``reduce.STALL_OVER`` x the
                 rows' median, whole (the rule of ``host.stall_ms`` on the
                 program's own record). A note gives the six causes, the
                 five longest rows with every field, and what
                 ``host.stall_ms`` counted from outside beside it;
    ``program``  of those, ``compile + gc + on_cpu``: what a change to the
                 program can take away;
    ``machine``  ``device_wait + off_cpu``: what no change moves (the rest of
                 ``inside`` is ``prefill``: a chunk in front of the read,
                 sound);
    ``slots``    the mean (``statistic``) of ``slots`` over the rows that
                 dispatched a step: the batch over the whole window."""
    read = _explained(facts)
    if read is None:
        return None
    rows, ex = read
    if part == "inside":
        facts.setdefault("notes", []).append(inside_note(facts, ex))
        return ex["long_ms"]
    if part in ("program", "machine"):
        return ex[part + "_ms"]
    if part == "slots":
        stepped = rows["slots"][rows["stepped"] == 1]
        return _stat([float(s) for s in stepped], statistic or "mean")
    raise ValueError(f"unknown part {part!r}")


def inside_note(facts, ex: dict) -> str:
    """``long iterations from inside: 1843.2 ms in 14 of 2610 rows over 2x
    the median 15.104 ms; by cause (ms, rows): compile 0.0 (0), gc ...;
    from outside host.stall_ms counts 1851.0 ms in 14 iterations (agree);
    the longest: step 5123 121.4 ms off_cpu cpu 0.8 wait 0.3 gc 0.0 ...``"""
    outside_s, _, outside_n = stalls(facts["window"]["durations"])
    gap = abs(ex["long_ms"] - 1e3 * outside_s)
    agree = "agree" if gap <= 0.05 * 1e3 * outside_s + 5.0 else \
        "DISAGREE by more than 5% + 5 ms: the record lost rows, or rows " \
        "stand next to the line"
    rows = "; ".join(
        f"step {r['step']} {r['ms']:.1f} ms {r['cause']} (cpu "
        f"{1e3 * r['cpu_s']:.1f}, wait {1e3 * r['wait_s']:.1f}, gc "
        f"{1e3 * r['gc_s']:.1f} gen {r['gc_gen']}, compiles "
        f"{r['compiles']}, chunks {r['chunks']} finals {r['finals']} seats "
        f"{r['seats']} stepped {r['stepped']} ahead {r['ahead']}, read "
        f"{r['read_step']}/{r['read_first']}, slots {r['slots']} queue "
        f"{r['queue']} tokens {r['tokens']})" for r in ex["longest"])
    return (
        f"long iterations from inside: {ex['long_ms']:.1f} ms in "
        f"{ex['long']} of {ex['rows']} rows over {ex['over']:g}x the median "
        f"{ex['median_ms']:.3f} ms; by cause (ms, rows): " + ", ".join(
            f"{c} {v['ms']:.1f} ({v['count']})"
            for c, v in ex["causes"].items())
        + f"; program {ex['program_ms']:.1f} ms, machine "
        f"{ex['machine_ms']:.1f} ms; from outside host.stall_ms counts "
        f"{1e3 * outside_s:.1f} ms in {outside_n} iterations ({agree}); "
        f"the longest: {rows or 'none'}")
