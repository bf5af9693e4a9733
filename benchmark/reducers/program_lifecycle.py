"""Per-layer metrics of set-up from the program's lifecycle ring.

``setup_s`` is timed from outside, by the kinds' own phases. What it is
made of inside the program (the package's import, each engine's build,
every program traced, lowered, and compiled or loaded by the backend, each
under its module's name, and every new signature of a built serving
program) the program records itself, always, into one bounded ring:
``deepspeed_tpu.observability.spans.lifecycle()``. It is read here as
``program_span.py`` reads ``captured()``: through the process's memory,
since ``facts`` holds no handle on an engine. Its stamps and the kinds'
``window.t0`` are both ``time.perf_counter()``, so where a kind hands the
window over, what happened after it opened is left out (a run with a
compile in its window is INVALID already). A program without the accessor
(the parent of the PR that added it) has nothing to read: ``None``.
"""

from __future__ import annotations

from ..reduce import merge, subtract, total

COMPILE, INIT, RETRACE = "compile", "init", "retrace"


def _lifecycle():
    try:
        from deepspeed_tpu.observability import spans
    except ImportError:
        return None
    read = getattr(spans, "lifecycle", None)
    return None if read is None else list(read())


def reduce(facts, *, part: str):
    """Seconds of set-up, by ``part``:

    ``import``       the ``init`` span of phase ``import``;
    ``engine_init``  the other ``init`` spans (their union) less the
                     ``compile`` spans inside them: the engines' own host
                     work. A note gives every one, whole and its own;
    ``trace_lower``  ``compile`` spans of the stages ``trace`` and
                     ``lower``: Python's share of a program, paid on every
                     start, which no cache saves. Notes give the five
                     programs that cost most over all three stages, and
                     every ``retrace`` by program with its ``why``;
    ``backend``      ``compile`` spans of the stage ``backend``. A note
                     splits them into compiled (a cache miss), loaded (a
                     hit) and those the cache said nothing of."""
    events = _lifecycle()
    if events is None:
        return None
    start = (facts.get("window") or {}).get("t0")
    before = [e for e in events
              if start is None
              or (e.t0 if e.t1 is None else e.t1) <= start]
    notes = facts.setdefault("notes", [])
    if len(before) < len(events) and part == "trace_lower":
        notes.append(f"{len(events) - len(before)} lifecycle events after "
                     "the window opened are left out of setup.*")
    compiles = [e for e in before if e.kind == COMPILE]
    inits = [e for e in before if e.kind == INIT]
    if part == "import":
        mine = [e for e in inits if e.meta.get("phase") == "import"]
        return sum(e.duration for e in mine) if mine else None
    if part == "engine_init":
        mine = [e for e in inits if e.meta.get("phase") != "import"]
        covered = merge((e.t0, e.t1) for e in compiles)
        notes.append("engines built (s whole, own): " + ", ".join(
            f"init.{e.meta.get('phase')} {e.duration:.3f} "
            f"{total(subtract([(e.t0, e.t1)], covered)):.3f}"
            for e in mine))
        return total(subtract(((e.t0, e.t1) for e in mine), covered))
    if part == "trace_lower":
        notes.append(dearest_note(compiles))
        notes.append(retrace_note([e for e in before if e.kind == RETRACE]))
        return sum(e.duration for e in compiles
                   if e.meta.get("stage") in ("trace", "lower"))
    if part == "backend":
        mine = [e for e in compiles if e.meta.get("stage") == "backend"]
        notes.append(backend_note(mine))
        return sum(e.duration for e in mine)
    raise ValueError(f"unknown part {part!r}")


def dearest_note(compiles: list, top: int = 5) -> str:
    """``the 5 dearest of 41 programs (s: trace + lower + backend, times
    built): jit__step_impl 12.300 = 1.200 + 0.800 + 10.300 (2), ...``: by
    the module's name, every signature of it together."""
    per: dict = {}
    for e in compiles:
        row = per.setdefault(e.meta.get("program", "?"),
                             {"trace": 0.0, "lower": 0.0, "backend": 0.0,
                              "built": 0})
        stage = e.meta.get("stage")
        if stage in row:
            row[stage] += e.duration
        row["built"] += stage == "backend"
    cost = sorted(per.items(), reverse=True, key=lambda kv: (
        kv[1]["trace"] + kv[1]["lower"] + kv[1]["backend"]))
    return (f"the {min(top, len(cost))} dearest of {len(cost)} programs (s: "
            "trace + lower + backend, times built): " + ", ".join(
                f"{name} {r['trace'] + r['lower'] + r['backend']:.3f} = "
                f"{r['trace']:.3f} + {r['lower']:.3f} + {r['backend']:.3f} "
                f"({r['built']})" for name, r in cost[:top]))


def retrace_note(retraces: list) -> str:
    """Every new signature of a built serving program, by the engine's key
    and the module, with what it cost; ``new`` adds up to the counter
    ``Serve/retraces`` (``prog.retraces``)."""
    if not retraces:
        return "retraces: none"
    n = sum(int(e.meta.get("new", 1)) for e in retraces)
    return f"retraces: {n} in all; " + "; ".join(
        f"{e.meta.get('program')} ({e.meta.get('module')}) signature "
        f"{e.meta.get('signatures')} at step {e.step}: {e.meta.get('why')}"
        for e in retraces)


def backend_note(backend: list) -> str:
    def said(hit):
        mine = [e for e in backend if e.meta.get("cache_hit") is hit]
        return len(mine), sum(e.duration for e in mine)

    read = sum(e.meta.get("retrieval_s", 0.0) for e in backend)
    (nc, sc), (nl, sl), (nn, sn) = said(False), said(True), said(None)
    return (f"backend: {len(backend)} programs; {nc} compiled {sc:.3f} s "
            f"(cache misses), {nl} loaded {sl:.3f} s (cache hits, "
            f"{read:.3f} s of it reading the cache), {nn} without a word "
            f"from the cache {sn:.3f} s")
