"""Lint-style source checks over ``deepspeed_tpu/``.

Bare ``print(`` is forbidden in library code: in a multi-host job it
writes from every process with no rank gating, it bypasses the
``DSTPU_LOG_LEVEL`` filter, and nothing downstream can parse it — output
belongs in ``utils/logging`` (human logs) or the observability layer
(machine-readable metrics).

Exempt: modules whose *stdout is their interface* — CLI report/bench
entry points and the autotuner's worker JSON protocol. Adding a module
here needs that justification, not convenience.

Bare ``except:`` and silent ``except Exception: pass`` are forbidden too
(resilience layer discipline): a swallowed exception is an invisible
failure mode — exactly what the typed-error taxonomy in
``resilience/guards.py`` exists to prevent. Catch the narrowest type you
can name; if a site truly must swallow everything (destructors,
best-effort probes on exotic backends), it goes in the allowlist WITH the
justification next to it.
"""

import re
from pathlib import Path

PKG = Path(__file__).resolve().parents[2] / "deepspeed_tpu"

# stdout-as-interface modules (relative to deepspeed_tpu/)
PRINT_ALLOWED = {
    "env_report.py",           # ds_report analog: a stdout report tool
    "comm/bench.py",           # comms microbench CLI table
    "ops/aio_bench.py",        # aio sweep CLI table
    "autotuning/cli.py",       # autotuner CLI frontend
    "autotuning/worker.py",    # prints JSON: the worker↔tuner IPC protocol
    "elasticity/agent.py",     # launcher agent: pre-logging bootstrap output
    "launcher/launch.py",      # process supervisor: child exit reporting
    "launcher/runner.py",      # multinode launcher CLI
    "runtime/checkpoint/to_fp32.py",   # zero_to_fp32-style CLI (stderr note)
    "observability/doctor.py",  # ops triage CLI: the report IS its stdout
    "observability/fleet_scrape.py",  # aggregator CLI: stdout is the
                                      # merged exposition (no --out)
}

_BARE_PRINT = re.compile(r"^\s*print\(")


def test_no_bare_print_in_library_code():
    offenders = []
    for path in sorted(PKG.rglob("*.py")):
        rel = path.relative_to(PKG).as_posix()
        if rel in PRINT_ALLOWED:
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if _BARE_PRINT.match(line):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "bare print( in library code — route through utils/logging or the "
        "observability metrics layer (or, for a stdout-protocol CLI, add "
        "an explicit justified entry to PRINT_ALLOWED):\n"
        + "\n".join(offenders))


def test_print_allowlist_entries_exist():
    """A deleted/renamed module must not leave a stale exemption behind."""
    missing = [rel for rel in PRINT_ALLOWED if not (PKG / rel).exists()]
    assert not missing, f"stale PRINT_ALLOWED entries: {missing}"


# --------------------------------------------------------- except hygiene
# except-Exception-pass sites that may stay, each with its justification
# (count per file, so a NEW silent swallow in the same file still fails):
EXCEPT_PASS_ALLOWED = {
    "ops/aio.py": 1,                  # __del__: a destructor must never raise
    "observability/xla.py": 1,        # best-effort device sync before
                                      # stop_trace — the trace must close
    "platform/accelerator.py": 1,     # defensive barrier on exotic backends
    "profiling/flops_profiler.py": 1,  # memory_analysis attr probe (fields
                                       # vary across jax versions)
    "runtime/offload.py": 1,          # copy_to_host_async is not on every
                                      # backend; the sync path still runs
}

_BARE_EXCEPT = re.compile(r"^\s*except\s*:")
_BROAD_EXCEPT = re.compile(r"^\s*except\s+(Exception|BaseException)\s*:")


def _silent_swallows(lines):
    """Line numbers of ``except Exception:`` (or BaseException) whose first
    following statement is ``pass`` — comments/blank lines between don't
    launder the swallow."""
    out = []
    for i, line in enumerate(lines):
        if not _BROAD_EXCEPT.match(line):
            continue
        for nxt in lines[i + 1:]:
            body = nxt.split("#", 1)[0].strip()
            if not body:
                continue
            if body == "pass":
                out.append(i + 1)
            break
    return out


def test_no_bare_or_silent_except_in_library_code():
    bare, silent = [], []
    for path in sorted(PKG.rglob("*.py")):
        rel = path.relative_to(PKG).as_posix()
        lines = path.read_text().splitlines()
        for lineno, line in enumerate(lines, 1):
            if _BARE_EXCEPT.match(line):
                bare.append(f"{rel}:{lineno}")
        hits = _silent_swallows(lines)
        if len(hits) > EXCEPT_PASS_ALLOWED.get(rel, 0):
            silent += [f"{rel}:{n}" for n in hits]
    assert not bare, (
        "bare `except:` in library code — catch a named exception type "
        "(see resilience/guards.py for the typed taxonomy):\n"
        + "\n".join(bare))
    assert not silent, (
        "silent `except Exception: pass` beyond the justified allowlist — "
        "catch the narrowest type, or add an EXCEPT_PASS_ALLOWED entry "
        "WITH its justification:\n" + "\n".join(silent))


# ------------------------------------------------------ clock-seam hygiene
# Every timestamp in the serving/observability/resilience stack must be
# fake-clock-testable — the observability/ glob below covers the PR-8
# telemetry plane (server.py, goodput.py, fleet_scrape.py) like every
# earlier module: modules take an injectable ``clock`` (default-arg
# references like ``clock=time.perf_counter`` are the seam and are fine);
# a DIRECT ``time.time()`` / ``time.perf_counter()`` / ``time.monotonic()``
# call inside a function body hard-wires wall time and makes the chaos /
# deadline / flight-record tests racy. ``time.sleep`` / ``time.strftime``
# are not timestamps and are not linted.
CLOCK_LINTED_DIRS = ("serving/", "observability/", "resilience/",
                     # profiling/ joined when FlopsProfiler grew its
                     # injectable-clock seam alongside the capacity
                     # census (PR 6) — its timed step must stay
                     # fake-clock-testable like every other timestamp
                     "profiling/")

# direct-call sites that may stay, each with its justification
# (count per file, like EXCEPT_PASS_ALLOWED):
CLOCK_CALL_ALLOWED: dict[str, int] = {
    # (none today — new entries need a why, e.g. "operator-facing wall
    # time in a filename, not a measured interval")
}

_CLOCK_CALL = re.compile(r"\btime\.(?:time|perf_counter|monotonic)\(\)")


def _clock_calls(lines):
    out = []
    for lineno, line in enumerate(lines, 1):
        code = line.split("#", 1)[0]
        if _CLOCK_CALL.search(code):
            out.append(lineno)
    return out


def test_no_bare_clock_calls_in_clock_seamed_modules():
    offenders = []
    for path in sorted(PKG.rglob("*.py")):
        rel = path.relative_to(PKG).as_posix()
        if not rel.startswith(CLOCK_LINTED_DIRS):
            continue
        hits = _clock_calls(path.read_text().splitlines())
        if len(hits) > CLOCK_CALL_ALLOWED.get(rel, 0):
            offenders += [f"{rel}:{n}" for n in hits]
    assert not offenders, (
        "direct wall-clock call in a clock-seamed module — take an "
        "injectable `clock` (default it to time.perf_counter WITHOUT "
        "calling it) so fake-clock tests stay deterministic, or add a "
        "justified CLOCK_CALL_ALLOWED entry:\n" + "\n".join(offenders))


def test_clock_call_allowlist_is_tight():
    stale = []
    for rel, allowed in CLOCK_CALL_ALLOWED.items():
        p = PKG / rel
        if not p.exists():
            stale.append(f"{rel} (deleted)")
            continue
        hits = len(_clock_calls(p.read_text().splitlines()))
        if hits < allowed:
            stale.append(f"{rel} (allows {allowed}, found {hits})")
    assert not stale, f"stale CLOCK_CALL_ALLOWED entries: {stale}"


def test_except_pass_allowlist_is_tight():
    """Fixed sites must leave the allowlist (stale exemptions hide new
    swallows), and every listed module must still exist."""
    stale = []
    for rel, allowed in EXCEPT_PASS_ALLOWED.items():
        p = PKG / rel
        if not p.exists():
            stale.append(f"{rel} (deleted)")
            continue
        hits = len(_silent_swallows(p.read_text().splitlines()))
        if hits < allowed:
            stale.append(f"{rel} (allows {allowed}, found {hits})")
    assert not stale, f"stale EXCEPT_PASS_ALLOWED entries: {stale}"
