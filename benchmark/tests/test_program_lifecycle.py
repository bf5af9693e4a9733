"""``benchmark/reducers/program_lifecycle.py`` on a ring made by hand, and on
a program that has no lifecycle ring."""

import pytest

from benchmark.reducers import program_lifecycle
from deepspeed_tpu.observability.spans import SpanEvent


def compile_(program, stage, t0, seconds, **meta):
    return SpanEvent("compile", t0, t0 + seconds,
                     meta={"program": program, "stage": stage, **meta})


@pytest.fixture
def a_set_up(monkeypatch):
    """Import 2 s; an inference engine built in 3 s with one program loaded
    inside it; a serving engine built in 1 s whose ``init_slots`` is
    compiled inside it; a step program built outside any engine's build,
    and traced once more for a second signature; then the window opens at
    30 s and one more program is compiled inside it."""
    evs = [
        SpanEvent("init", 0.0, 2.0, meta={"phase": "import"}),
        compile_("jit_place", "trace", 3.0, 0.1),
        compile_("jit_place", "lower", 3.1, 0.2),
        compile_("jit_place", "backend", 3.3, 0.7, cache_hit=True,
                 retrieval_s=0.6),
        SpanEvent("init", 2.5, 5.5, meta={"phase": "inference"}),
        compile_("jit_init_slots", "trace", 6.0, 0.05),
        compile_("jit_init_slots", "lower", 6.05, 0.05),
        compile_("jit_init_slots", "backend", 6.1, 0.4, cache_hit=False),
        SpanEvent("init", 5.9, 6.9, meta={"phase": "serving"}),
        compile_("jit__step_impl", "trace", 10.0, 1.0),
        compile_("jit__step_impl", "lower", 11.0, 0.5),
        compile_("jit__step_impl", "backend", 11.5, 8.0),
        compile_("jit__step_impl", "trace", 20.0, 0.25),
        SpanEvent("retrace", 20.5, None, step=7, meta={
            "program": "step", "module": "jit__step_impl", "signatures": 2,
            "new": 1, "why": "trace 0.250 s, no lowering: an executable it "
                             "had"}),
        compile_("jit_late", "trace", 31.0, 0.5),
        compile_("jit_late", "backend", 31.5, 2.0, cache_hit=False),
    ]
    monkeypatch.setattr(program_lifecycle, "_lifecycle", lambda: evs)
    return {"window": {"t0": 30.0, "t1": 70.0}}


def test_sums_by_stage_before_the_window(a_set_up):
    facts = a_set_up
    assert program_lifecycle.reduce(facts, part="import") \
        == pytest.approx(2.0)
    assert program_lifecycle.reduce(facts, part="trace_lower") \
        == pytest.approx(0.1 + 0.2 + 0.05 + 0.05 + 1.0 + 0.5 + 0.25)
    assert program_lifecycle.reduce(facts, part="backend") \
        == pytest.approx(0.7 + 0.4 + 8.0)
    said = "\n".join(facts["notes"])
    assert "2 lifecycle events after the window opened are left out" in said
    assert "jit_late" not in said
    # the dearest first, every signature of a module together
    assert ("the 3 dearest of 3 programs (s: trace + lower + backend, times "
            "built): jit__step_impl 9.750 = 1.250 + 0.500 + 8.000 (1), "
            "jit_place 1.000 = 0.100 + 0.200 + 0.700 (1), "
            "jit_init_slots 0.500") in said
    assert ("retraces: 1 in all; step (jit__step_impl) signature 2 at step "
            "7: trace 0.250 s, no lowering") in said


def test_an_engine_s_build_is_its_span_less_the_compiles_inside(a_set_up):
    facts = a_set_up
    # inference 3.0 less 1.0 of jit_place; serving 1.0 less 0.5
    assert program_lifecycle.reduce(facts, part="engine_init") \
        == pytest.approx(2.0 + 0.5)
    (note,) = facts["notes"]
    assert note == ("engines built (s whole, own): init.inference 3.000 "
                    "2.000, init.serving 1.000 0.500")


def test_compiled_is_told_from_loaded(a_set_up):
    facts = a_set_up
    program_lifecycle.reduce(facts, part="backend")
    (note,) = facts["notes"]
    assert note == ("backend: 3 programs; 1 compiled 0.400 s (cache misses), "
                    "1 loaded 0.700 s (cache hits, 0.600 s of it reading the "
                    "cache), 1 without a word from the cache 8.000 s")


def test_the_four_parts_stay_under_the_set_up(a_set_up):
    parts = [program_lifecycle.reduce(a_set_up, part=p) for p in (
        "import", "engine_init", "trace_lower", "backend")]
    # disjoint by construction: together no more than the 30 s before the
    # window
    assert sum(parts) == pytest.approx(2.0 + 2.5 + 2.15 + 9.1) and \
        sum(parts) <= 30.0


def test_a_kind_without_a_window_reads_the_whole_ring(a_set_up):
    assert program_lifecycle.reduce({}, part="backend") \
        == pytest.approx(0.7 + 0.4 + 8.0 + 2.0)


@pytest.mark.parametrize("part", ["import", "engine_init", "trace_lower",
                                  "backend"])
def test_a_program_without_the_ring_has_nothing_to_read(monkeypatch, part):
    """The parent of the PR that added ``lifecycle()``: its ``spans`` module
    has no such accessor, the metric is left out of the line, and no note
    is written."""
    from deepspeed_tpu.observability import spans

    monkeypatch.delattr(spans, "lifecycle")
    facts = {}
    assert program_lifecycle.reduce(facts, part=part) is None
    assert facts == {}


def test_a_ring_with_no_import_span_reads_no_import(monkeypatch):
    monkeypatch.setattr(program_lifecycle, "_lifecycle", lambda: [])
    assert program_lifecycle.reduce({}, part="import") is None
    assert program_lifecycle.reduce({}, part="backend") == 0.0


def test_the_program_s_own_ring_is_what_is_read():
    """Not faked: this process imported the package and has compiled, so
    every part reads a number."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x * 2)(jnp.ones(3))
    facts = {}
    assert program_lifecycle.reduce(facts, part="trace_lower") > 0
    assert program_lifecycle.reduce(facts, part="backend") > 0
    assert any(n.startswith("backend: ") for n in facts["notes"])
