"""The reducers of ``benchmark/reducers/`` on events made by hand, and on a
program that has nothing for them to read."""

import pytest

from benchmark.reducers import program_counter, program_span
from deepspeed_tpu.observability.spans import SpanEvent


def iteration(step, t, *, readback, chunk=None, slots=3):
    """One ``srv.step`` of 10 ms at ``t``: 1 ms of the loop's own, a decode
    pair inside ``decode_step``, then tail; with ``chunk`` a prefill
    dispatch and its blocking read in front."""
    ms = 1e-3
    evs = []
    at = t + 0.2 * ms
    if chunk is not None:
        evs.append(SpanEvent("prefill_chunk", at, at + 0.3 * ms, rid=9,
                             step=step, meta={"size": 64, "final": True}))
        evs.append(SpanEvent("srv.prefill_readback", at + 0.3 * ms,
                             at + 0.3 * ms + chunk, step=step))
        at += 0.3 * ms + chunk
    evs.append(SpanEvent("srv.decode_dispatch", at + 0.1 * ms, at + 0.4 * ms,
                         step=step))
    evs.append(SpanEvent("srv.decode_readback", at + 0.4 * ms,
                         at + 0.4 * ms + readback, step=step))
    evs.append(SpanEvent("decode_step", at, at + 0.5 * ms + readback,
                         step=step, meta={"slots": slots, "queue": 0}))
    end = at + 0.5 * ms + readback
    evs.append(SpanEvent("srv.tail", end, end + 0.2 * ms, step=step))
    evs.append(SpanEvent("srv.step", t, end + 0.3 * ms, step=step))
    return evs


@pytest.fixture
def three_iterations(monkeypatch):
    evs = (iteration(4, 0.0, readback=8e-3, slots=2)
           + iteration(5, 0.1, readback=7e-3, chunk=2e-3, slots=3)
           + iteration(6, 0.2, readback=9e-3, slots=4)
           # another engine's step 5 elsewhere on the clock: not a child
           + [SpanEvent("train_phase", 5.0, 5.5, step=5),
              # a request's lifecycle and an instant: no step, no end
              SpanEvent("queued", 0.0, 0.1, rid=9),
              SpanEvent("marker", 0.1, None, step=5, meta={"name": "x"})])
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    return evs


def test_span_less_its_excluded_children(three_iterations):
    facts = {}
    got = program_span.reduce(
        facts, parent="srv.step", statistic="median",
        exclude=["srv.prefill_readback", "srv.decode_readback"])
    # every iteration: 0.2 + 0.5 + 0.3 ms around the reads; the one with a
    # chunk has its 0.3 ms dispatch on top
    assert got == pytest.approx(1.0)
    assert program_span.reduce(
        {}, parent="srv.step", statistic="p95",
        exclude=["srv.prefill_readback", "srv.decode_readback"]) \
        == pytest.approx(1.3)
    (note,) = facts["notes"]
    assert note.startswith("srv.step 10.000 ms median over 3;")
    # the children, largest first, with how many iterations each ran in;
    # decode_step nests around the pair and is not taken off twice
    assert "decode_step 8.500 (3), srv.decode_readback 8.000 (3)" in note
    assert "srv.prefill_readback 2.000 (1)" in note
    assert "train_phase" not in note and "queued" not in note
    assert note.endswith("its own 0.300")   # 0.2 before, 0.1 after


def test_statistic_of_a_count_on_the_spans(three_iterations):
    facts = {}
    assert program_span.reduce(facts, parent="decode_step", meta="slots",
                               statistic="mean") == pytest.approx(3.0)
    assert facts == {}      # a count has no split to note


@pytest.mark.parametrize("events", [[], [SpanEvent("queued", 0.0, 1.0)]])
def test_nothing_recorded_is_nothing_to_read(monkeypatch, events):
    monkeypatch.setattr(program_span, "_captured", lambda: events)
    assert program_span.reduce({}, parent="srv.step") is None


def test_a_program_without_the_accessor_has_nothing_to_read(monkeypatch):
    """The parent of the PR that added ``captured()``: the reducer returns
    None and does not raise, and the line leaves the metric out."""
    from deepspeed_tpu.observability import spans

    monkeypatch.delattr(spans, "captured")
    assert program_span._captured() == []
    assert program_span.reduce({}, parent="srv.step") is None


def test_counter_total_and_absence():
    from deepspeed_tpu.observability.metrics import get_registry

    assert program_counter.reduce({}, counter="Bench/never_kept") is None
    get_registry().counter("Bench/by_hand").inc(3)
    get_registry().counter("Bench/by_hand").inc(2)
    assert program_counter.reduce({}, counter="Bench/by_hand") == 5.0
    get_registry().counter("Bench/at_zero")
    assert program_counter.reduce({}, counter="Bench/at_zero") == 0.0
