"""``benchmark/reducers/program_iterations.py`` on a recorded set of rows
(``data/iteration_rows.json``: 400 consecutive rows of one window of the
steady chat cell on one v5e, with sixteen sound iterations behind a chunk,
one freeze of 122 ms inside a read-back and one short one outside a wait), on a program that keeps no
rows, and the seven entries that read it."""

import json
import os

import numpy as np
import pytest

from benchmark import reduce as R
from benchmark.reducers import program_iterations
from deepspeed_tpu.observability import spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BACKLOG = ["gpt2-774m.serve-backlog",
           "kanana-2-30b-a3b-l7.serve-backlog-longdoc"]
CHAT = ["gpt2-774m.serve-chat-steady", "gpt2-774m.serve-chat-burst"]
NEW = [("host.stall_ms.inside", "ms", "lower", "serve_tokens_per_s", BACKLOG),
       ("host.stall_ms.program", "ms", "lower", "serve_tokens_per_s", BACKLOG),
       ("host.stall_ms.machine", "ms", "lower", "serve_tokens_per_s", BACKLOG),
       ("host.stall_ms.inside.steady", "ms", "lower", "itl_p95_ms", CHAT),
       ("host.stall_ms.program.steady", "ms", "lower", "itl_p95_ms", CHAT),
       ("host.stall_ms.machine.steady", "ms", "lower", "itl_p95_ms", CHAT),
       ("sched.slots_running", "slots", "higher", "serve_tokens_per_s",
        BACKLOG)]


@pytest.fixture
def recorded_rows(monkeypatch):
    """``facts`` of the recorded window, with ``spans.iterations`` handing
    out its rows (cut to the window it is asked for, as the ring's are)."""
    with open(os.path.join(HERE, "data", "iteration_rows.json")) as f:
        rec = json.load(f)
    # the fields the benchmark's note and metrics read are the program's
    assert tuple(rec["names"]) == spans.ROW.names
    rows = np.array([tuple(r) for r in rec["rows"]], spans.ROW)

    def iterations(t0=None, t1=None):
        return rows[(rows["t0"] >= t0) & (rows["t0"] <= t1)]

    monkeypatch.setattr(spans, "iterations", iterations)
    return {"window": dict(rec["window"], durations=rec["durations"],
                           counts=[int(r["tokens"]) for r in rows]),
            "notes": []}, rows, rec


def test_the_three_parts_and_the_batch(recorded_rows):
    facts, rows, rec = recorded_rows
    args = {"inside": {"part": "inside"}, "program": {"part": "program"},
            "machine": {"part": "machine"},
            "slots": {"part": "slots", "statistic": "mean"}}
    got = {k: R.run_reducer("program_iterations", facts, a)
           for k, a in args.items()}
    want = rec["expected"]
    for k in args:
        assert got[k] == pytest.approx(want[k], rel=1e-9), k
    assert 0 <= got["program"] + got["machine"] <= got["inside"]
    # the rule is host.stall_ms's, on the program's rows: whole rows over
    # twice the median
    wall = rows["t1"] - rows["t0"]
    long = wall[wall > R.STALL_OVER * np.median(wall)]
    assert got["inside"] == pytest.approx(1e3 * long.sum())
    assert len(long) == want["long"] >= 3
    # ... and agrees with what the kind's clock counted around step() and
    # its own booking, within 5% + 5 ms
    outside = R.stall_time(facts)
    assert abs(got["inside"] - outside) <= 0.05 * outside + 5.0
    stepped = rows[rows["stepped"] == 1]
    assert got["slots"] == pytest.approx(stepped["slots"].mean())
    assert 1 <= got["slots"] <= 48      # the steady cell: a few of 48
    # one note, by the metric that is read first; the six causes add up
    (note,) = facts["notes"]
    assert note.startswith("long iterations from inside: "
                           f"{got['inside']:.1f} ms in {want['long']} of "
                           f"{len(rows)} rows over 2x the median")
    for cause in spans.CAUSES:
        assert f"{cause} " in note
    ex = spans.explain(rows)
    assert sum(v["ms"] for v in ex["causes"].values()) \
        == pytest.approx(got["inside"])
    assert sum(v["count"] for v in ex["causes"].values()) == want["long"]
    assert "(agree)" in note and "the longest: step " in note
    assert f"in {R.stalls(facts['window']['durations'])[2]} iterations" in note


def test_rows_outside_the_window_are_left_out(recorded_rows):
    facts, rows, _ = recorded_rows
    whole = program_iterations.reduce(facts, part="inside")
    facts["window"]["t1"] = float(rows["t0"][len(rows) // 2])
    half = program_iterations.reduce(facts, part="inside")
    assert 0 <= half < whole
    facts["window"]["t0"] = facts["window"]["t1"] = float(rows["t1"][-1]) + 1
    assert program_iterations.reduce(facts, part="inside") is None


def test_a_program_without_the_record_has_nothing_to_read(
        recorded_rows, monkeypatch):
    facts, _, _ = recorded_rows
    monkeypatch.delattr(spans, "iterations")
    for part in ("inside", "program", "machine", "slots"):
        assert program_iterations.reduce(facts, part=part) is None
    assert facts["notes"] == []


def test_a_kind_without_a_window_has_nothing_to_read(recorded_rows):
    assert program_iterations.reduce({}, part="inside") is None
    with pytest.raises(ValueError):
        program_iterations.reduce(recorded_rows[0], part="nothing")


def test_the_seven_entries_read_the_record():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # by name, wherever later entries put them: no position is pinned
    names = {n[0] for n in NEW}
    tail = [m for m in spec["per_layer"] if m["name"] in names]
    assert [(m["name"], m["unit"], m["better"], m["moves"], m["workloads"])
            for m in tail] == NEW
    for m in tail:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["source"], m["layer"]) == ("program_counter", "scheduler")
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)
        assert reader["reducer"] == "program_iterations"
        assert {k: reader[k] for k in ("name", "layer", "unit", "moves")} \
            == {k: m[k] for k in ("name", "layer", "unit", "moves")}
        part = m["name"].split(".")[2] if "stall" in m["name"] else "slots"
        assert reader["args"]["part"] == part
    # what times the same thing from outside stays as it was
    outside = {m["name"]: m for m in spec["per_layer"]}
    assert outside["host.stall_ms"]["workloads"][:2] == BACKLOG
    assert outside["host.stall_ms.steady"]["workloads"] == CHAT
