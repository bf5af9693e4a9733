"""BENCHMARK.json and the files it points to, against the builder's
contract: names, units, limits, and that every metric can be reported."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(spec["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in spec["paths"])
    assert len(spec["command"]) <= 32 and all(map(line_ok, spec["command"]))
    rs = spec["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert "assumed" in conf and isinstance(conf["config"], dict)
        # the family's builder and plain reference are files found by name
        importlib.import_module(f"benchmark.models.{conf['family']}")
        importlib.import_module(f"benchmark.reference.{conf['family']}")


def test_workloads(spec):
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in spec["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        importlib.import_module(f"benchmark.kinds.{mix['kind']}")
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def reporting(metric, spec):
    return set(metric.get("workloads", [w["name"] for w in spec["workloads"]]))


def test_metrics(spec):
    e2e, per = spec["end_to_end"], spec["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in spec["workloads"]}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        # PERF.md section 2: a rate or a tail is held at 1% to 2%, in half
        # percents, and one that cannot be held there is a per-layer metric;
        # only the one metric a cell has beside setup_s may take the wider
        # bound its sets force
        if m["name"] != "setup_s":
            assert round(m["bound"] * 200) == pytest.approx(m["bound"] * 200)
            only = any(
                [e["name"] for e in e2e if e["name"] != "setup_s"
                 and c in reporting(e, spec)] == [m["name"]] for c in cells)
            assert m["bound"] <= (0.025 if only else 0.02), m["name"]
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] == 0.1
    for m in per:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert line_ok(m["layer"])
        moved = [e for e in e2e if e["name"] == m["moves"]]
        assert len(moved) == 1, m["name"]
        # the metric it moves is reported wherever this one is
        assert reporting(m, spec) <= reporting(moved[0], spec), m["name"]
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)
        assert (reader["layer"], reader["unit"], reader["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        from benchmark import reduce as R
        assert reader["reducer"] in R.GENERIC or os.path.exists(os.path.join(
            ROOT, "benchmark", "reducers", reader["reducer"] + ".py"))
        if reader["reducer"] == "kernel_roofline":
            assert m["name"] == reader["args"]["kernel"] + "_roofline"
            importlib.import_module(
                f"benchmark.kernels.{reader['args']['kernel']}")
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert reporting(m, spec) <= cells
    for c in cells:
        mine = [m["name"] for m in e2e if c in reporting(m, spec)]
        assert "setup_s" in mine and len(mine) >= 2, c
        assert any(c in reporting(m, spec) for m in per), c
    # one layer, one spelling
    layers = {m["layer"] for m in per}
    assert len({x.lower() for x in layers}) == len(layers)


def test_files_under_paths_are_named_from_the_contract_s_characters(spec):
    for p in spec["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_peaks_have_a_source_and_unknown_devices_fail():
    from benchmark import harness

    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    assert "Google Cloud" in table["_source"]
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")
