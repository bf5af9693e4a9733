"""The yardstick under the floor: tier-1 collects the benchmark's own tests.

``benchmark/tests/`` checks what every PR's numbers rest on: ``BENCHMARK.json``
against its contract, the reducers and ``reduce.py`` by hand-built traces, the
window rule, the traffic plans, and the second family's plain reference. They
live beside the benchmark, which no PR but a ``benchmark`` one may edit, so
this file only brings them into ``tests/``: their functions and fixtures, by
name. ``test_rehearsal.py`` (a CPU run of every cell, minutes) stays by hand.

``test_nemotron_h.py`` also pins that its cell's five metrics are the LAST
of ``per_layer``: true the day the cell was added, false as soon as any later
PR appends a metric (which the contract says goes at the end), whatever that
metric lists. The ``nh_spec`` fixture below therefore leaves out what was
appended after PR 37's last entry AND DOES NOT LIST THE NEMOTRON CELL: the
test still reads the committed file for every metric that names its cell, so
one that wrongly lists it fails both of its assertions. ``test_mimo_v2_flash.py``
pins likewise that its configuration, its cell and its five metrics are the
LAST of their lists: the ``mm_spec`` fixture below hands it ``BENCHMARK.json``
without the configurations and cells appended since PR 42 and, of the metrics
appended since, with those alone that list the MiMo cell.
Since PR 48 two of the Nemotron cell's five (``ssm_state_step_roofline``,
``ssm.state_bytes_per_slot``) list a second cell, Falcon-H1's, which runs the
same kernel under the same reader: ``nh_spec`` also takes out of every list
the cells that ``workloads`` appends after the Nemotron cell, so that the pin
"the metrics that list the Nemotron cell ALONE are the last five" reads the
file as PR 37 left it. ``test_zaya.py`` and ``test_falcon_h1.py`` pin no
position. Since PR 50 one metric appended behind them all lists BOTH cells
that run ``ssm_state_step`` (``ssm.block_bytes_per_program``, ``LATER``
below): the two tests pin their cell's set of metrics as it stood the day
the cell was added, so ``nh_spec`` and ``fh_spec`` hand them the file
without it, and ``test_the_block_a_program_takes_is_read_in_both_cells``
below holds the entry itself. Since PR 51 the GLM-5.2 cell joins
``moe.held_rows_share`` (one of the five the Nemotron test counts as listing
its cell alone) and ``moe.load_max_over_mean`` (which the Nemotron and MiMo
tests read): a cell ``workloads`` appends after theirs, which ``nh_spec`` and
``mm_spec`` take out as they take out every later one;
``test_glm_moe_dsa.py`` pins no position. Since PR 55 the GLM-5.3-Flash cell
joins 24 accepted metrics (among them the Nemotron test's
``moe.held_rows_share`` and ``ssm.state_bytes_per_slot``), again a cell
``workloads`` appends after theirs, which the fixtures take out; its three
own metrics list it alone and ``test_glm5_next.py`` pins that its cell is
the LAST of the 24 lists it joined: since PR 57 the Solar-Open2 cell stands
behind it in 13 of them, and ``g53_spec`` below takes it out;
``test_solar_open2.py`` pins no position, nor does
``test_bailing_hybrid.py`` (since PR 62 the Ling-3.0-flash cell stands behind
the Solar-Open2 cell in 29 lists, and behind Kanana's in
``mla_decode_attention_roofline``: a cell ``workloads`` appends last, which
every fixture here takes out). The Ouro test has no
such pin and reads the file whole. ``test_contract.py`` holds every entry. A
``benchmark`` PR should loosen the ``[-5:]`` and ``[-1]`` pins and take these
fixtures away.
"""

import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

pytest.register_assert_rewrite(
    "benchmark.tests.test_contract", "benchmark.tests.test_reduce",
    "benchmark.tests.test_reducers", "benchmark.tests.test_traffic",
    "benchmark.tests.test_window", "benchmark.tests.test_deepseek_v3",
    "benchmark.tests.test_ouro", "benchmark.tests.test_nemotron_h",
    "benchmark.tests.test_mimo_v2_flash", "benchmark.tests.test_zaya",
    "benchmark.tests.test_falcon_h1", "benchmark.tests.test_glm_moe_dsa",
    "benchmark.tests.test_glm5_next", "benchmark.tests.test_solar_open2",
    "benchmark.tests.test_bailing_hybrid",
    "benchmark.tests.test_program_lifecycle",
    "benchmark.tests.test_program_iterations")

from benchmark.tests.test_bailing_hybrid import *  # noqa: E402,F401,F403
from benchmark.tests.test_contract import *  # noqa: E402,F401,F403
from benchmark.tests.test_deepseek_v3 import *  # noqa: E402,F401,F403
from benchmark.tests.test_falcon_h1 import *  # noqa: E402,F401,F403
from benchmark.tests.test_glm5_next import *  # noqa: E402,F401,F403
from benchmark.tests.test_glm_moe_dsa import *  # noqa: E402,F401,F403
from benchmark.tests.test_mimo_v2_flash import *  # noqa: E402,F401,F403
from benchmark.tests.test_nemotron_h import *  # noqa: E402,F401,F403
from benchmark.tests.test_ouro import *  # noqa: E402,F401,F403
from benchmark.tests.test_program_iterations import *  # noqa: E402,F401,F403
from benchmark.tests.test_program_lifecycle import *  # noqa: E402,F401,F403
from benchmark.tests.test_reduce import *  # noqa: E402,F401,F403
from benchmark.tests.test_reducers import *  # noqa: E402,F401,F403
from benchmark.tests.test_solar_open2 import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import *  # noqa: E402,F401,F403
from benchmark.tests.test_window import *  # noqa: E402,F401,F403
from benchmark.tests.test_zaya import *  # noqa: E402,F401,F403


NEMOTRON_CELL = "nemotron-3-super-l11-e128.serve-backlog-think"
FALCON_CELL = "falcon-h1-34b-l6.serve-backlog-shortchat"
GLM52_CELL = "glm-5.2-l7-e16.serve-backlog-longctx"
GLM53_CELL = "glm-5.3-flash-l5-e36.serve-backlog-longgen"
# appended since the two cells' tests pinned their sets, and listing them
LATER = {"ssm.block_bytes_per_program"}


def _spec_without_later():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] not in LATER]
    return spec


def _without_cells_after(spec, cell):
    """``spec`` with the cells ``workloads`` appends after ``cell`` taken
    out of every metric's list: the file as the PR that added ``cell`` left
    those lists."""
    cells = [w["name"] for w in spec["workloads"]]
    later = set(cells[cells.index(cell) + 1:])
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in later]
    return spec


@pytest.fixture(scope="module")
def glm_spec():  # noqa: F811
    # (since PR 55 three of the five metrics the GLM-5.2 test counts as
    # listing its cell alone list the GLM-5.3-Flash cell behind it)
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return _without_cells_after(json.load(f), GLM52_CELL)


@pytest.fixture(scope="module")
def g53_spec():  # noqa: F811
    # (since PR 57 the Solar-Open2 cell stands behind the GLM-5.3-Flash
    # cell in the lists whose last entry that cell's test pins)
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return _without_cells_after(json.load(f), GLM53_CELL)


def test_the_seven_entries_read_the_record(monkeypatch):  # noqa: F811
    """``test_program_iterations.py``'s test of that name, which pins the
    lists of PR 53's seven metrics whole (it reads the file itself, through
    no fixture): since PR 57 the Solar-Open2 cell stands behind the two
    backlog cells in four of them, since PR 62 the Ling-3.0-flash cell
    behind that. Run here on the file without the cells ``workloads``
    appends after the GLM-5.3-Flash cell."""
    from benchmark.tests import test_program_iterations as pinned

    load = json.load

    def as_pr_56_left_it(f):
        spec = load(f)
        return _without_cells_after(spec, GLM53_CELL) \
            if "per_layer" in spec else spec

    monkeypatch.setattr(json, "load", as_pr_56_left_it)
    pinned.test_the_seven_entries_read_the_record()
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = load(f)
    cells = [w["name"] for w in spec["workloads"][-2:]]
    assert [c.split("-")[0] for c in cells] == ["solar", "ling"] and all(
        m["workloads"][-2:] == cells for m in spec["per_layer"]
        if m["name"].startswith(("host.stall_ms.inside",
                                 "host.stall_ms.program",
                                 "host.stall_ms.machine",
                                 "sched.slots_running"))
        and not m["name"].endswith("steady"))


@pytest.fixture(scope="module")
def fh_spec():  # noqa: F811
    # (since PR 55 ``ssm.state_share_of_step_bytes``, one of the metrics the
    # Falcon-H1 test counts as listing its cell alone, lists a later cell)
    spec = _without_cells_after(_spec_without_later(), FALCON_CELL)
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m.get("workloads") != []]
    return spec


def test_the_block_a_program_takes_is_read_in_both_cells():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "ssm.block_bytes_per_program")
    assert entry == {
        "name": "ssm.block_bytes_per_program", "unit": "B",
        "better": "higher", "source": "program_span", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": [NEMOTRON_CELL, FALCON_CELL]}
    # the cells whose configuration has a Mamba-2 mixer, and no other
    assert entry["workloads"] == next(
        m["workloads"] for m in spec["per_layer"]
        if m["name"] == "ssm_state_step_roofline")
    with open(os.path.join(_ROOT, "benchmark", "layer_metrics",
                           "ssm.block_bytes_per_program.json")) as f:
        reader = json.load(f)
    assert (reader["reducer"], reader["args"]) == ("program_span", {
        "parent": "decode_step", "meta": "ssm_block_bytes",
        "statistic": "mean"})
    assert {k: reader[k] for k in ("name", "unit", "layer", "moves")} == {
        k: entry[k] for k in ("name", "unit", "layer", "moves")}


@pytest.fixture(scope="module")
def nh_spec():  # noqa: F811
    cell = NEMOTRON_CELL
    spec = _spec_without_later()
    names = [m["name"] for m in spec["per_layer"]]
    cut = names.index("ssm.state_bytes_per_slot") + 1
    spec["per_layer"] = spec["per_layer"][:cut] + [
        m for m in spec["per_layer"][cut:]
        if cell in m.get("workloads", [cell])]
    return _without_cells_after(spec, cell)


@pytest.fixture(scope="module")
def mm_spec():  # noqa: F811
    name = "mimo-v2-flash-l7-e16"
    cell = name + ".serve-backlog-mixedlen"
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    def upto(entries, last):
        return entries[:[e["name"] for e in entries].index(last) + 1]

    spec["configs"] = upto(spec["configs"], name)
    spec["workloads"] = upto(spec["workloads"], cell)
    cut = len(upto(spec["per_layer"], "cache.window_bytes_per_slot"))
    spec["per_layer"] = spec["per_layer"][:cut] + [
        m for m in spec["per_layer"][cut:]
        if cell in m.get("workloads", [cell])]
    return spec
