"""What PR 57 added to the yardstick, on hand cases: the Solar-Open2
configuration against its catalog row and its two copies of the source's
keys, the cut against the guide's floors, the family's counts and refusals,
where the cell is listed and what its mix says, the new reducer and the new
kernel's count, the kind's controls. The CPU rehearsal of the cell is
``test_rehearsal.py``'s, which takes every cell of ``BENCHMARK.json`` (by
hand: minutes, not tier-1's)."""

import json
import os

import pytest

from benchmark.kernels import (kda_state_step, moe_experts,
                               nope_gqa_decode_attention)
from benchmark.models import solar_open2 as fam
from benchmark.reducers import delta_gqa_step_hbm_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "solar-open2-250b-l4-e40"
CELL = NAME + ".serve-backlog-longreason"
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts",
           "vocab_size"]
EXTRA = {"n_head": "num_attention_heads",
         "layer_norm_epsilon": "rms_norm_eps", "router_experts": None,
         "first_expert_held": None, "kda_low_rank": None}
NEW = ["nope_gqa_decode_attention_roofline",
       "delta_gqa.decode_step_hbm_share", "attn.kv_share_of_step_bytes"]
# the accepted metrics the cell reports beside its own (ISSUE 57 §6)
JOINED = ["kda_state_step_roofline", "moe_experts_roofline",
          "ssm.state_bytes_per_slot", "ssm.state_share_of_step_bytes",
          "cache.bytes_per_token", "moe.load_max_over_mean",
          "moe.held_rows_share", "prog.decode_step_ms",
          "prog.prefill_chunk_ms", "serve.itl_p95_ms.backlog",
          "sched.decode_gap_ms", "sched.host_self_ms",
          "sched.prefill_ahead_share", "sched.decode_ahead_share",
          "sched.slots_running", "device.idle_share.serve", "prog.retraces",
          "prog.decode_fallback_builds", "host.stall_ms.inside",
          "host.stall_ms.program", "host.stall_ms.machine",
          "setup.import_s", "setup.engine_init_s", "setup.trace_lower_s",
          "setup.backend_s", "setup.programs", "setup.cache_misses"]


@pytest.fixture(scope="module")
def so2_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def so2_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def so2_mix():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longreason-backlog.json")) as f:
        return json.load(f)


def test_solar_s_two_copies_of_the_source_s_keys_agree(so2_conf, so2_spec):
    for key, value in so2_conf["config"].items():
        if key in EXTRA:
            assert key in so2_conf["assumed"], key
            if EXTRA[key]:
                assert value == so2_conf["config"][EXTRA[key]]
        else:
            assert so2_conf[key] == value, key
    assert so2_conf["reduced"] == REDUCED
    assert so2_conf["family"] == "solar_open2" and so2_conf["chips"] == 1
    assert so2_conf["config"]["router_experts"] \
        == so2_conf["published"]["n_routed_experts"] == 320
    # every reading the config leaves open, with the reading it excludes
    for line in ("weights", "kda_low_rank", "kda_gate", "kda_beta",
                 "kda_mixer", "gqa_gate", "no_position_code", "router",
                 "intermediate_size"):
        assert line in so2_conf["assumed"], line
    for line in ("kda_low_rank", "kda_gate", "kda_beta", "gqa_gate",
                 "no_position_code", "router"):
        assert "Excluded" in so2_conf["assumed"][line], line
    for key in ("source", "published", "deployment", "bytes", "rehearsal"):
        assert so2_conf[key], key
    assert "each layer shared by 8 chips" in so2_conf["deployment"] \
        and "published layers 0..3" in so2_conf["deployment"]
    entry = next(e for e in so2_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == so2_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_solar_has_every_key_of_its_catalog_row(so2_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert so2_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert so2_conf["published"][key] == value, key
        else:
            assert so2_conf[key] == value \
                and so2_conf["config"][key] == value, key
    # no width, head count, state size, top-k or router width is cut
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_dim", "_rank", "_size", "_heads", "_per_tok"))]


def test_solar_s_cut_keeps_the_guide_s_floors(so2_conf):
    c, p = so2_conf["config"], so2_conf["published"]
    # published layers 0..3: one whole period G K K K, four layers, no
    # leading dense layer to count
    assert (c["num_hidden_layers"], c["gqa_layers"]) == (4, [0])
    assert p["gqa_layers"][:2] == [0, 4] and c["gqa_interval"] == 3
    assert c["first_k_dense_replace"] == 0
    assert c["n_routed_experts"] == 40 >= 8 and c["router_experts"] == 320
    assert c["vocab_size"] * 8 == p["vocab_size"] == 196608
    assert fam.check(c) == "AKKK"


def test_solar_s_family_counts_what_the_issue_counted(so2_conf):
    c = so2_conf["config"]
    n, k = fam.layer_params(c), fam.kinds(c)
    assert k == {"kda": 3, "attention": 1, "routed": 4, "layers": 4}
    assert round(n["kda"] / 1e6, 2) == 137.63
    assert round(n["attention"] / 1e6, 2) == 109.05
    layer = n["router"] + n["shared"] + 40 * n["expert"]
    assert round(layer / 1e6, 1) == 646.2
    held = 3 * n["kda"] + n["attention"] + 4 * layer + 2 * n["head"]
    assert round(held / 1e9, 3) == 3.308
    assert sum(fam.state_bytes_per_slot(c).values()) == 13025280
    assert fam.cache_bytes_per_token(c) == {"kv": 4096}
    cfg = fam.model_config(c, "bfloat16")
    assert cfg.mixer_pattern == "AKKK" and cfg.kda_gate_floor == 0 \
        and cfg.kda_neg_eigval and cfg.attn_out_gate
    assert cfg.held_experts == 40 and cfg.num_experts == 320
    assert (cfg.n_head, cfg.kv_heads, cfg.head_dim) == (64, 8, 128)
    flops = fam.flops_per_token(c, 28000)
    assert flops["attention"] > flops["kda"] / 3 > 0


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("use_gqa_gate", False),
    ("kda_allow_neg_eigval", False), ("kda_use_full_proj", True),
    ("first_k_dense_replace", 1), ("gqa_layers", [1])])
def test_solar_s_family_refuses_what_it_does_not_run(so2_conf, key, value):
    with pytest.raises(ValueError, match=key):
        fam.check({**so2_conf["config"], key: value})


def test_solar_s_cell_is_listed_where_it_reports(so2_spec, so2_mix):
    cell = next(w for w in so2_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longreason-backlog", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in so2_spec["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"], name
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            reader = json.load(f)
        assert {k: reader[k] for k in ("name", "unit", "layer", "moves")} \
            == {k: by_name[name][k] for k in ("name", "unit", "layer",
                                              "moves")}
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    assert CELL in next(m for m in so2_spec["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    e = so2_mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_chunk"]) == (24, 65536, 512)
    assert so2_mix["kind"] == "backlog_delta" and so2_mix["requests"] == 256
    assert so2_mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 24576, "sigma": 0.6, "min": 4096,
        "max": 57344}
    assert so2_mix["answer_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.6, "min": 256,
        "max": 6144}
    assert so2_mix["prompt_tokens"]["max"] + so2_mix["answer_tokens"]["max"] \
        <= e["max_len"]
    # both sides of a chunk's edge, the mix's least length, and a prompt
    # tens of key blocks deep
    lens = so2_mix["check_prompt_tokens"]
    assert any(500 < n < 512 for n in lens) \
        and any(512 < n < 520 for n in lens) and max(lens) > 12000
    assert so2_mix["check_decode_steps"] >= 6


def test_the_attention_step_s_count_on_a_hand_case(so2_conf, monkeypatch):
    """24 slots at 28k live positions each, 8 KV heads of 128 under 64
    query heads: 2.75 GB of K and V, bound by memory."""
    from benchmark.kernels import full_decode_attention as fda

    flops, nbytes = fda.ops_and_bytes(
        live=24 * 28000, running=24, heads=64, kv_heads=8, head_dim=128,
        v_dim=128)
    assert flops == 2.0 * 24 * 28000 * 64 * 256
    assert round(nbytes / 1e9, 2) == 2.77
    assert nbytes / 819e9 > 10 * flops / 197e12
    monkeypatch.setattr(nope_gqa_decode_attention, "step_means",
                        lambda key: (24 * 28000.0, 24.0))
    (name, (fl, by)), = nope_gqa_decode_attention.calls(
        {"model": so2_conf["config"]}).items()
    assert name == "nope_gqa_decode_attention" and (fl, by) == (flops, nbytes)
    # a family without the gate's key reads nothing
    assert nope_gqa_decode_attention.calls(
        {"model": {"num_attention_heads": 64}}) == {}


def test_the_accepted_counts_price_this_configuration_s_shapes(so2_conf):
    """``kda_state_step``: 3 layers call it once each at 64 x 128 x 128
    (read off ``linear_attn_config``); ``moe_experts``: experts 1280 wide
    over 4096."""
    c = so2_conf["config"]
    lin = c["linear_attn_config"]
    (flops, nbytes), = kda_state_step.ops_and_bytes(
        running=24, H=lin["num_heads"], D=lin["head_dim"]).values()
    assert nbytes == 24 * (2 * 4 * 2 ** 20 + (5 * 64 * 128 + 64) * 4)
    up, down = moe_experts.ops_and_bytes(
        rows=192, touched=18, d=c["hidden_size"],
        f=c["moe_intermediate_size"]).values()
    assert round((up[1] + down[1]) / 1e6) == round(
        (18 * 3 * 4096 * 1280 * 2 + 2 * 192 * (4096 + 1280) * 2) / 1e6)


def test_the_delta_gqa_step_s_least_traffic_on_a_hand_case(so2_conf):
    c = so2_conf["config"]
    parts = delta_gqa_step_hbm_share.terms(
        fam.layer_params(c), fam.kinds(c), touched=18, running=24,
        state_bytes=13025280, live=24 * 28000, token_bytes=4096)
    gb = {k: round(v / 1e9, 2) for k, v in parts.items()}
    # ISSUE 57's floor: K/V 2.8, experts 2.3, other weights 1.2, state 0.6,
    # the head 0.2
    assert gb == {"weights outside the routed experts": 1.18,
                  "the head": 0.2, "held experts touched": 2.26,
                  "the running slots' state in and out": 0.63,
                  "the live K and V": 2.75}
    assert 8.4 < 1e3 * sum(parts.values()) / 819e9 < 8.8


def test_solar_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds.backlog_delta import CONTROLS

    assert CONTROLS == ("gate-floored", "beta-sigmoid", "out-gate-dropped",
                        "out-gate-per-head", "rope-on-attention",
                        "softmax-router", "weights-8bit")
