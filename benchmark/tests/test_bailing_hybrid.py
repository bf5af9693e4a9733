"""What PR 62 added to the yardstick, on hand cases: the Ling-3.0-flash
configuration against its catalog row and its two copies of the source's
keys, the cut against the guide's floors, the family's counts and refusals,
where the cell is listed and what its mix says, the new reducer and the
accepted kernels' counts at this configuration's shapes, the kind's controls.
The CPU rehearsal of the cell is ``test_rehearsal.py``'s, which takes every
cell of ``BENCHMARK.json`` (by hand: minutes, not tier-1's)."""

import json
import os

import pytest

from benchmark.kernels import kda_state_step, mla_decode_attention, moe_experts
from benchmark.models import bailing_hybrid as fam
from benchmark.reducers import delta_gqa_step_hbm_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "ling-3.0-flash-l6-e64"
CELL = NAME + ".serve-backlog-reasontail"
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
           "num_experts", "vocab_size", "num_nextn_predict_layers"]
EXTRA = {"n_head": "num_attention_heads",
         "layer_norm_epsilon": "rms_norm_eps", "n_routed_experts":
         "num_experts", "router_experts": None, "first_expert_held": None,
         "linear_attn_config": None}
NEW = ["delta_latent.decode_step_hbm_share", "moe.held_group_token_share"]
# the accepted metrics the cell reports beside its own (ISSUE 62 §6)
JOINED = ["kda_state_step_roofline", "mla_decode_attention_roofline",
          "moe_experts_roofline", "ssm.state_bytes_per_slot",
          "ssm.state_share_of_step_bytes", "attn.kv_share_of_step_bytes",
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
def ling_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ling_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ling_mix():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reasontail-backlog.json")) as f:
        return json.load(f)


def test_ling_s_two_copies_of_the_source_s_keys_agree(ling_conf, ling_spec):
    for key, value in ling_conf["config"].items():
        if key in EXTRA:
            assert key in ling_conf["assumed"], key
            if EXTRA[key]:
                assert value == ling_conf["config"][EXTRA[key]]
        else:
            assert ling_conf[key] == value, key
    assert ling_conf["reduced"] == REDUCED
    assert ling_conf["family"] == "bailing_hybrid" and ling_conf["chips"] == 1
    assert ling_conf["config"]["router_experts"] \
        == ling_conf["published"]["num_experts"] == 512
    # every reading the config leaves open, with the reading it excludes
    for line in ("weights", "layer_pattern", "use_qk_norm", "kda_gate",
                 "kda_mixer", "mla", "mla_gate", "router", "clamps",
                 "param_count"):
        assert line in ling_conf["assumed"], line
    for line in ("use_qk_norm", "kda_gate", "kda_mixer", "mla", "mla_gate",
                 "router", "clamps"):
        assert "Excluded" in ling_conf["assumed"][line], line
    for key in ("source", "published", "deployment", "bytes", "rehearsal"):
        assert ling_conf[key], key
    for said in ("each layer shared by 8 chips = the 8 routing groups",
                 "published layers 36..41", "rows 0..19647 of 157 184",
                 "MTP"):
        assert said in ling_conf["deployment"], said
    entry = next(e for e in ling_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == ling_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert ling_spec["configs"][-1] == entry


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_ling_has_every_key_of_its_catalog_row(ling_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    assert ling_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert ling_conf["published"][key] == value, key
        else:
            assert ling_conf[key] == value \
                and ling_conf["config"][key] == value, key
    assert set(ling_conf["published"]) == set(REDUCED)
    # no width, head count, state size, top-k or group count is cut
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_dim", "_rank", "_size", "_heads", "_per_tok", "_group"))]


def test_ling_s_cut_keeps_the_guide_s_floors(ling_conf):
    c, p = ling_conf["config"], ling_conf["published"]
    # published layers 36..41: the last of seven stages of six, one whole
    # period K K K K K A, six expert layers (>= 4), every clamp live
    assert (c["num_hidden_layers"], c["layer_group_size"]) == (6, 6)
    assert p["num_hidden_layers"] == 42 == 7 * 6
    assert fam.check(c) == "KKKKKA"
    assert (c["first_k_dense_replace"], p["first_k_dense_replace"]) == (0, 2)
    assert c["expert_swiglu_limit_list"] \
        == p["expert_swiglu_limit_list"][36:] == [4] * 6
    assert c["share_expert_swiglu_limit_list"] \
        == p["share_expert_swiglu_limit_list"][36:] == [5, 5, 5, 5, 7, 7]
    # one routing group of the eight, an eighth of the vocabulary
    assert c["num_experts"] == 64 >= 8 and c["router_experts"] == 512
    assert c["num_experts"] * c["n_group"] == c["router_experts"]
    assert c["vocab_size"] * 8 == p["vocab_size"] == 157184
    assert (c["num_nextn_predict_layers"],
            p["num_nextn_predict_layers"]) == (0, 1)


def test_ling_s_family_counts_what_the_issue_counted(ling_conf):
    c = ling_conf["config"]
    n, k = fam.layer_params(c), fam.kinds(c)
    assert k == {"kda": 5, "attention": 1, "dense": 0, "routed": 6,
                 "layers": 6}
    assert round(n["kda"] / 1e6, 1) == 63.0
    assert round(n["attention"] / 1e6, 1) == 32.0
    assert round(n["expert"] / 1e6, 3) == 5.898
    assert round((n["router"] + n["shared"]) / 1e6, 2) == 7.21
    outside = 5 * n["kda"] + n["attention"] + 6 * (n["router"] + n["shared"])
    assert round(outside / 1e6) == 390
    held = outside + 6 * 64 * n["expert"] + 2 * n["head"]
    assert round(held / 1e9, 2) == 2.76
    assert sum(fam.state_bytes_per_slot(c).values()) \
        == 5 * (2 ** 21 + 73728) == 10854400
    assert fam.cache_bytes_per_token(c) == {"latent": 1152}
    cfg = fam.model_config(c, "bfloat16")
    assert cfg.mixer_pattern == "KKKKKA" and cfg.kda_gate_floor == -5.0 \
        and cfg.kda_rank == 0 and cfg.kda_qk_norm \
        and cfg.attn_out_gate == "head" and cfg.pos_embedding == "rope"
    assert (cfg.held_experts, cfg.num_experts, cfg.moe_n_group,
            cfg.moe_topk_group) == (64, 512, 8, 4)
    assert (cfg.n_head, cfg.head_dim, cfg.v_dim, cfg.latent_dim) \
        == (32, 192, 128, 576)
    assert cfg.moe_swiglu_limits == (4,) * 6 \
        and cfg.moe_shared_swiglu_limits == (5, 5, 5, 5, 7, 7)
    flops = fam.flops_per_token(c, 5000)
    assert flops["experts"] > flops["kda"] > flops["attention"] > 0
    assert fam.train_flops_per_token(c, 10000) == 3.0 * sum(flops.values())


@pytest.mark.parametrize("key,value", [
    ("use_qk_norm", False), ("no_kda_lora", False), ("kda_safe_gate", False),
    ("use_mla_nope", True), ("rope_interleave", False),
    ("gated_attention_proj_granularity_type", "elementwise"),
    ("q_lora_rank", 1536), ("num_nextn_predict_layers", 1),
    ("expert_swiglu_limit_list", [4, 4]), ("n_head", 64)])
def test_ling_s_family_refuses_what_it_does_not_run(ling_conf, key, value):
    with pytest.raises(ValueError, match=key):
        fam.check({**ling_conf["config"], key: value})


def test_ling_s_cell_is_listed_where_it_reports(ling_spec, ling_mix):
    cell = next(w for w in ling_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reasontail-backlog", 1)
    assert len(cell["why"]) <= 200 and ling_spec["workloads"][-1] == cell
    by_name = {m["name"]: m for m in ling_spec["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            reader = json.load(f)
        assert {k: reader[k] for k in ("name", "unit", "layer", "moves")} \
            == {k: by_name[name][k] for k in ("name", "unit", "layer",
                                              "moves")}
    assert [m["name"] for m in ling_spec["per_layer"][-2:]] == NEW
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    listed = {m["name"] for m in ling_spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    assert next(m for m in ling_spec["end_to_end"] if m["name"]
                == "serve_tokens_per_s")["workloads"][-1] == CELL
    e = ling_mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_chunk"]) == (160, 24576, 512)
    assert ling_mix["kind"] == "backlog_delta_latent" \
        and ling_mix["requests"] == 1024
    assert ling_mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 1.1, "min": 256,
        "max": 16384}
    assert ling_mix["answer_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024,
        "max": 8192}
    assert ling_mix["prompt_tokens"]["max"] \
        + ling_mix["answer_tokens"]["max"] <= e["max_len"]
    assert ling_mix["check_prompt_tokens"] == [510, 513, 2050, 12301]
    assert ling_mix["check_decode_steps"] == 6
    assert 0 < ling_mix["route_gap"] < ling_mix["logit_tolerance"] < 0.1


def test_the_accepted_counts_price_this_configuration_s_shapes(ling_conf,
                                                               monkeypatch):
    """``kda_state_step``: 5 layers call it once each at 32 x 128 x 128 (read
    off the ``linear_attn_config`` alias); ``mla_decode_attention``: Kanana's
    shapes, 32 heads over 512 + 64; ``moe_experts``: experts 768 wide over
    2560, counted off the ``n_routed_experts`` alias."""
    c = ling_conf["config"]
    lin = c["linear_attn_config"]
    (flops, nbytes), = kda_state_step.ops_and_bytes(
        running=160, H=lin["num_heads"], D=lin["head_dim"]).values()
    assert nbytes == 160 * (2 * 2 * 2 ** 20 + (5 * 32 * 128 + 32) * 4)
    calls = mla_decode_attention.calls({
        "model": c, "slots": 160, "decode_live_tokens": [160 * 5000.0]})
    fl, by = calls["mla_decode_attention"]
    assert fl == 2.0 * 160 * 5000 * 32 * (576 + 512)
    assert round(by / 1e9, 2) == 0.93          # the live latents, once
    up, down = moe_experts.ops_and_bytes(
        rows=1280, touched=59, d=c["hidden_size"],
        f=c["moe_intermediate_size"]).values()
    assert round((up[1] + down[1]) / 1e6) == round(
        (59 * 3 * 2560 * 768 * 2 + 2 * 1280 * (2560 + 768) * 2) / 1e6)
    # a program that kept no spans reads nothing, and raises nothing
    from benchmark.reducers import program_span

    monkeypatch.setattr(program_span, "_captured", lambda: [])
    assert moe_experts.calls({"model": c, "slots": 160}) == {}
    assert kda_state_step.calls({"model": c}) == {}


def test_the_delta_latent_step_s_least_traffic_on_a_hand_case(ling_conf,
                                                              monkeypatch):
    """ISSUE 62's reckoning: 160 running slots at ~5k live positions, 59 of
    the 64 held experts touched a layer — state 3.5 GB, experts 2.1, other
    weights 0.8, the head 0.1, latents 0.9; and the reducer itself on spans
    of that step, silent on a program without the new meta."""
    from types import SimpleNamespace

    from benchmark.reducers import delta_latent_step_hbm_share as mine
    from benchmark.reducers import program_span

    c = ling_conf["config"]
    parts = delta_gqa_step_hbm_share.terms(
        fam.layer_params(c), fam.kinds(c), touched=59, running=160,
        state_bytes=10854400, live=160 * 5000, token_bytes=1152)
    gb = {k: round(v / 1e9, 2) for k, v in parts.items()}
    assert gb == {"weights outside the routed experts": 0.78,
                  "the head": 0.1, "held experts touched": 4.18,
                  "the running slots' state in and out": 3.47,
                  "the live K and V": 0.92}
    assert 11.0 < 1e3 * sum(parts.values()) / 819e9 < 12.0
    meta = {"experts_touched": 59.0, "slots": 160, "live_positions": 800000,
            "state_bytes_per_slot": 10854400, "cache_bytes_per_token": 1152}
    facts = {"family": "bailing_hybrid", "model": c,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    monkeypatch.setattr(mine, "program_time", lambda *a, **k: 14.0)
    monkeypatch.setattr(program_span, "_captured", lambda: [
        SimpleNamespace(kind="decode_step", t1=1.0, meta=meta)])
    monkeypatch.setattr(mine, "_captured", program_span._captured)
    assert mine.reduce(facts, program="x") is None      # a parent's spans
    meta["held_group_token_share"] = 0.5
    share = mine.reduce(facts, program="x")
    assert round(share, 1) == round(100 * 1e3 * sum(parts.values())
                                    / 819e9 / 14.0, 1)
    assert mine.LATENTS in facts["notes"][-1]


def test_ling_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds.backlog_delta_latent import BUFFERS, CONTROLS

    assert CONTROLS == (
        "gate-unbounded", "kda-out-gate-dropped", "qk-gain-dropped",
        "rope-dropped", "rope-halves", "out-gate-per-channel",
        "out-gate-dropped", "plain-top8", "routed-clamp-dropped",
        "shared-clamp-routed", "routed-scale-1", "weights-8bit")
    assert BUFFERS == ("c", "kda", "conv")
