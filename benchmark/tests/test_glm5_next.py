"""What PR 55 added to the yardstick, on hand cases: the GLM-5.3-Flash
configuration against its catalog row and its two copies of the source's
keys, the cut against the guide's floors, the family's counts and refusals,
where the cell is listed and what its mix says, the new reducer and the new
kernel's count, the kind's tap on the engine's own programs. The CPU
rehearsal of the cell is ``test_rehearsal.py``'s, which takes every cell of
``BENCHMARK.json`` (by hand: minutes, not tier-1's)."""

import json
import os

import pytest

from benchmark.kernels import kda_state_step
from benchmark.models import glm5_next as fam
from benchmark.reducers import linear_step_hbm_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "glm-5.3-flash-l5-e36"
CELL = NAME + ".serve-backlog-longgen"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
           "layer_types", "indexer_types", "linear_attn_config",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
EXTRA = {"n_head": "num_attention_heads",
         "layer_norm_epsilon": "rms_norm_eps", "router_experts": None,
         "first_expert_held": None, "kda_low_rank": None}
NEW = ["kda_state_step_roofline", "linear.decode_step_hbm_share",
       "dsa.keys_scored_over_live"]
# the accepted metrics the cell reports beside its own (ISSUE 55 §5)
JOINED = ["sched.decode_gap_ms", "prog.decode_step_ms",
          "prog.prefill_chunk_ms", "device.idle_share.serve",
          "sched.host_self_ms", "sched.prefill_ahead_share",
          "serve.itl_p95_ms.backlog", "prog.retraces",
          "prog.decode_fallback_builds", "setup.import_s",
          "setup.engine_init_s", "setup.trace_lower_s", "setup.backend_s",
          "setup.programs", "setup.cache_misses", "moe.load_max_over_mean",
          "moe.held_rows_share", "moe_experts_roofline",
          "cache.bytes_per_token", "ssm.state_bytes_per_slot",
          "ssm.state_share_of_step_bytes", "dsa.selected_over_live",
          "dsa.fetched_over_selected",
          "sparse_mla_decode_attention_roofline"]


@pytest.fixture(scope="module")
def g53_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def g53_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def g53_mix():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longgen-backlog.json")) as f:
        return json.load(f)


def test_glm53_s_two_copies_of_the_source_s_keys_agree(g53_conf, g53_spec):
    for key, value in g53_conf["config"].items():
        if key in EXTRA:
            assert key in g53_conf["assumed"], key
            if EXTRA[key]:
                assert value == g53_conf["config"][EXTRA[key]]
        else:
            assert g53_conf[key] == value, key
    assert g53_conf["reduced"] == REDUCED
    assert g53_conf["family"] == "glm5_next" and g53_conf["chips"] == 1
    assert g53_conf["config"]["router_experts"] \
        == g53_conf["published"]["n_routed_experts"] == 288
    # every reading the config leaves open, with the reading it excludes
    for line in ("weights", "kda_low_rank", "kda_gate", "kda_mixer",
                 "index_kpool", "indexer", "no_position_code", "mhc",
                 "swiglu_limit", "multi_token_prediction", "vision_tower"):
        assert line in g53_conf["assumed"], line
    for line in ("kda_low_rank", "kda_gate", "index_kpool", "swiglu_limit"):
        assert "Excluded" in g53_conf["assumed"][line], line
    for key in ("source", "published", "deployment", "bytes", "rehearsal"):
        assert g53_conf[key], key
    assert "each layer shared by 8 chips" in g53_conf["deployment"] \
        and "published layers 2..6" in g53_conf["deployment"]
    entry = next(e for e in g53_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == g53_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_glm53_has_every_key_of_its_catalog_row(g53_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-5.3-Flash")
    assert g53_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert g53_conf["published"][key] == value, key
        else:
            assert g53_conf[key] == value \
                and g53_conf["config"][key] == value, key
    # no width, head count, state size, index_topk, index_kpool, top-k,
    # router width, hc_mult or Sinkhorn count is cut: of the nested group
    # only its two layer lists differ
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_dim", "_rank", "_size", "_heads", "_topk", "_per_tok", "_mult",
         "_iters", "_kpool"))]
    lin, was = g53_conf["linear_attn_config"], row["config"][
        "linear_attn_config"]
    assert {k: v for k, v in lin.items() if not k.endswith("_layers")} \
        == {k: v for k, v in was.items() if not k.endswith("_layers")}


def test_glm53_s_cut_keeps_the_guide_s_floors(g53_conf):
    c, p = g53_conf["config"], g53_conf["published"]
    # published layers 2..6: one leading dense layer, a whole period of four
    # and four layers behind the dense one
    assert c["mlp_layer_types"] == p["mlp_layer_types"][2:7] \
        == ["dense"] + ["sparse"] * 4
    assert c["layer_types"] == p["layer_types"][2:7] == [
        "linear_attention", "deepseek_sparse_attention"] \
        + ["linear_attention"] * 3
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (5, 1)
    assert c["linear_attn_config"]["kda_layers"] == [0, 2, 3, 4] \
        and c["linear_attn_config"]["full_attn_layers"] == [1]
    assert c["n_routed_experts"] == 36 >= 8 and c["router_experts"] == 288
    assert c["vocab_size"] * 8 == p["vocab_size"] == 154880
    assert c["num_nextn_predict_layers"] == 0


def test_glm53_s_family_counts_what_the_issue_counted(g53_conf):
    c = g53_conf["config"]
    n, k = fam.layer_params(c), fam.kinds(c)
    assert k == {"kda": 4, "attention": 1, "dense": 1, "routed": 4,
                 "layers": 5}
    assert round(n["kda"] / 1e6, 1) == 137.6
    assert round(n["attention"] / 1e6, 1) == 117.4
    assert round(n["indexer"] / 1e6, 1) == 6.9
    held = (k["kda"] * n["kda"] + n["attention"] + n["indexer"]
            + 5 * n["mhc"] + n["dense"]
            + 4 * (n["router"] + n["shared"] + 36 * n["expert"])
            + 2 * n["head"])
    assert round(held / 1e9, 3) == 4.718
    assert sum(fam.state_bytes_per_slot(c).values()) == 17367808
    assert fam.cache_bytes_per_token(c) == {"latents": 1024,
                                            "pooled_keys": 64}
    cfg = fam.model_config(c, "bfloat16")
    assert (cfg.mixer_pattern, cfg.index_pattern) == ("KAKKK", "-F---")
    assert cfg.held_experts == 36 and cfg.num_experts == 288
    flops = fam.flops_per_token(c, 4096)
    assert flops["kda"] > flops["attention"] > flops["indexer"] > 0


@pytest.mark.parametrize("key,value", [
    ("qk_rope_head_dim", 64), ("mhc", False),
    ("index_kpool_always_select_tail", False), ("scoring_func", "softmax"),
    ("num_nextn_predict_layers", 1)])
def test_glm53_s_family_refuses_what_it_does_not_run(g53_conf, key, value):
    with pytest.raises(ValueError, match=key):
        fam.check({**g53_conf["config"], key: value})


def test_glm53_s_cell_is_listed_where_it_reports(g53_spec, g53_mix):
    cell = next(w for w in g53_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longgen-backlog", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in g53_spec["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            reader = json.load(f)
        assert {k: reader[k] for k in ("name", "unit", "layer", "moves")} \
            == {k: by_name[name][k] for k in ("name", "unit", "layer",
                                              "moves")}
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    # the pooled score reads a quarter of what dsa_index_score.py counts
    assert CELL not in by_name["dsa_index_score_roofline"]["workloads"]
    assert CELL in next(m for m in g53_spec["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    e = g53_mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_chunk"]) == (160, 8192, 512)
    assert g53_mix["kind"] == "backlog_linear" \
        and g53_mix["requests"] == 1024
    assert g53_mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 64,
        "max": 4096}
    assert g53_mix["answer_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.6, "min": 256,
        "max": 4000}
    # a prompt short enough for the open group to weigh, both sides of a
    # chunk's edge, past index_topk + index_kpool, and a prompt of which
    # every other group is left out
    lens = g53_mix["check_prompt_tokens"]
    assert min(lens) < 32 and any(500 < n < 512 for n in lens) \
        and any(512 < n < 520 for n in lens) and max(lens) > 4000 \
        and any(2052 < n < 2200 for n in lens)
    assert 0.0171 < g53_mix["logit_tolerance"] < 0.0730
    assert g53_mix["check_decode_steps"] >= 8


def test_the_state_step_s_count_on_a_hand_case():
    """160 running slots of 64 heads of 128 x 128: the float32 state in and
    out (2 x 4 MiB a slot) beside q, k, v, the decay and o (5 x 32 KiB) and
    beta; 8 FLOP a state value."""
    (flops, nbytes), = kda_state_step.ops_and_bytes(
        running=160, H=64, D=128).values()
    assert flops == 8.0 * 160 * 64 * 128 * 128
    assert nbytes == 160 * (2 * 4 * 2 ** 20 + (5 * 64 * 128 + 64) * 4)
    # bound by memory: 1.37 GB at 819 GB/s is 1.7 ms, 1.3 GFLOP nothing
    assert nbytes / 819e9 > 100 * flops / 197e12
    assert kda_state_step.calls({"model": {"hidden_size": 4096}}) == {}


def test_the_linear_step_s_least_traffic_on_a_hand_case(g53_conf):
    c = g53_conf["config"]
    parts = linear_step_hbm_share.terms(
        c, fam.layer_params(c), fam.kinds(c), touched=35.5, running=160,
        state_bytes=17367808, selected=160 * 2052, scored=160 * 700)
    gb = {k: round(v / 1e9, 2) for k, v in parts.items()}
    # ISSUE 55's floor: experts 7.2, state 5.4 (5.56 with the tails), other
    # weights 2.0 (1.88 + the head's 0.16), selected latents 0.34
    assert gb == {"weights outside the routed experts": 1.88,
                  "the head": 0.16, "held experts touched": 7.15,
                  "the running slots' state in and out": 5.56,
                  "the selected latents": 0.34,
                  "the indexer keys scored": 0.03}
    assert 18.0 < 1e3 * sum(parts.values()) / 819e9 < 18.6


def test_the_kind_taps_the_engine_s_own_sampler():
    """``tapped``: the function runs as it is, the logits its sampler was
    handed come back beside its result, and the sampler is put back."""
    import types

    from benchmark.kinds.backlog_linear import tapped

    srv = types.SimpleNamespace(_sampler=lambda logits, key: logits + key)
    impl = lambda x: srv._sampler(2 * x, 1)  # noqa: E731
    was = srv._sampler
    assert tapped(srv, impl)(5) == (11, 10) and srv._sampler is was


def test_glm53_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds.backlog_linear import CONTROLS

    assert CONTROLS == ("state-bf16", "sinkhorn-once", "open-group-unread",
                        "max-for-mean", "clamp-dropped", "gate-unbounded",
                        "weights-8bit")
