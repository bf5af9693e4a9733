"""What PR 29 added to the yardstick, on hand cases: the configuration's two
copies of the source's keys, the family's counts, the kernels' operations and
bytes, and the reducer of the decode step's reads."""

import json
import os

import pytest

from benchmark import reduce as R
from benchmark.kernels import mla_decode_attention, moe_experts
from benchmark.models import deepseek_v3 as fam
from benchmark.reducers import decode_step_hbm_share, program_span
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana-2-30b-a3b-l7.json")) as f:
        return json.load(f)


def test_the_two_copies_of_the_source_s_keys_agree(conf):
    aliases = {"n_head": "num_attention_heads",
               "layer_norm_epsilon": "rms_norm_eps"}
    for key, value in conf["config"].items():
        if key in aliases:
            assert value == conf["config"][aliases[key]]
            assert key in conf["assumed"]
        else:
            assert conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == 7 \
        and conf["published"]["num_hidden_layers"] == 48


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalog_row_but_the_depth(conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in conf["reduced"]:
            assert conf[key] == value and conf["config"][key] == value, key


def test_the_family_counts_the_published_sizes(conf):
    n = fam.layer_params(dict(conf["config"], num_hidden_layers=48))
    beside = n["attention"] + n["router"] + n["shared"]
    assert round(128 * n["expert"] / 1e6, 2) == 603.98
    assert round(beside / 1e6, 2) == 36.04
    assert round((n["attention"] + n["dense"]) / 1e6, 1) == 64.1
    assert round(2 * n["head"] / 1e6, 1) == 525.3
    cfg = fam.model_config(conf["config"], "bfloat16")
    assert cfg.param_count() == 2 * n["head"] + n["attention"] + n["dense"] \
        + 6 * (beside + 128 * n["expert"])
    assert cfg.segments == (("dense", 1), ("moe", 6))
    with pytest.raises(ValueError, match="n_group"):
        fam.model_config(dict(conf["config"], n_group=8), "bfloat16")


def test_kernel_counts_on_hand_cases():
    flops, nbytes = mla_decode_attention.ops_and_bytes(
        live_tokens=1000, slots=2, heads=32, rank=512, rope=64)
    assert flops == 2 * 1000 * 32 * (576 + 512)
    assert nbytes == (1000 * 576 + 2 * 32 * 1088) * 2
    assert mla_decode_attention.append_bytes(slots=2, rank=512, rope=64) \
        == 2 * 576 * 257 * 2
    both = moe_experts.ops_and_bytes(rows=288, touched=100, d=2048, f=768)
    assert both["moe_experts_up"] == (
        2 * 288 * 2048 * 768 * 2, 100 * 2 * 2048 * 768 * 2 + 288 * 2816 * 2)
    assert both["moe_experts_down"][1] == 100 * 768 * 2048 * 2 + 288 * 2816 * 2
    # another family's cell has nothing for them to count
    assert mla_decode_attention.calls({"model": {"n_head": 20},
                                       "decode_live_tokens": [5]}) == {}
    assert moe_experts.calls({"model": {"n_head": 20}}) == {}


def step_span(step, touched):
    return SpanEvent("decode_step", step, step + 0.02, step=step, meta={
        "slots": 48, "experts_touched": touched,
        "cache_bytes_per_token": 8064, "moe_rows_over_routed": 6.4,
        "moe_load_max_over_mean": 4.0})


def test_mean_call_of_the_expert_kernels_weighs_steps_and_chunks(
        conf, monkeypatch):
    evs = [step_span(0, 110.0), step_span(1, 120.0),
           SpanEvent("prefill_chunk", 0.5, 0.6, step=1,
                     meta={"size": 512, "final": False})]
    monkeypatch.setattr(moe_experts, "_spans", lambda: evs)
    got = moe_experts.calls({"model": conf["config"], "slots": 48})
    step = moe_experts.ops_and_bytes(rows=288, touched=115, d=2048, f=768)
    chunk = moe_experts.ops_and_bytes(rows=3072, touched=128, d=2048, f=768)
    for name in step:
        for i in (0, 1):
            assert got[name][i] == pytest.approx(
                (2 * step[name][i] + chunk[name][i]) / 3, rel=1e-9)


def test_decode_step_hbm_share_on_a_hand_case(conf, monkeypatch):
    evs = [step_span(0, 100.0), step_span(1, 120.0)]
    monkeypatch.setattr(decode_step_hbm_share, "_captured", lambda: evs)
    monkeypatch.setattr(decode_step_hbm_share, "program_time",
                        lambda facts, **kw: 20.0)          # ms
    facts = {"family": "deepseek_v3", "model": conf["config"],
             "decode_live_tokens": [100_000, 140_000],
             "peaks": {"hbm_bytes_per_s": 819e9}}
    n = fam.layer_params(conf["config"])
    weights = 2 * (7 * n["attention"] + n["dense"] + n["head"] + 6 * (
        n["router"] + n["shared"] + 110 * n["expert"]))
    least_ms = 1e3 * (weights + 120_000 * 8064) / 819e9
    assert decode_step_hbm_share.reduce(
        facts, program="^jit__step_impl\\(") == pytest.approx(
            100 * least_ms / 20.0)
    assert 9.0 < least_ms < 11.5       # ISSUE 29 reckons 10.6 at 115 touched
    # a program that records no such span, a family without the counts
    monkeypatch.setattr(decode_step_hbm_share, "_captured", lambda: [])
    assert decode_step_hbm_share.reduce(facts, program="x") is None
    assert decode_step_hbm_share.reduce(dict(facts, family="gpt2"),
                                        program="x") is None


def test_a_chunk_s_count_reaches_its_span_through_the_reader(monkeypatch):
    evs = [SpanEvent("prefill_chunk", 0.0, 0.1, step=0,
                     meta={"size": 512, "moe_rows_over_routed": 1.4}),
           SpanEvent("prefill_chunk", 0.2, 0.3, step=1,
                     meta={"size": 512, "moe_rows_over_routed": 1.5}),
           SpanEvent("prefill_chunk", 0.4, 0.5, step=2, meta={"size": 512})]
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    assert program_span.reduce({}, parent="prefill_chunk", statistic="mean",
                               meta="moe_rows_over_routed") \
        == pytest.approx(1.45)
    assert "decode_step_hbm_share" not in R.GENERIC


def test_served_tokens_are_held_to_the_reference_through_their_own_routing(
        conf):
    """``backlog_routed`` at the rehearsal's size: the serving engine's
    ``routing_log`` covers every position of prompt and answer, the served
    tokens are the reference's draws when it follows that routing, and a
    token changed by hand is not."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import types

    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from benchmark.kinds import backlog_routed as kind
    from benchmark.reference import deepseek_v3 as ref
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    published = dict(conf["config"], **conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    params = model.init(jax.random.PRNGKey(3))
    eng = ds.init_inference(model, params, {"dtype": "float32"},
                            mesh=build_mesh(MeshSpec(data=1),
                                            devices=jax.devices()[:1]))
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 128,
                                 "prefill_chunk": 16})
    srv.routing_log = {}
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 37,
                                               dtype=np.int32)
    rid = srv.submit(prompt, 6, seed=5)
    srv.drain()
    toks = np.asarray(srv.pop_result(rid).tokens)
    routing = kind.served_routing(srv.routing_log[rid], 37 + 5)
    assert routing.shape == (2, 1, 42, 2) and (routing >= 0).all()
    cell = types.SimpleNamespace(
        mix={"logit_tolerance": 0.02, "route_gap": 0.01}, reference=ref)
    missed, _ = kind.drawn_from_the_reference(cell, eng, prompt, toks,
                                              srv.routing_log[rid], 5)
    assert missed == 0
    wrong = toks.copy()
    wrong[3] = (toks[3] + 1) % cfg.vocab_size
    missed, _ = kind.drawn_from_the_reference(cell, eng, prompt, wrong,
                                              srv.routing_log[rid], 5)
    assert missed >= 1
    with pytest.raises(ValueError, match="uncovered"):
        kind.served_routing(srv.routing_log[rid][1:], 42)
    srv.close()
