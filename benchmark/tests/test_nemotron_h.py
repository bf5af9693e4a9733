"""What PR 37 added to the yardstick, on hand cases: the NemotronH
configuration against its catalog row and its two copies of the source's
keys, the family's counts and refusals, where the cell is listed, the new
reducer and the new kernels' counts, and the kind's comparisons with their
controls (each of which has to fail) at a small size."""

import json
import os
import types

import pytest

from benchmark.kernels import latent_experts, ssm_state_step
from benchmark.models import nemotron_h as fam
from benchmark.reducers import hybrid_step_hbm_share, program_span
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "nemotron-3-super-l11-e128"
CELL = NAME + ".serve-backlog-think"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
           "vocab_size"]
EXTRA = {"n_head": "num_attention_heads", "n_embd": "hidden_size",
         "router_experts": None, "first_expert_held": None}


@pytest.fixture(scope="module")
def nh_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def nh_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_nemotron_s_two_copies_of_the_source_s_keys_agree(nh_conf, nh_spec):
    for key, value in nh_conf["config"].items():
        if key in EXTRA:
            assert key in nh_conf["assumed"], key
            if EXTRA[key]:
                assert value == nh_conf["config"][EXTRA[key]]
        else:
            assert nh_conf[key] == value, key
    assert nh_conf["reduced"] == REDUCED and nh_conf["family"] == "nemotron_h"
    assert nh_conf["published"]["num_hidden_layers"] == 88
    assert nh_conf["published"]["n_routed_experts"] \
        == nh_conf["config"]["router_experts"] == 512
    assert nh_conf["published"]["vocab_size"] == 131072
    assert nh_conf["config"]["hybrid_override_pattern"] \
        == nh_conf["published"]["hybrid_override_pattern"][:11]
    # every line of the equations that config.json does not carry
    for line in ("mamba_layout", "mamba_init", "ssm_state_dtype",
                 "attention_position_code", "experts",
                 "e_score_correction_bias", "multi_token_prediction",
                 "weights"):
        assert line in nh_conf["assumed"], line
    for key in ("deployment", "bytes"):
        assert nh_conf[key], key
    entry = next(c for c in nh_spec["configs"] if c["name"] == NAME)
    assert entry["source"] == nh_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_nemotron_has_every_key_of_its_catalog_row(nh_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert nh_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert nh_conf["published"][key] == value, key
        else:
            assert nh_conf[key] == value \
                and nh_conf["config"][key] == value, key
    # no width is among the keys cut (the guide's rule)
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_cut_keeps_the_guide_s_floors(nh_conf):
    c, pub = nh_conf["config"], nh_conf["published"]
    whole = pub["hybrid_override_pattern"]
    assert c["num_hidden_layers"] == len(c["hybrid_override_pattern"]) == 11
    # a whole period (MEMEMEM*E) and the published ratio 40 : 40 : 8
    assert c["hybrid_override_pattern"].startswith(whole[:9])
    ratio = [c["hybrid_override_pattern"].count(k) for k in "ME*"]
    assert ratio == [5, 5, 1] and [whole.count(k) for k in "ME*"] \
        == [8 * r for r in ratio]
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 >= 131072
    assert c["router_experts"] == 4 * c["n_routed_experts"]
    assert c["vocab_size"] * 4 == pub["vocab_size"]


def test_the_new_cell_is_listed_where_its_readers_find_something(nh_spec):
    cell = next(w for w in nh_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "think-backlog", 1)
    listed = {m["name"] for m in nh_spec["per_layer"] + nh_spec["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "serve_tokens_per_s", "setup_s", "prog.decode_step_ms",
        "prog.prefill_chunk_ms", "sched.decode_gap_ms", "sched.host_self_ms",
        "device.idle_share.serve", "prog.retraces",
        "prog.decode_fallback_builds", "serve.itl_p95_ms.backlog",
        "attn.fetched_over_live", "cache.append_moved_over_new",
        "cache.bytes_per_token", "moe.load_max_over_mean",
        "hybrid.decode_step_hbm_share", "ssm_state_step_roofline",
        "latent_experts_roofline", "moe.held_rows_share",
        "ssm.state_bytes_per_slot"}
    # NOT decode_attention_roofline: its count reads n_head for K/V, 16
    # times this model's 2 KV heads; nor Kanana's keys; nor the two that
    # call an iteration over twice the median a stall: here that is every
    # iteration with a chunk before its step (one in four)
    assert not listed & {"decode_attention_roofline", "moe_experts_roofline",
                         "prog.decode_step_hbm_share", "moe.rows_over_routed",
                         "host.stall_ms", "serve.tokens_per_s_less_stalls"}
    new = [m for m in nh_spec["per_layer"] if m.get("workloads") == [CELL]]
    assert all(m["moves"] == "serve_tokens_per_s" for m in new)
    assert [m["name"] for m in nh_spec["per_layer"][-5:]] \
        == [m["name"] for m in new]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "think-backlog.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "backlog_hybrid"
    assert mix["engine"] == {"slots": 64, "max_len": 6144,
                             "prefill_chunk": 512}
    # ISSUE 37's three (a short one, one ending on a bucket of 128, one on
    # whole chunks), and two that end 3 and 2 tokens behind a chunk boundary
    # in a padded bucket: what the window and padding controls need
    assert mix["check_prompt_tokens"] == [24, 640, 1536, 515, 1538]
    # a last chunk right-padded behind the longest prompt still fits
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        <= mix["engine"]["max_len"]
    assert max(mix["check_prompt_tokens"]) + mix["check_decode_steps"] \
        <= mix["engine"]["max_len"]


def test_nemotron_s_family_counts_the_published_sizes(nh_conf):
    n = fam.layer_params(nh_conf["config"])
    assert [round(n[k] / 1e6, 2) for k in
            ("mamba", "attention", "experts_other", "expert", "head")] \
        == [109.58, 35.65, 54.53, 5.51, 134.22]
    cfg = fam.model_config(nh_conf["config"], "bfloat16")
    held = 5 * n["mamba"] + n["attention"] + 5 * (
        n["experts_other"] + 128 * n["expert"]) + 2 * n["head"]
    assert cfg.param_count() == held and round(held * 2 / 1e9, 2) == 9.30
    assert (cfg.block_pattern, cfg.num_experts, cfg.held_experts,
            cfg.moe_top_k, cfg.moe_latent_dim, cfg.kv_heads, cfg.head_dim,
            cfg.pos_embedding) == ("MEMEMEM*EME", 512, 128, 22, 1024, 2, 128,
                                   "none")
    whole = fam.model_config(dict(
        nh_conf["config"], **{k: nh_conf["published"][k] for k in REDUCED},
        router_experts=512), "bfloat16")
    assert round(whole.param_count() / 1e9, 2) == 120.67
    assert round(whole.param_count(active_only=True) / 1e9, 2) == 12.77


@pytest.mark.parametrize("key, other", [
    ("mlp_hidden_act", "silu"), ("n_group", 2), ("use_conv_bias", False),
    ("tie_word_embeddings", True), ("n_shared_experts", 2),
    ("hybrid_override_pattern", "MEM"), ("head_dim", 64), ("n_head", 16),
    ("expand", 4), ("sliding_window", 4096)])
def test_nemotron_s_family_refuses_what_it_runs_one_value_of(nh_conf, key,
                                                             other):
    with pytest.raises(ValueError, match=key):
        fam.model_config(dict(nh_conf["config"], **{key: other}), "bfloat16")


# ------------------------------------------------ reducers and kernel counts
def step_span(step, running, touched=120.0, held=352.0):
    return SpanEvent("decode_step", step, step + 0.02, step=step, meta={
        "slots": running, "cache_bytes_per_token": 1024,
        "state_bytes_per_slot": 21278720, "experts_touched": touched,
        "held_rows": held, "held_rows_share": held / (64 * 22)})


def test_hybrid_step_hbm_share_on_a_hand_case(nh_conf, monkeypatch):
    evs = [step_span(0, 64), step_span(1, 60, touched=116.0)]
    monkeypatch.setattr(hybrid_step_hbm_share, "_captured", lambda: evs)
    monkeypatch.setattr(hybrid_step_hbm_share, "program_time",
                        lambda facts, **kw: 20.0)           # ms
    facts = {"family": "nemotron_h", "model": nh_conf["config"],
             "decode_live_tokens": [90000, 110000],
             "peaks": {"hbm_bytes_per_s": 819e9}}
    n = fam.layer_params(nh_conf["config"])
    other = (5 * n["mamba"] + n["attention"] + 5 * n["experts_other"]) * 2
    moved = other + 2 * n["head"] + 5 * 118 * n["expert"] * 2 \
        + 2 * 62 * 21278720 + 100000 * 1024
    got = hybrid_step_hbm_share.reduce(facts, program="^jit__step_impl\\(")
    assert got == pytest.approx(100 * 1e3 * moved / 819e9 / 20.0)
    assert 50 < got < 100
    assert any("1.712 GB" in note and "the head 0.268 GB" in note
               and "2.639 GB" in note and "K/V 0.102 GB" in note
               for note in facts["notes"]), facts["notes"]
    # a program that records no such span (the parent), another family
    monkeypatch.setattr(hybrid_step_hbm_share, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert hybrid_step_hbm_share.reduce(facts, program="x") is None
    monkeypatch.setattr(hybrid_step_hbm_share, "_captured", lambda: evs)
    assert hybrid_step_hbm_share.reduce(dict(facts, family="gpt2"),
                                        program="x") is None
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="held_rows_share") == 0.25
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="state_bytes_per_slot") == 21278720


def test_the_new_kernels_counts_on_hand_cases(nh_conf, monkeypatch):
    chunk = SpanEvent("prefill_chunk", 2, 2.05, step=2, meta={
        "size": 512, "held_rows": 2816.0, "experts_touched": 128.0})
    evs = [step_span(0, 64), step_span(1, 60), chunk]
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    facts = {"model": nh_conf["config"]}
    got = ssm_state_step.calls(facts)["ssm_state_step"]
    state = 128 * 64 * 128 * 4
    assert got[1] == 62 * (2 * state + 8 * (2 * 64 * 128 + 2 * 128) * 4)
    assert got[0] == 5.0 * 62 * 128 * 64 * 128
    assert got[1] / 819e9 > 100 * got[0] / 197e12          # memory-bound
    up, down = (latent_experts.calls(facts)[k] for k in
                ("latent_experts_up", "latent_experts_down"))
    rows, touched = (2 * 352 + 2816) / 3, (2 * 120 + 128) / 3
    assert up[0] == down[0] == pytest.approx(2 * rows * 1024 * 2688)
    assert up[1] == down[1] == pytest.approx(
        touched * 1024 * 2688 * 2 + rows * (1024 + 2688) * 2)
    # another family's model, a program without the spans: nothing to read
    assert latent_experts.calls({"model": {"n_embd": 1280}}) == {}
    assert ssm_state_step.calls({"model": {"n_embd": 1280}}) == {}
    monkeypatch.setattr(program_span, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert latent_experts.calls(facts) == {} \
        and ssm_state_step.calls(facts) == {}


# ------------------------------------------- the kind's own comparisons
@pytest.fixture(scope="module")
def nh_small(nh_conf):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from benchmark.reference import nemotron_h as ref
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    published = dict(nh_conf["config"], **nh_conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    params = model.init(jax.random.PRNGKey(3))
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    cell = types.SimpleNamespace(
        seed=11, reference=ref, published=published,
        mix={"engine": {"slots": 8, "max_len": 128, "prefill_chunk": 16},
             "check_prompt_tokens": [9, 24, 48, 21, 50],
             "check_decode_steps": 4,
             "logit_tolerance": 1e-4, "route_gap": 1e-6})
    return cfg, model, params, mesh, cell


def nh_engine(nh_small, params=None):
    import deepspeed_tpu as ds

    _, model, own, mesh, _ = nh_small
    return ds.init_inference(model, params or own, {"dtype": "float32"},
                             mesh=mesh)


def test_both_comparisons_pass_on_the_system(nh_small):
    from benchmark.kinds import backlog_hybrid as kind

    cfg, _, params, _, cell = nh_small
    notes: list = []
    assert kind.check_logits(cell, cfg, params, nh_engine(nh_small), notes)
    assert sum("through the cache, prompt" in n for n in notes) == 5
    assert sum("last-position logits" in n for n in notes) == 5
    # 8 slots: one idle with a predecessor's state in it, the 5 prompts in 7
    assert sum("seated in 2 slots" in n for n in notes) == 2
    assert all("idle slots bit-equal: True" in n for n in notes
               if "through the cache" in n)
    assert not any("OUTSIDE" in n or "NOT" in n for n in notes), notes


@pytest.mark.parametrize("control", ["window dropped at a chunk boundary",
                                     "padding advances the state"])
def test_a_broken_hand_over_fails_the_cache_path_alone(nh_small, control,
                                                       monkeypatch):
    from benchmark.kinds import backlog_hybrid as kind
    from deepspeed_tpu.models import ssm

    chunked = ssm.mix_chunk

    def broken(cfg, p, y, S, W, valid=None):
        import jax.numpy as jnp

        if valid is None and y.shape[1] > 16:      # apply(): the whole prompt
            return chunked(cfg, p, y, S, W, valid)
        if control.startswith("window"):
            return chunked(cfg, p, y, S, jnp.zeros_like(W), valid)
        return chunked(cfg, p, y, S, W, None)

    cfg, _, params, _, cell = nh_small
    cell = types.SimpleNamespace(**{**vars(cell), "mix": dict(
        cell.mix, check_prompt_tokens=[21, 50])})
    monkeypatch.setattr(ssm, "mix_chunk", broken)
    notes: list = []
    assert not kind.check_logits(cell, cfg, params, nh_engine(nh_small), notes)
    for n in notes:
        assert ("OUTSIDE" in n) == ("through the cache" in n), n


def state_in_bf16(monkeypatch):
    """The recurrent state held in bfloat16 where the configuration states
    float32: rounded wherever a program hands it on (``reduce_precision``: a
    convert pair is folded away on the TPU)."""
    import jax

    from deepspeed_tpu.models import ssm

    chunked, stepped = ssm.mix_chunk, ssm.mix_step

    def chunk(*a, **kw):
        out, S, W = chunked(*a, **kw)
        return out, jax.lax.reduce_precision(S, 8, 7), W

    def step(*a, **kw):
        out, S, W = stepped(*a, **kw)
        return out, jax.lax.reduce_precision(S, 8, 7), W

    monkeypatch.setattr(ssm, "mix_chunk", chunk)
    monkeypatch.setattr(ssm, "mix_step", step)


def test_the_state_in_bf16_fails_the_cache_path_alone(nh_small, monkeypatch):
    from benchmark.kinds import backlog_hybrid as kind

    cfg, _, params, _, cell = nh_small
    state_in_bf16(monkeypatch)
    notes: list = []
    assert not kind.check_logits(cell, cfg, params, nh_engine(nh_small), notes)
    # the forward keeps no state between programs; of the cache path's
    # prompts most, not all, part by over 1e-4: the state's last bits move a
    # logit little (on the chip, in bf16, this control reads inside the
    # limit: the mix's logit_tolerance_why)
    outside = [("through the cache" in n, "OUTSIDE" in n) for n in notes]
    assert (False, True) not in outside and outside.count((True, True)) >= 3
    # rounded at every hand-over, an idle slot's state stays what it was
    assert all("idle slots bit-equal: True" in n for n in notes
               if "through the cache" in n)


def test_an_idle_slot_stepped_like_a_running_one_fails(nh_small, monkeypatch):
    import jax.numpy as jnp

    from benchmark.kinds import backlog_hybrid as kind
    from deepspeed_tpu.models import ssm

    stepped = ssm.mix_step

    def step(cfg, p, y, S, W, layer, length, fused):
        return stepped(cfg, p, y, S, W, layer, jnp.maximum(length, 1), fused)

    cfg, _, params, _, cell = nh_small
    monkeypatch.setattr(ssm, "mix_step", step)
    notes: list = []
    assert not kind.check_logits(cell, cfg, params, nh_engine(nh_small), notes)
    assert any("did NOT come out of the steps bit-equal" in n for n in notes)
    # the running slots' rows are what they were
    assert not any("OUTSIDE" in n for n in notes), notes


def test_the_selection_bias_dropped_fails_both(nh_small):
    import jax.numpy as jnp

    from benchmark.kinds import backlog_hybrid as kind

    cfg, _, params, _, cell = nh_small
    dropped = {**params, "layers": tuple(
        {**seg, "router_bias": jnp.zeros_like(seg["router_bias"])}
        if "router_bias" in seg else seg for seg in params["layers"])}
    notes: list = []
    assert not kind.check_logits(cell, cfg, params,
                                 nh_engine(nh_small, dropped), notes)
    assert sum("OUTSIDE" in n for n in notes) >= 4, notes


def test_expert_operands_in_8_bits_fail_both(nh_small):
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import backlog_hybrid as kind

    def e4m3(w):
        s = jnp.abs(w).max() / 240.0
        return jax.lax.reduce_precision(w / s, 4, 3) * s

    cfg, _, params, _, cell = nh_small
    rounded = {**params, "layers": tuple(
        {**seg, "w1": e4m3(seg["w1"]), "w2": e4m3(seg["w2"])}
        if "w1" in seg else seg for seg in params["layers"])}
    notes: list = []
    assert not kind.check_logits(cell, cfg, params,
                                 nh_engine(nh_small, rounded), notes)
    assert all("OUTSIDE" in n for n in notes), notes
