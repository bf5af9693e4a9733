"""A trunk of a Mamba-2 mixer and rotary GQA attention side by side in every
layer, a gated FFN behind them, a muP multiplier on every branch
(``models/hybrid.py`` kind ``P``, ``inference/kinds/parallel.py``; Falcon-H1)
against the plain reference (``benchmark/reference/falcon_h1.py``):
``apply()``, prefill in chunks + decode through the slots, each branch alone,
a multiplier folded into its weight, the idle row, the sizes, what is refused
— and the controls, each of which has to FAIL the comparison. The tiny preset
keeps what the kernels' layouts turn on: 4 heads a group, a head dim off the
state size, 5 query heads a KV head, every multiplier off 1."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.reference import falcon_h1 as ref
from deepspeed_tpu.inference.decode import (GenCarry, ParallelCache,
                                            cache_bytes_per_token,
                                            forward_with_cache, init_cache,
                                            state_bytes_per_slot)
from deepspeed_tpu.inference.kinds import kind_of
from deepspeed_tpu.models import (MuP, build_model, config_from_hf,
                                  falcon_h1, ssm)
from deepspeed_tpu.serving.scheduler import plan_chunks
from deepspeed_tpu.serving.slots import init_slots, insert_request

F32 = jnp.float32
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published(cfg) -> dict:
    """The reference's keys of a native configuration."""
    m = cfg.mup
    return dict(
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps, mamba_n_heads=cfg.ssm_heads,
        mamba_d_head=cfg.ssm_head_dim, mamba_n_groups=cfg.ssm_groups,
        mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.ssm_conv,
        mamba_d_ssm=cfg.ssm_heads * cfg.ssm_head_dim,
        embedding_multiplier=m.embed, lm_head_multiplier=m.head,
        attention_in_multiplier=m.attn_in, attention_out_multiplier=m.attn_out,
        key_multiplier=m.key, ssm_in_multiplier=m.ssm_in,
        ssm_out_multiplier=m.ssm_out, ssm_multipliers=list(m.ssm),
        mlp_multipliers=[m.mlp_gate, m.mlp_down])


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def engine(model, params, **conf):
    return ds.init_inference(model, params, {"dtype": "float32", **conf},
                             mesh=one_device_mesh())


def tiny(**over):
    return falcon_h1("tiny", dtype=F32, **over)


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def configured():
    ref.configure(published(tiny()))
    yield
    ref.ROUND, ref.WINDOW_CUT = None, 0


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_tiny_preset_keeps_what_the_layouts_turn_on():
    cfg = tiny()
    assert cfg.ssm_heads // cfg.ssm_groups == 4
    assert cfg.ssm_head_dim != cfg.ssm_state
    assert cfg.n_head // cfg.kv_heads == 5
    assert cfg.n_head * cfg.head_dim != cfg.d_model
    flat = [v for f in dataclasses.astuple(cfg.mup)
            for v in (f if isinstance(f, tuple) else (f,))]
    assert len(flat) == 14 and all(v != 1.0 for v in flat)


# ---------------------------------------------------- the whole model
def test_apply_equals_the_reference(served):
    cfg, model, params = served
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 37))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)(params, ids)
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    assert rel(got, want) < 1e-5
    loss = ref.run_highest(ref.loss, params, {"input_ids": jnp.asarray(ids)})
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5


@pytest.mark.parametrize("branch,off", [("attention", "ssm_out"),
                                        ("the mixer", "attn_out")])
def test_each_branch_alone_equals_the_reference_s(served, branch, off):
    """The other branch's out-multiplier 0, in the system and the reference:
    each branch is held to the reference by itself, and parts from the whole
    model by far more than rounding."""
    cfg, model, params = served
    alone = dataclasses.replace(cfg, mup=dataclasses.replace(
        cfg.mup, **{off: 0.0}))
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 29))
    whole = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    ref.configure(published(alone))
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(build_model(alone).apply)(params, ids)
    assert rel(got, want) < 1e-5
    assert rel(whole, want) > 3e-2, branch


FOLDS = {           # multiplier -> (the weights it folds into, along columns)
    "embed": ("tok_embed",), "head": ("lm_head",), "key": ("wk",),
    "attn_in": ("wq", "wk", "wv"), "attn_out": ("wo",), "ssm_in": ("w_in",),
    "ssm_out": ("w_out",), "ssm": ("w_in",), "mlp_gate": ("w_gate",),
    "mlp_down": ("w_down",)}


@pytest.mark.parametrize("name", sorted(FOLDS))
def test_a_multiplier_folded_into_its_weight_equals_it_applied(served, name):
    """Every multiplier scales a product: set to 1 with its weights scaled
    instead, the model computes the same logits (and dropped, set to 1 with
    the weights as they were, it does not)."""
    cfg, model, params = served
    value = getattr(cfg.mup, name)
    one = (1.0,) * 5 if name == "ssm" else 1.0
    folded_cfg = dataclasses.replace(cfg, mup=dataclasses.replace(
        cfg.mup, **{name: one}))
    scale = ssm.in_multipliers(cfg) if name == "ssm" else value

    def fold(tree):
        return {k: fold(v) if isinstance(v, dict)
                else tuple(fold(t) for t in v) if isinstance(v, tuple)
                else v * scale if k in FOLDS[name] else v
                for k, v in tree.items()}

    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 23))
    with jax.default_matmul_precision("highest"):
        applied = jax.jit(model.apply)(params, ids)
        folded = jax.jit(build_model(folded_cfg).apply)(fold(params), ids)
        dropped = jax.jit(build_model(folded_cfg).apply)(params, ids)
    assert rel(folded, applied) < 1e-5
    assert rel(dropped, applied) > 1e-2


# ------------------------------------------------------ through the cache
def through_the_slots(cfg, model, params, prompts, given, chunk, slots,
                      max_len, flash):
    """Per prompt (1 + steps, V) logits: prefill in ``chunk``s into a batch-1
    cache, seated in a slot, ``given`` tokens decoded by the slots' step."""
    seats = [1 + 2 * i for i in range(len(prompts))]
    state = init_slots(cfg, slots, max_len, F32)
    rows = [[] for _ in prompts]
    for i, prompt in enumerate(prompts):
        cache = init_cache(cfg, 1, max_len, F32)
        for ch in plan_chunks(prompt, chunk, overlap=False):
            lg, cache = forward_with_cache(
                model, params, jnp.asarray(ch.ids[None]),
                cache._replace(length=jnp.int32(ch.start)),
                last_token_head=True,
                last_index=jnp.int32(ch.last_index) if ch.final else None)
        cache = cache._replace(length=jnp.int32(len(prompt)))
        rows[i].append(lg[0, 0])
        state = insert_request(state, jnp.int32(seats[i]), GenCarry(
            tok=jnp.zeros((1,), jnp.int32), cache=cache,
            rng=jnp.zeros((1, 2), jnp.uint32), done=jnp.zeros((1,), bool)))
    cache = state.cache
    for t in range(len(given[0])):
        toks = np.zeros(slots, np.int32)
        toks[seats] = [g[t] for g in given]
        lg, cache = forward_with_cache(model, params,
                                       jnp.asarray(toks)[:, None], cache,
                                       flash_decode=flash)
        for i, s in enumerate(seats):
            rows[i].append(lg[s, 0])
    return [jnp.stack(r) for r in rows], cache


def cache_case(cfg, lengths=(5, 32, 29, 46), steps=4):
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    given = [rng.integers(0, cfg.vocab_size, steps).astype(np.int32)
             for _ in lengths]
    return prompts, given


def reference_rows(params, prompts, given):
    out = []
    for prompt, toks in zip(prompts, given):
        n = len(prompt)
        ids = np.concatenate([prompt, toks])[None]
        out.append(np.asarray(ref.run_highest(
            ref.logits, params, jnp.asarray(ids),
            rows=tuple(range(n - 1, n + len(toks)))))[0])
    return out


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_prefill_in_chunks_then_the_slots_step_equal_the_reference(served,
                                                                   flash):
    """Prompts of 5 (one padded bucket), 32 (two whole chunks), 29 (3 behind
    a boundary in a padded bucket) and 46 (2 behind one) in chunks of 16,
    seated between slots at length 0, then 4 given tokens through the slots'
    step: every row against the reference's one full forward."""
    cfg, model, params = served
    prompts, given = cache_case(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 16, 8,
                                   128, flash)
    for g, w in zip(got, reference_rows(params, prompts, given)):
        assert rel(g, w) < 2e-5


@pytest.mark.parametrize("control", ["window dropped at a chunk boundary",
                                     "padding advances the state"])
def test_controls_of_the_cache_path_fail(served, control, monkeypatch):
    cfg, model, params = served
    chunked = ssm.mix_chunk

    def broken(cfg, p, y, S, W, valid=None):
        if control.startswith("window"):
            return chunked(cfg, p, y, S, jnp.zeros_like(W), valid)
        return chunked(cfg, p, y, S, W, None)

    monkeypatch.setattr(ssm, "mix_chunk", broken)
    prompts, given = cache_case(cfg, lengths=(29, 46))
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 16, 8,
                                   128, False)
    worst = max(rel(g, w) for g, w in zip(
        got, reference_rows(params, prompts, given)))
    assert worst > 1e-2, worst


def test_the_reference_s_window_cut_is_the_dropped_window(served,
                                                          monkeypatch):
    """The reference's own control (``WINDOW_CUT``, what the cell's kind
    runs on the chip) computes what a cache path that dropped its window at
    every chunk boundary computes."""
    cfg, model, params = served
    chunked = ssm.mix_chunk
    monkeypatch.setattr(ssm, "mix_chunk", lambda cfg, p, y, S, W, valid=None:
                        chunked(cfg, p, y, S, jnp.zeros_like(W), valid))
    prompts, given = cache_case(cfg, lengths=(46,), steps=0)
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 16, 8,
                                   128, False)
    sound = reference_rows(params, prompts, given)
    ref.WINDOW_CUT = 16
    cut = reference_rows(params, prompts, given)
    assert rel(got[0], cut[0]) < 2e-5 < 1e-2 < rel(got[0], sound[0])


@pytest.mark.parametrize("control", ["key_multiplier", "ssm_multipliers",
                                     "8-bit products"])
def test_controls_of_the_reference_fail(served, control):
    cfg, model, params = served
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 37))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)(params, ids)
    pub = published(cfg)
    if control == "8-bit products":
        ref.ROUND = lambda a: jax.lax.reduce_precision(a, 8, 3)
    else:
        pub[control] = 1.0 if control == "key_multiplier" else [1.0] * 5
    ref.configure(pub)
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    assert rel(got, want) > 1e-2


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_a_row_at_length_0_touches_nothing(served, flash):
    """Whatever the idle slots hold, a running row's buffers come out
    bit-equal; an idle slot's own state and window are untouched, and with
    the kernels its K/V planes too."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, lengths=(21, 7), steps=3)
    _, clean = through_the_slots(cfg, model, params, prompts,
                                 [g[:0] for g in given], 16, 6, 128, flash)
    idle = np.array([0, 2, 4, 5])
    noise = jax.random.normal(jax.random.PRNGKey(8), clean.ssm.shape, F32)
    dirty = clean._replace(
        ssm=clean.ssm.at[:, idle].set(noise[:, idle]),
        conv=clean.conv.at[:, idle].set(1.5),
        k=clean.k.at[:, idle].set(0.5), v=clean.v.at[:, idle].set(-0.5))
    outs = []
    for cache in (clean, dirty):
        for t in range(3):
            toks = np.zeros(6, np.int32)
            toks[[1, 3]] = [g[t] for g in given]
            lg, cache = forward_with_cache(model, params,
                                           jnp.asarray(toks)[:, None], cache,
                                           flash_decode=flash)
        outs.append((lg, cache))
    (lg_a, a), (lg_b, b) = outs
    run = np.array([1, 3])
    assert (np.asarray(lg_a)[run] == np.asarray(lg_b)[run]).all()
    for name in ("k", "v", "ssm", "conv"):
        assert (np.asarray(getattr(a, name))[:, run]
                == np.asarray(getattr(b, name))[:, run]).all(), name
    for name in ("ssm", "conv") + (("k", "v") if flash else ()):
        assert (np.asarray(getattr(b, name))[:, idle]
                == np.asarray(getattr(dirty, name))[:, idle]).all(), name
    assert (np.asarray(b.length) == [0, 24, 0, 10, 0, 0]).all()


def test_served_requests_equal_solo_generate(served):
    """Through ``ServingEngine``: two requests in one slot after each other
    and a third beside them, each equal to solo ``generate()``."""
    cfg, model, params = served
    eng = engine(model, params)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (30, 19, 45)]
    conf = {"slots": 2, "max_len": 128, "prefill_chunk": 16, "greedy": True}
    got = ds.ServingEngine(eng, conf).serve_batch(prompts, [6, 6, 6],
                                                  seeds=[1, 2, 3])
    for p, g, s in zip(prompts, got, (1, 2, 3)):
        solo = np.asarray(eng.generate(p[None], 6, request_seeds=[s],
                                       greedy=True, cache_len=128))[0]
        assert list(g) == list(solo)


# ------------------------------------------------------------ the sizes
def test_the_cache_is_a_plane_and_a_state_in_every_layer():
    cfg = tiny()
    kind = kind_of(cfg)
    assert type(kind).__name__ == "ParallelHybrid" and kind.recurrent
    shapes = init_cache(cfg, 3, 128, F32, (3,))
    assert isinstance(shapes, ParallelCache)
    L, KV, hd = cfg.n_layer, cfg.kv_heads, cfg.head_dim
    assert shapes.k.shape == shapes.v.shape == (L, 3, KV, hd, 128)
    assert shapes.ssm.shape == (L, 3, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state)
    assert shapes.conv.shape == (L, 3, cfg.ssm_conv - 1,
                                 ssm.dims(cfg)["conv"])
    assert cache_bytes_per_token(cfg, F32) == L * 2 * KV * hd * 4
    assert state_bytes_per_slot(cfg, F32) == (shapes.ssm.nbytes
                                              + shapes.conv.nbytes) // 3


def test_the_spans_say_what_a_step_has_to_move(served):
    cfg, model, params = served
    eng = engine(model, params)
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 128,
                                 "prefill_chunk": 16, "greedy": True})
    kind = srv.kind
    sizes = {"cache_bytes_per_token": cache_bytes_per_token(cfg, F32),
             "state_bytes_per_slot": state_bytes_per_slot(cfg, F32)}
    plan = plan_chunks(np.zeros(21, np.int32), 16, overlap=False)
    assert kind.chunk_meta(plan[0]) == {**sizes, "tokens_real": 16,
                                        "tokens_padded": 0}
    assert kind.chunk_meta(plan[1]) == {
        **sizes, "tokens_real": 5, "tokens_padded": plan[1].size - 5}
    layers = sum(a.nbytes for a in jax.tree.leaves(eng.params["layers"]))
    head = eng.params["lm_head"].nbytes
    meta = kind.step_meta([], [], np.array([0, 40, 9]), {1: None, 2: None})
    moved = {"state_bytes_step": 2 * 2 * sizes["state_bytes_per_slot"],
             "kv_bytes_step": 49 * sizes["cache_bytes_per_token"],
             "weight_bytes_step": layers, "head_bytes_step": head}
    # the state one program of ``ssm_state_step`` takes: the tiny trunk's
    # two groups at once; Falcon-H1-34B's one group of 16 heads, 2 MiB
    block = {"ssm_block_bytes":
             cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4}
    assert ssm.step_block_bytes(falcon_h1("34b")) == 2 << 20
    assert meta == {**sizes, **block, "live_positions": 49, **moved,
                    "state_share_of_step_bytes":
                        moved["state_bytes_step"] / sum(moved.values())}
    assert kind.step_meta([], [], None, {}) == {**sizes, **block}


def test_param_count_is_the_models_name():
    cfg = falcon_h1("34b")
    assert 33.5e9 < cfg.param_count() < 33.8e9
    per_layer = cfg._mixer_params_per_layer("P", False)
    assert abs(per_layer - 430.12e6) < 0.1e6
    shapes = jax.eval_shape(build_model(tiny()).init, jax.random.PRNGKey(0))
    named = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up",
             "w_down")
    held = sum(np.prod(shapes["layers"][0][k].shape) for k in named) \
        + shapes["tok_embed"].size + shapes["lm_head"].size
    assert tiny().param_count() == held


def test_the_importer_maps_the_catalog_row():
    import json
    import os

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    hf = row["config"]
    cfg = config_from_hf(hf)
    assert cfg == falcon_h1("34b")
    assert (cfg.n_layer, cfg.d_model, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size, cfg.max_seq) == (
        row["layers"], row["hidden_size"], row["num_attention_heads"],
        row["num_key_value_heads"], row["head_dim"], row["dense_width"],
        row["vocab_size"], row["context_length"])
    assert cfg.mup == MuP(
        embed=hf["embedding_multiplier"], head=hf["lm_head_multiplier"],
        attn_in=hf["attention_in_multiplier"],
        attn_out=hf["attention_out_multiplier"], key=hf["key_multiplier"],
        ssm_in=hf["ssm_in_multiplier"], ssm_out=hf["ssm_out_multiplier"],
        ssm=tuple(hf["ssm_multipliers"]), mlp_gate=hf["mlp_multipliers"][0],
        mlp_down=hf["mlp_multipliers"][1])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.rope_theta) == (
        32, 128, 2, 256, 4, 128, 1e11)
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        config_from_hf({**hf, "mamba_norm_before_gate": True})


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("serving,why", [
    ({"page_size": 16}, "paged pool"),
    ({"page_size": 16, "kv_quant_bits": 8}, "int8 KV"),
    ({"greedy": True, "speculation": {"enabled": True}}, "speculation"),
    ({"host_pool_bytes": 1 << 20, "page_size": 16}, "paged pool"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_with_its_own_words(served, serving, why):
    cfg, model, params = served
    eng = engine(model, params, flash_decode=False)
    with pytest.raises(ValueError, match="side by side.*" + why):
        ds.ServingEngine(eng, {"slots": 2, "max_len": 64,
                               "prefill_chunk": 16, **serving})


def test_weight_quantization_and_a_mesh_are_refused(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="side by side.*quantization"):
        ds.ServingEngine(engine(model, params, quantize=True),
                         {"slots": 2, "max_len": 64, "prefill_chunk": 16})
    if len(jax.devices()) > 1:
        with pytest.raises(ValueError, match="side by side.*mesh"):
            ds.ServingEngine(ds.init_inference(model, params,
                                               {"dtype": "float32"}),
                             {"slots": 2, "max_len": 64, "prefill_chunk": 16})


def test_training_is_refused(served):
    cfg, model, _ = served
    with pytest.raises(ValueError, match="side by side.*served, not trained"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw",
                                     "params": {"lr": 1e-3}}}, model)


def test_what_is_not_the_block_is_refused():
    with pytest.raises(ValueError, match="every layer of its trunk"):
        build_model(tiny(block_pattern="PPM"))
    with pytest.raises(ValueError, match="rotary attention"):
        build_model(tiny(pos_embedding="none"))
    with pytest.raises(ValueError, match="mup"):
        from deepspeed_tpu.models import tiny_test

        build_model(tiny_test(mup=MuP(embed=2.0)))
