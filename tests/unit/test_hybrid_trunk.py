"""A trunk of one mixer a layer (``models/hybrid.py``: Mamba-2 | latent
experts | attention) against the plain reference
(``benchmark/reference/nemotron_h.py``): the three Mamba-2 forms, ``apply()``,
prefill in chunks + decode through the slots, the experts' shares, the idle
row, the re-seated slot, the parameter count, what is refused — and the
controls, each of which has to FAIL the comparison."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.reference import nemotron_h as ref
from deepspeed_tpu.inference.decode import (GenCarry, HybridCache,
                                            cache_bytes_per_token,
                                            forward_with_cache, init_cache,
                                            state_bytes_per_slot)
from deepspeed_tpu.models import build_model, nemotron_h, ssm
from deepspeed_tpu.serving.scheduler import plan_chunks
from deepspeed_tpu.serving.slots import init_slots, insert_request

PUB = dict(mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
           ssm_state_size=16, conv_kernel=4, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, layer_norm_epsilon=1e-5,
           num_experts_per_tok=4, routed_scaling_factor=2.5)
F32 = jnp.float32


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def engine(model, params, **conf):
    return ds.init_inference(model, params, {"dtype": "float32", **conf},
                             mesh=one_device_mesh())


def tiny(**over):
    return nemotron_h("tiny", dtype=F32, **over)


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ref.configure(PUB)
    return cfg, model, params


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def mamba_layer(params):
    return jax.tree.map(lambda a: a[0], params["layers"][0])


def mamba_ref(p, y, state=None):
    with jax.default_matmul_precision("highest"):
        return ref.mamba(y, jax.tree.map(lambda a: jnp.asarray(a, F32), p),
                         dict(PUB), state)


# ------------------------------------------------------ the three forms
@pytest.mark.parametrize("block", [4, 8, 7, 64],
                         ids=lambda b: f"scan blocks of {b}")
def test_whole_sequence_equals_the_recurrence(served, block):
    """37 tokens from an empty state: blocks that divide the sequence, do
    not, and one block longer than it."""
    cfg, _, params = served
    cfg = dataclasses.replace(cfg, ssm_chunk=block)
    p = mamba_layer(params)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 37, cfg.d_model), F32)
    want, (S, W) = mamba_ref(p, y)
    empty = {n: jnp.zeros(s, F32) for n, s in ssm.state_shapes(cfg, 2).items()}
    with jax.default_matmul_precision("highest"):
        got, S2, W2 = ssm.mix_chunk(cfg, p, y, empty["ssm"], empty["conv"])
    assert rel(got, want) < 1e-5 and rel(S2, S) < 1e-5
    assert rel(W2, W) < 1e-6


@pytest.mark.parametrize("sizes,valid", [
    ([16, 16, 5], 5), ([16, 16, 8], 5), ([16, 16, 8], 1), ([8, 32], 29),
    ([64], 37)], ids=lambda v: str(v))
def test_chunks_hand_their_state_over_and_stop_at_the_true_length(
        served, sizes, valid):
    """A prompt in chunks, the last one a bucket padded behind ``valid``
    real tokens (with garbage): output and both states as the recurrence
    leaves them after the last REAL token."""
    cfg, _, params = served
    p = mamba_layer(params)
    real = sum(sizes[:-1]) + valid
    y = jax.random.normal(jax.random.PRNGKey(2), (1, sum(sizes), cfg.d_model),
                          F32)
    want, (S, W) = mamba_ref(p, y[:, :real])
    state = {n: jnp.zeros(s, F32) for n, s in ssm.state_shapes(cfg, 1).items()}
    S2, W2, outs, at = state["ssm"], state["conv"], [], 0
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate(sizes):
            last = i == len(sizes) - 1
            out, S2, W2 = ssm.mix_chunk(cfg, p, y[:, at:at + n], S2, W2,
                                        jnp.int32(valid) if last else None)
            outs.append(out[:, :valid] if last else out)
            at += n
    assert rel(jnp.concatenate(outs, 1), want) < 1e-5
    assert rel(S2, S) < 1e-5 and rel(W2, W) < 1e-6


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "kernel"])
def test_one_token_steps_equal_the_recurrence(served, fused):
    cfg, _, params = served
    p = mamba_layer(params)
    B, T = 3, 11
    y = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.d_model), F32)
    want, (S, W) = mamba_ref(p, y)
    shapes = ssm.state_shapes(cfg, B)
    S2 = jnp.zeros((2,) + shapes["ssm"], F32)        # layer 1 of two
    W2 = jnp.zeros((2,) + shapes["conv"], F32)
    outs = []
    with jax.default_matmul_precision("highest"):
        for t in range(T):
            out, S2, W2 = ssm.mix_step(cfg, p, y[:, t:t + 1], S2, W2,
                                       jnp.int32(1), jnp.full((B,), t + 1),
                                       fused)
            outs.append(out)
    assert rel(jnp.concatenate(outs, 1), want) < 1e-5
    assert rel(S2[1], S) < 1e-5 and rel(W2[1], W) < 1e-6
    assert not S2[0].any() and not W2[0].any()       # the other layer


# ---------------------------------------------------- the whole model
def test_apply_equals_the_reference(served):
    cfg, model, params = served
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 37))
    with jax.default_matmul_precision("highest"):
        got, routing = jax.jit(lambda p, i: model.apply(
            p, i, return_aux=True))(params, ids)
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    assert rel(got, want) < 1e-5
    assert routing.shape == (2, 2, 37, cfg.moe_top_k)


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


def cache_case(cfg, lengths=(5, 21, 33), steps=4):
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
    """Prompts of 5 (one padded bucket), 21 (a chunk, then a padded bucket)
    and 33 (two chunks and one real token) in chunks of 16, seated between
    slots at length 0, then 4 given tokens through the slots' step: every
    row against the reference's one full forward."""
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
    """The conv window not carried across a chunk boundary; a bucket's
    padding advancing the state: each parts from the reference by orders of
    magnitude more than the path itself does."""
    cfg, model, params = served
    chunked = ssm.mix_chunk

    def broken(cfg, p, y, S, W, valid=None):
        if control.startswith("window"):
            return chunked(cfg, p, y, S, jnp.zeros_like(W), valid)
        return chunked(cfg, p, y, S, W, None)

    monkeypatch.setattr(ssm, "mix_chunk", broken)
    prompts, given = cache_case(cfg, lengths=(21, 35))
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_slots(cfg, model, params, prompts, given, 16, 8,
                                   128, False)
    worst = max(rel(g, w) for g, w in zip(
        got, reference_rows(params, prompts, given)))
    assert worst > 1e-2, worst


def test_selection_bias_dropped_fails(served):
    cfg, model, params = served
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 37))
    dropped = {**params, "layers": tuple(
        {**seg, "router_bias": jnp.zeros_like(seg["router_bias"])}
        if "router_bias" in seg else seg for seg in params["layers"])}
    with jax.default_matmul_precision("highest"):
        got = model.apply(dropped, ids)
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    assert rel(got, want) > 1e-2


def test_expert_operands_in_8_bits_fail(served):
    """The experts' matrices rounded to 4 exponent and 3 mantissa bits under one scale a tensor: a
    thousand times the path's own distance from the reference."""
    cfg, model, params = served
    ids = np.random.default_rng(6).integers(0, cfg.vocab_size, (2, 37))

    def e4m3(w):
        s = jnp.abs(w).max() / 240.0
        return jax.lax.reduce_precision(w / s, 4, 3) * s

    rounded = {**params, "layers": tuple(
        {**seg, "w1": e4m3(seg["w1"]), "w2": e4m3(seg["w2"])}
        if "w1" in seg else seg for seg in params["layers"])}
    with jax.default_matmul_precision("highest"):
        got = model.apply(rounded, ids)
    want = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    assert rel(got, want) > 1e-3


# ----------------------------------------------------------- the shares
def test_the_shares_add_up_to_the_whole_layer(served):
    """Four shares of 4 experts (each through W_dn and W_up, which are
    linear around the sum) plus the shared expert counted once equal the
    uncut layer of 16 — in the program and in the reference."""
    cfg, model, params = served
    p = jax.tree.map(lambda a: a[0], params["layers"][1])          # an E
    y = jax.random.normal(jax.random.PRNGKey(7), (2, 9, cfg.d_model), F32)
    yt = y.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        whole, stats, idx = model.latent_experts(y, p)
        shared = jnp.square(jax.nn.relu(yt @ p["ws_in"])) @ p["ws_out"]
        parts, held_rows = [], 0.0
        for j in range(4):
            share = dataclasses.replace(cfg, moe_experts_held=4,
                                        moe_first_held=4 * j,
                                        moe_shared_d_ff=0)
            pj = {k: v for k, v in p.items() if not k.startswith("ws_")}
            pj.update(w1=p["w1"][4 * j:4 * j + 4], w2=p["w2"][4 * j:4 * j + 4])
            out, st, idx_j = build_model(share).latent_experts(y, pj)
            assert (idx_j == idx).all()          # every share routes alike
            parts.append(out.reshape(-1, cfg.d_model))
            held_rows += float(st[3])
            ref.configure(PUB, first_held=4 * j)
            want_j, _ = ref.experts(yt, pj, ref.PUBLISHED, shared=False)
            assert rel(parts[-1], want_j) < 1e-5
        ref.configure(PUB)
    assert held_rows == yt.shape[0] * cfg.moe_top_k == float(stats[3])
    assert rel(sum(parts) + shared, whole.reshape(-1, cfg.d_model)) < 1e-5


# ------------------------------------------- idle rows, re-seated slots
@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_a_row_at_length_0_touches_nothing(served, flash):
    """Whatever the idle slots hold, a running row's buffers come out
    bit-equal; an idle slot's own state is untouched."""
    cfg, model, params = served
    prompts, given = cache_case(cfg, lengths=(21, 7), steps=3)
    _, clean = through_the_slots(cfg, model, params, prompts,
                                 [g[:0] for g in given], 16, 6, 128, flash)
    idle = np.array([0, 2, 4, 5])
    noise = jax.random.normal(jax.random.PRNGKey(8), clean.ssm.shape, F32)
    dirty = clean._replace(
        ssm=clean.ssm.at[:, idle].set(noise[:, idle]),
        conv=clean.conv.at[:, idle].set(1.5))
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
    assert (np.asarray(b.ssm)[:, idle] == np.asarray(dirty.ssm)[:, idle]).all()
    assert (np.asarray(b.conv)[:, idle]
            == np.asarray(dirty.conv)[:, idle]).all()
    assert (np.asarray(b.length) == [0, 24, 0, 10, 0, 0]).all()


def test_a_reseated_slot_never_reads_its_predecessors_state(served):
    """One slot: a request served after another equals the same request
    served first (and both equal solo ``generate()``)."""
    cfg, model, params = served
    eng = engine(model, params)
    rng = np.random.default_rng(9)
    a, b = (rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (30, 19))
    conf = {"slots": 1, "max_len": 64, "prefill_chunk": 16, "greedy": True}
    after = ds.ServingEngine(eng, conf).serve_batch([a, b], [6, 6],
                                                    seeds=[1, 2])[1]
    first = ds.ServingEngine(eng, conf).serve_batch([b], [6], seeds=[2])[0]
    solo = np.asarray(eng.generate(b[None], 6, request_seeds=[2], greedy=True,
                                   cache_len=64))[0]
    assert list(after) == list(first) == list(solo)


def test_the_spans_carry_the_cache_and_state_counts(served):
    cfg, model, params = served
    eng = engine(model, params)
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 64,
                                 "prefill_chunk": 16, "greedy": True})
    meta = srv.kind.chunk_meta(None)
    assert meta == {"cache_bytes_per_token": cache_bytes_per_token(cfg, F32),
                    "state_bytes_per_slot": state_bytes_per_slot(cfg, F32)}
    counts = srv.kind.step_meta([np.array([[3., 4., 16., 7.],
                                           [2., 3., 8., 5.]])], [], None, [])
    assert counts["held_rows"] == 6.0 and counts["experts_touched"] == 3.5
    assert counts["held_rows_share"] == 6.0 / (3 * cfg.moe_top_k)
    # the state one program of the step's kernel takes: both of the tiny
    # trunk's groups (2 x 4 heads of 16 x 16 float32), far under 2 MiB
    assert counts["ssm_block_bytes"] == ssm.step_block_bytes(cfg) \
        == cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    # no field that nothing reads, none the host could only assert
    assert set(counts) == set(meta) | {"held_rows", "held_rows_share",
                                       "experts_touched",
                                       "moe_load_max_over_mean",
                                       "ssm_block_bytes"}


# ------------------------------------------------------------ the sizes
def test_the_cache_is_planes_for_attention_and_a_state_a_slot():
    cfg = nemotron_h(block_pattern="MEMEMEM*EME", n_layer=11,
                     moe_experts_held=128, vocab_size=32768)
    assert cache_bytes_per_token(cfg) == 1024            # 1 plane, 2 KV heads
    assert state_bytes_per_slot(cfg) == 5 * (128 * 64 * 128 * 4
                                             + 3 * 10240 * 2)
    shapes = jax.eval_shape(lambda: init_cache(cfg, 2, 256))
    assert isinstance(shapes, HybridCache)
    assert shapes.k.shape == (1, 2, 2, 128, 256)
    assert shapes.ssm.shape == (5, 2, 128, 64, 128) and shapes.ssm.dtype == F32
    assert shapes.conv.shape == (5, 2, 3, 10240)


def test_param_count_is_the_models_name():
    cfg = nemotron_h()
    assert len(cfg.block_pattern) == 88
    assert [cfg.block_pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert round(cfg.param_count() / 1e9, 2) == 120.67
    assert round(cfg.param_count(active_only=True) / 1e9, 2) == 12.77
    cut = dataclasses.replace(cfg, block_pattern="MEMEMEM*EME", n_layer=11,
                              moe_experts_held=128, vocab_size=32768)
    assert round(cut.param_count() * 2 / 1e9, 2) == 9.30      # bf16 GB held


def test_plans_for_a_recurrent_state_never_rewind():
    for n in (5, 16, 21, 33, 100):
        prompt = np.arange(n, dtype=np.int32)
        free, kept = (plan_chunks(prompt, 16, overlap=o)
                      for o in (True, False))
        assert [c.size for c in free] == [c.size for c in kept]
        at = 0
        for c in kept:
            assert c.start == at
            at += c.size if not c.final else c.last_index + 1
        assert at == n and kept[-1].true_len == n


# ------------------------------------------------------------- refused
@pytest.mark.parametrize("serving,why", [
    ({"page_size": 16}, "paged pool"),
    ({"page_size": 16, "kv_quant_bits": 8}, "int8 KV"),
    ({"greedy": True, "speculation": {"enabled": True}}, "speculation"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_with_these_block_kinds(served, serving, why):
    cfg, model, params = served
    eng = engine(model, params, flash_decode=False)
    with pytest.raises(ValueError, match="one mixer a layer"):
        ds.ServingEngine(eng, {"slots": 2, "max_len": 64,
                               "prefill_chunk": 16, **serving})


def test_weight_quantization_is_refused(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="one mixer a layer"):
        ds.ServingEngine(engine(model, params, quantize=True),
                         {"slots": 2, "max_len": 64, "prefill_chunk": 16})


def test_a_mesh_of_several_devices_is_refused(served):
    cfg, model, params = served
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="one mixer a layer"):
        ds.ServingEngine(ds.init_inference(model, params,
                                           {"dtype": "float32"}),
                         {"slots": 2, "max_len": 64, "prefill_chunk": 16})


def test_training_is_refused(served):
    cfg, model, _ = served
    with pytest.raises(ValueError, match="served, not trained"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw",
                                     "params": {"lr": 1e-3}}}, model)


def test_a_pattern_has_to_name_every_layer():
    with pytest.raises(ValueError, match="block_pattern"):
        build_model(tiny(block_pattern="MEX", n_layer=3))
    with pytest.raises(ValueError, match="hold"):
        build_model(tiny(moe_experts_held=4, moe_first_held=2))


def test_other_families_import_none_of_the_new_modules():
    """Nothing a configuration without a ``block_pattern`` builds or serves
    imports the mixers' modules: their set-up is the parent's."""
    import subprocess
    import sys

    code = (
        "import sys, jax, numpy as np, jax.numpy as jnp\n"
        "import deepspeed_tpu as ds\n"
        "from deepspeed_tpu.models import build_model, tiny_test\n"
        "from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh\n"
        "m = build_model(tiny_test(max_seq=64, dtype=jnp.float32))\n"
        "e = ds.init_inference(m, m.init(jax.random.PRNGKey(0)),\n"
        "                      {'dtype': 'float32'}, mesh=build_mesh(\n"
        "        MeshSpec(data=1), devices=jax.devices()[:1]))\n"
        "s = ds.ServingEngine(e, {'slots': 2, 'max_len': 32,\n"
        "                         'prefill_chunk': 8})\n"
        "s.serve_batch([np.arange(5, dtype=np.int32)], [3], seeds=[1])\n"
        "new = [k for k in sys.modules if k.endswith(('models.ssm',\n"
        "       'models.hybrid', 'ops.ssm_step'))]\n"
        "assert not new, new\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
