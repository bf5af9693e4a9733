"""A looped trunk (``loop_steps`` > 1: the Ouro block, ``models/presets.py
ouro``) on the serving path, against the plain reference
(``benchmark/reference/ouro.py``) at a small size in float32: 4 layers x 3
passes, width 64, 4 heads, vocabulary 251, seeded weights with every norm
gain and the gate's bias off their initial values, so that a gain applied in
the wrong place shows."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro as ref
from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                            cache_layout, forward_with_cache,
                                            init_cache)
from deepspeed_tpu.models import build_model, llama2, ouro
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PUBLISHED = {"num_attention_heads": 4, "rms_norm_eps": 1e-6,
             "rope_theta": 1000000, "total_ut_steps": 3,
             "early_exit_threshold": 1}
PROMPT, CHUNK, STEPS, MAX_LEN = 21, 16, 6, 128
TOL = 1e-4


def one_device_mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def seeded(model, seed: int = 0):
    """The model's own init with the norm gains and the gate's bias drawn
    too (the init leaves them constant and 0)."""
    params = model.init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def gain(a):
        return a * (1.0 + 0.3 * jax.random.normal(next(keys), a.shape,
                                                  a.dtype))

    layers = {k: gain(v) if k.endswith("_scale") else v
              for k, v in params["layers"].items()}
    out = dict(params, layers=layers, lnf_scale=gain(params["lnf_scale"]))
    if "exit_gate_b" in out:
        out["exit_gate_b"] = jnp.float32(0.4)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = ouro("tiny", dtype=jnp.float32)
    assert (cfg.n_layer, cfg.loop_steps, cfg.d_model, cfg.n_head,
            cfg.vocab_size) == (4, 3, 64, 4, 251)
    model = build_model(cfg)
    ref.configure(PUBLISHED)
    return cfg, model, seeded(model)


@pytest.fixture(scope="module")
def ids(tiny):
    return np.random.default_rng(7).integers(
        0, tiny[0].vocab_size, (1, PROMPT + STEPS)).astype(np.int32)


@pytest.fixture(scope="module")
def want(tiny, ids):
    """The reference's one full forward: logits (S, V), hidden (R, S, d),
    pdf (S, R)."""
    _, _, params = tiny
    lg = ref.run_highest(ref.logits, params, jnp.asarray(ids))
    hidden, pdf = ref.run_highest(ref.passes, params, jnp.asarray(ids))
    return (np.asarray(lg[0]), np.asarray(hidden[:, 0]),
            np.asarray(pdf[:, 0]).T)


def through_the_cache(model, params, ids, flash: bool = True):
    """Prefill in two chunks (16 + 5) and then 6 given tokens one at a time,
    through ``forward_with_cache`` on one carried cache: (logits (S, V),
    hidden (R, S, d), pdf (S, R), the cache)."""
    cache = init_cache(model.cfg, 1, MAX_LEN)
    cuts = [0, CHUNK, PROMPT] + list(range(PROMPT + 1, ids.shape[1] + 1))
    lgs, hid, pdf = [], [], []
    with jax.default_matmul_precision("highest"):
        for a, b in zip(cuts, cuts[1:]):
            lg, cache, passes = forward_with_cache(
                model, params, jnp.asarray(ids[:, a:b]), cache,
                flash_decode=flash, with_passes=True)
            lgs.append(np.asarray(lg[0]))
            hid.append(np.asarray(passes["hidden"][:, 0]))
            if "exit_pdf" in passes:
                pdf.append(np.asarray(passes["exit_pdf"][0]))
    return (np.concatenate(lgs), np.concatenate(hid, 1),
            np.concatenate(pdf) if pdf else None, cache)


def worst(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# ------------------------------------------------- (a) against the reference
@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "dense"])
def test_prefill_in_chunks_then_decode_equals_the_reference_s_full_forward(
        tiny, ids, want, flash):
    _, model, params = tiny
    lg, hidden, pdf, _ = through_the_cache(model, params, ids, flash)
    for got, ref_ in zip((lg, hidden, pdf), want):
        assert got.shape == ref_.shape
        assert worst(got, ref_) < TOL
    np.testing.assert_allclose(pdf.sum(-1), 1.0, atol=1e-6)


def test_the_cacheless_forward_loops_too(tiny, ids, want):
    _, model, params = tiny
    with jax.default_matmul_precision("highest"):
        lg, aux = model.apply(params, jnp.asarray(ids), return_aux=True)
    assert worst(np.asarray(lg[0]), want[0]) < TOL
    assert worst(np.asarray(aux["hidden"][:, 0]), want[1]) < TOL
    assert worst(np.asarray(aux["exit_pdf"][0]), want[2]) < TOL


# ------------------------------------------------------------ (b) the cache
def test_a_plane_a_pass_and_layer_each_written_once_a_token(tiny, ids):
    cfg, model, params = tiny
    planes = cfg.n_layer * cfg.loop_steps
    assert cache_layout(cfg, 3, MAX_LEN)[0] == (
        planes, 3, cfg.kv_heads, cfg.head_dim, MAX_LEN)
    assert cache_bytes_per_token(cfg) == \
        planes * 2 * cfg.kv_heads * cfg.head_dim * 4
    *_, cache = through_the_cache(model, params, ids[:, :PROMPT + 1])
    before = (np.asarray(cache.k), np.asarray(cache.v))
    n = int(cache.length)
    assert n == PROMPT + 1
    for buf in before:            # every plane holds the n tokens, no more
        assert (np.abs(buf[..., :n]).sum((1, 2, 3)) > 0).all()
        assert not buf[..., n:].any()
    _, after = forward_with_cache(model, params,
                                  jnp.asarray(ids[:, n:n + 1]), cache,
                                  flash_decode=True)
    assert int(after.length) == n + 1
    for old, new in zip(before, (np.asarray(after.k), np.asarray(after.v))):
        changed = (old != new).any((1, 2, 3))            # (planes, max_len)
        assert changed[:, n].all() and changed.sum() == planes
    # two passes of one layer keep different keys for the same token
    k = np.asarray(after.k)
    assert np.abs(k[0, ..., :n] - k[cfg.n_layer, ..., :n]).max() > 1e-2


def test_the_published_model_s_cached_token_is_what_its_file_states():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        conf = json.load(f)
    cfg = ouro("2.6b", dtype=jnp.bfloat16)
    assert cache_layout(cfg, 12, 384)[0] == (192, 12, 16, 128, 384)
    assert cache_bytes_per_token(cfg) == 192 * 2 * 16 * 128 * 2 == 1572864
    assert "1 572 864 B" in conf["bytes"]["cache_bf16"]
    assert cfg.param_count() == 2 * 49152 * 2048 + 48 * (
        4 * 2048 ** 2 + 3 * 2048 * 5632)


# -------------------------------------------------------- (c) the mutations
class SharedPlanes(TransformerLM):
    """Every pass on the first pass's planes (plane = layer)."""

    def loop_passes(self, params, x, carry, one_pass):
        return super().loop_passes(
            params, x, carry, lambda x, c, r: one_pass(x, c, r * 0))


class NormAfterTheLastPassOnly(TransformerLM):
    def loop_passes(self, params, x, carry, one_pass):
        for r in range(self.cfg.loop_steps):
            x, carry = one_pass(x, carry, jnp.int32(r))
        x = self._final_norm(params, x)
        return x, carry, {"hidden": x[None]}


class NoPostNorms(TransformerLM):
    def _post_norm(self, y, p, ln):
        return y


@pytest.mark.parametrize("mutant", [SharedPlanes, NormAfterTheLastPassOnly,
                                    NoPostNorms])
def test_a_trunk_that_leaves_part_of_the_loop_out_fails(tiny, ids, want,
                                                        mutant):
    cfg, _, params = tiny
    lg, *_ = through_the_cache(mutant(cfg), params, ids)
    assert worst(lg, want[0]) > 100 * TOL
    if mutant is SharedPlanes:
        # ... and only the cache shows it: one forward over the whole
        # sequence writes and reads each pass's keys before the next pass
        # overwrites them
        cache = init_cache(cfg, 1, MAX_LEN)
        with jax.default_matmul_precision("highest"):
            whole, _ = forward_with_cache(mutant(cfg), params,
                                          jnp.asarray(ids), cache)
        assert worst(np.asarray(whole[0]), want[0]) < TOL


def test_a_gate_whose_last_pass_takes_its_own_value_fails(tiny, ids, want,
                                                          monkeypatch):
    _, model, params = tiny

    def last_is_lambda(lam):
        before = jnp.cumprod(jnp.concatenate(
            [jnp.ones_like(lam[:1]), 1.0 - lam[:-1]]), axis=0)
        return jnp.moveaxis(lam * before, 0, -1)

    monkeypatch.setattr(T, "exit_pdf", last_is_lambda)
    lg, _, pdf, _ = through_the_cache(model, params, ids)
    assert worst(lg, want[0]) < TOL              # the logits do not show it
    assert worst(pdf, want[2]) > 100 * TOL


# -------------------------------------------------- (d) one pass, no extras
def test_one_pass_without_the_extras_is_the_llama_shaped_trunk(ids):
    """``loop_steps`` 1 with no sandwich norms and no gate builds what the
    trunk built before it could loop: the same configuration as the
    ``llama2`` preset at these sizes, the same parameter tree, and the same
    bits as the parent commit computed (digests taken there, PR 34) through
    the cacheless forward and through prefill + decode on the cache."""
    import hashlib

    dims = dict(n_layer=4, n_head=4, n_kv_head=None, d_model=64, d_ff=176,
                vocab_size=251, max_seq=256, norm_eps=1e-6, rope_theta=1e6,
                fused_xent=False, dtype=jnp.float32)
    plain = llama2("tiny", **dims)
    one = ouro("tiny", loop_steps=1, sandwich_norm=False, exit_gate=False,
               dtype=jnp.float32)
    assert one == plain
    model = build_model(one)
    params = model.init(jax.random.PRNGKey(2))
    assert set(params) == {"tok_embed", "layers", "lnf_scale", "lm_head"}
    assert not any("post" in k for k in params["layers"])
    whole = np.asarray(model.apply(params, jnp.asarray(ids)))
    cache = init_cache(one, 1, MAX_LEN)
    assert cache.k.shape[0] == one.n_layer
    lg, cache = forward_with_cache(model, params, jnp.asarray(ids[:, :PROMPT]),
                                   cache, flash_decode=True)
    outs = [np.asarray(lg)]
    for t in range(PROMPT, ids.shape[1]):
        lg, cache, passes = forward_with_cache(
            model, params, jnp.asarray(ids[:, t:t + 1]), cache,
            flash_decode=True, with_passes=True)
        assert passes is None
        outs.append(np.asarray(lg))
    cached = np.concatenate(outs, 1)
    np.testing.assert_allclose(float(whole.sum()), 32.44783020019531,
                               rtol=1e-5)
    np.testing.assert_allclose(float(cached.sum()), 32.44782257080078,
                               rtol=1e-5)
    got = [hashlib.sha256(a.tobytes()).hexdigest()[:16]
           for a in (whole, cached, np.asarray(cache.k))]
    if got != ["c7bfd0185f405904", "ff1f3b36d4187afe", "a0ee0561ac3e2afb"]:
        # another CPU may round a fused multiply differently; the sums
        # above have to hold on any
        pytest.skip("equal to the parent's to 1e-5, not bit for bit, on "
                    "this CPU")


# -------------------------------------------------- (e) through the engine
def test_served_requests_equal_solo_generate_and_spans_carry_the_loop(tiny):
    import deepspeed_tpu as ds

    cfg, model, params = tiny
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "flash_decode": True},
                            mesh=one_device_mesh())
    srv = ds.ServingEngine(eng, {"slots": 4, "max_len": MAX_LEN,
                                 "prefill_chunk": 16, "temperature": 0.9,
                                 "top_k": 30, "spans": True})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 21, 40)]
    outs = srv.serve_batch(prompts, [6, 9, 4], seeds=[1, 2, 3])
    for p, o, n, s in zip(prompts, outs, [6, 9, 4], [1, 2, 3]):
        solo = eng.generate(p[None], n, request_seeds=[s], temperature=0.9,
                            top_k=30, cache_len=MAX_LEN)
        assert o.tolist() == np.asarray(solo)[0].tolist()
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    assert steps and len(chunks) == 1 + 2 + 3
    layers = sum(a.nbytes for a in jax.tree.leaves(eng.params["layers"]))
    head = eng.params["lm_head"].nbytes + eng.params["lnf_scale"].nbytes \
        + eng.params["exit_gate_w"].nbytes + eng.params["exit_gate_b"].nbytes
    for e in steps + chunks:
        m = e.meta
        assert (m["loop_steps"], m["cache_planes"]) == (3, 12)
        assert m["cache_bytes_per_token"] == 12 * 2 * 4 * 16 * 4
        assert len(m["exit_pdf"]) == 3
        assert sum(m["exit_pdf"]) == pytest.approx(1.0, abs=1e-5)
        assert min(m["exit_pdf"]) > 0
    for e in steps:
        assert e.meta["weight_bytes_per_token"] == pytest.approx(
            (3 * layers + head) / e.meta["slots"])
        assert "attn_fetched_over_live" in e.meta
    final = [e for e in chunks if e.meta["final"]]
    assert final[0].meta["weight_bytes_per_token"] == pytest.approx(
        (3 * layers + head) / 5)            # the 5-token prompt, not its 8


# ------------------------------------------------------------- (f) refusals
@pytest.mark.parametrize("serving, reason", [
    ({"page_size": 8, "pool_pages": 64}, "the paged pool"),
    ({"page_size": 8, "pool_pages": 64, "kv_quant_bits": 8}, "int8 KV"),
    ({"greedy": True, "speculation": {"enabled": True}}, "speculation"),
    (None, "a mesh of several devices"),
])
def test_serving_refuses_what_a_looped_trunk_does_not_compose_with(
        tiny, serving, reason):
    import deepspeed_tpu as ds

    _, model, params = tiny
    conf = {"dtype": "float32", "flash_decode": False}
    eng = ds.init_inference(model, params, conf) if serving is None else \
        ds.init_inference(model, params, conf, mesh=one_device_mesh())
    with pytest.raises(ValueError, match="looped trunk.*" + reason):
        ds.ServingEngine(eng, {"slots": 2, "max_len": MAX_LEN,
                               "prefill_chunk": 16, **(serving or {})})
    with pytest.raises(NotImplementedError, match="contiguous only"):
        cache_layout(model.cfg, 0, 0, page_size=8, pages=4)


def test_training_a_looped_trunk_is_refused_with_its_reason(tiny):
    import deepspeed_tpu as ds

    with pytest.raises(ValueError, match="exit\\s+distribution"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
                      tiny[1])


@pytest.mark.parametrize("bad", [
    dict(loop_steps=0), dict(loop_steps=1), dict(use_bias=True),
    dict(parallel_residual=True), dict(num_experts=4),
    dict(attention="mla", kv_lora_rank=8, qk_nope_head_dim=8,
         qk_rope_head_dim=8, v_head_dim=8)])
def test_the_constructor_refuses_a_loop_around_another_block(bad):
    with pytest.raises(ValueError, match="loop"):
        TransformerLM(ouro("tiny", **bad))
