"""Kanana-2-30B-A3B's seven-layer cut at its published widths, on the chip
(skips off the TPU; the builder of PR 29 ran it through ``chiprun``, PERF.md
section 6): a 3072-token prompt prefilled in chunks of 512, seated in a slot
of the 48-slot cache, then 32 teacher-forced decode steps — the logits at
each of the 32 positions against the plain reference's full forward, which
follows the cached path's own routing at its near-ties as the cell's check
does (``benchmark/kinds/backlog_routed.py``); and on that cache the absorbed
step against the expanded path."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="published widths need the chip")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_chunked_prefill_then_32_slot_steps_against_the_reference():
    from benchmark.models import deepseek_v3 as fam
    from benchmark.reference import deepseek_v3 as ref
    from deepspeed_tpu.inference.decode import (GenCarry, forward_with_cache,
                                                init_cache)
    from deepspeed_tpu.models import mla
    from deepspeed_tpu.models.transformer import _norm
    from deepspeed_tpu.serving.slots import init_slots, insert_request

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana-2-30b-a3b-l7.json")) as f:
        published = json.load(f)["config"]
    cfg, model = fam.build(published, "bfloat16", flash_attention=False)
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(2901))
    P, n, S, slots, slot = 3072, 32, 8192, 48, 17
    ids = np.random.default_rng(29).integers(0, cfg.vocab_size, (1, P + n))
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longdoc-backlog.json")) as f:
        mix = json.load(f)
    chunk = jax.jit(lambda p, c, i, start: forward_with_cache(
        model, p, i, c._replace(length=start), last_token_head=True,
        with_routing=True), donate_argnums=(1,))
    cache = init_cache(cfg, 1, S)
    routing, got = [], []
    for a in range(0, P, 512):
        lg, cache, chose = chunk(params, cache, jnp.asarray(
            ids[:, a:a + 512], jnp.int32), jnp.int32(a))
        routing.append(np.asarray(chose))              # (layers, 1, 512, k)
    got.append(np.asarray(lg[0, -1]))
    state = jax.jit(lambda: init_slots(cfg, slots, S))()
    pf = GenCarry(tok=jnp.zeros((1,), jnp.int32), cache=cache,
                  rng=jnp.zeros((1, 2), jnp.uint32),
                  done=jnp.zeros((1,), bool))
    state = jax.jit(insert_request, donate_argnums=(0,))(
        state, jnp.int32(slot), pf)
    step = jax.jit(lambda p, c, t: forward_with_cache(
        model, p, t, c, flash_decode=True, with_routing=True),
        donate_argnums=(1,))
    cache = state.cache
    for t in range(n):
        tok = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(
            int(ids[0, P + t]))
        lg, cache, chose = step(params, cache, tok)
        got.append(np.asarray(lg[slot, 0]))
        routing.append(np.asarray(chose)[:, slot:slot + 1])
    # position P + n has no decoded successor: the reference sees P + n
    # tokens, the cached path chose experts for all of them
    theirs = jnp.asarray(np.concatenate(routing, axis=2))
    want, followed = ref.run_highest(
        lambda p, i, t: (lambda out, took: (out[0, -n - 1:], took))(
            *ref.logits(p, i, follow=t, gap=mix["route_gap"])),
        params, jnp.asarray(ids[:, :P + n], jnp.int32), theirs)
    want = np.asarray(want)                                # (n + 1, V)
    scale = float(np.abs(want).max())
    worst = [float(np.abs(g - w).max()) / scale for g, w in zip(got, want)]
    print("the reference followed", int(followed), "of", theirs[..., 0].size,
          "token-layers; worst difference by position, share of the largest "
          "logit:", [f"{w:.2e}" for w in worst])
    assert max(worst) <= mix["logit_tolerance"], worst

    # the absorbed step against the expanded path, on that cache: layer 3
    p3 = jax.tree.map(lambda a: a[2], model.segment_params(params["layers"])[1])
    lengths = cache.length                                   # (slots,)
    y = jax.random.normal(jax.random.PRNGKey(3), (slots, 1, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    q_nope, q_rope, _ = mla.project(cfg, _norm(y, p3["ln1_scale"], None,
                                               cfg.norm, cfg.norm_eps),
                                    p3, pos)
    from deepspeed_tpu.ops.mla_attention import mla_decode_attention

    absorbed = mla.absorb_o(cfg, p3, mla_decode_attention(
        mla.absorb_q(cfg, p3, q_nope, q_rope), cache.c, lengths, layer=3,
        rank=cfg.kv_lora_rank, scale=mla.softmax_scale(cfg)))
    expanded = mla.attend_expanded(cfg, p3, q_nope, q_rope, cache.c[3], pos,
                                   jnp.max(lengths))
    a = np.asarray(absorbed[slot], np.float32)
    e = np.asarray(expanded[slot], np.float32)
    rel = float(np.abs(a - e).max() / np.abs(e).max())
    print("absorbed against expanded, slot of 3104 tokens:", f"{rel:.2e}")
    assert rel < 2e-2, rel
