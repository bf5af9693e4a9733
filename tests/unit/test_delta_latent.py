"""Ling-3.0-flash's trunk (``model_type: bailing_hybrid``): KDA mixers with
full maps and q/k gains behind the bounded gate beside roped latent attention
with an output gate a head, experts chosen within routing groups and clamped
by a value a layer — the system against
``benchmark/reference/bailing_hybrid.py`` on seeded weights (the full forward;
chunked prefill, seating and decode through the kind, its kernels off and
on), and its parts against what defines them: ``mix_chunk`` and ``mix_step``
across a chunk's edge against the recurrence, the gated latent layer alone,
the grouped router against the written-out rule with ties, both clamps a
layer, the eight shares of an expert layer against the whole layer. One
configuration and one set of weights for the file (ROADMAP D23)."""

import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import deepspeed_tpu as ds  # noqa: E402
from benchmark.models import bailing_hybrid as fam  # noqa: E402
from benchmark.reference import bailing_hybrid as ref  # noqa: E402
from deepspeed_tpu.inference.decode import (GenCarry,  # noqa: E402
                                            forward_with_cache, init_cache)
from deepspeed_tpu.inference.kinds import DeltaLatent, kind_of  # noqa: E402
from deepspeed_tpu.models import (bailing_hybrid, build_model,  # noqa: E402
                                  config_from_hf, kda)
from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh  # noqa: E402
from deepspeed_tpu.serving.slots import (init_slots, insert_request,  # noqa: E402
                                         retire_slots)

F32 = jnp.float32
S, PROMPT, CHUNK, MAX_LEN = 114, 107, 32, 256
NAME = "ling-3.0-flash-l6-e64"
BUFFERS = ("c", "kda", "conv")


def conf():
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def published(**over):
    c = conf()
    return {**c["config"], **c["rehearsal"], **over}


@pytest.fixture(scope="module")
def small():
    """The rehearsal's configuration in float32, seeded weights, one
    sequence and the reference's logits of it."""
    cfg, model = fam.build(published(), "float32", False)
    params = model.init(jax.random.PRNGKey(3))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, S)), jnp.int32)
    want = np.asarray(ref.run_highest(ref.logits, params, ids))
    return cfg, model, params, ids, want


def worst(got, want):
    return float((np.abs(np.asarray(got) - want).max(-1)
                  / np.abs(want).max(-1)).max())


def controlled(control, params, ids):
    ref.CONTROL.add(control)
    try:
        return np.asarray(ref.run_highest(ref.logits, params, ids))
    finally:
        ref.CONTROL.clear()


def through_the_kind(cfg, model, params, ids, flash, size=CHUNK):
    """The prompt in chunks of ``size``, the last right-padded to its bucket
    (107 = 3 x 32 + 11 in a bucket of 16; 64 + 43 in a bucket of 64, where
    the KDA layers scan in ``kda_chunk_scan`` with the kernels on, behind
    ``valid`` in the second), seated in slots 0 and 2 of three, slot 2 then
    retired; 7 steps, one token a slot. Returns (slot 0's logit rows from
    the prompt's last on, the buffers of slots 1 and 2 before the steps, and
    after)."""
    @partial(jax.jit, donate_argnums=(0,))
    def chunk(cache, blk, start, last):
        lg, cache = forward_with_cache(
            model, params, blk, cache._replace(length=start),
            flash_decode=flash, last_token_head=True, last_index=last)
        return lg[0, 0], cache._replace(length=start + last + 1)

    @partial(jax.jit, donate_argnums=(0,))
    def step(cache, toks):
        lg, cache = forward_with_cache(model, params, toks[:, None], cache,
                                       flash_decode=flash)
        return lg[:, 0], cache

    cache = init_cache(cfg, 1, MAX_LEN, F32)
    start = 0
    while start < PROMPT:
        n = min(size, PROMPT - start)
        bucket = size if n == size else max(8, 1 << (n - 1).bit_length())
        blk = np.zeros((1, bucket), np.int32)
        blk[0, :n] = np.asarray(ids[0, start:start + n])
        row, cache = chunk(cache, jnp.asarray(blk), jnp.int32(start),
                           jnp.int32(n - 1))
        start += n
    rows = [row]
    carry = GenCarry(tok=jnp.zeros((1,), jnp.int32), cache=cache,
                     rng=jnp.zeros((1, 2), jnp.uint32),
                     done=jnp.zeros((1,), bool))
    state = init_slots(cfg, 3, MAX_LEN, F32)
    for slot in (0, 2):
        state = insert_request(state, jnp.int32(slot), carry)
    state = retire_slots(state, jnp.asarray([False, False, True]))
    slots = state.cache
    before = [np.asarray(getattr(slots, n)[:, 1:]) for n in BUFFERS]
    for t in range(PROMPT, S):
        lg, slots = step(slots, jnp.broadcast_to(ids[0, t], (3,)))
        rows.append(lg[0])
    after = [np.asarray(getattr(slots, n)[:, 1:]) for n in BUFFERS]
    assert np.asarray(slots.length).tolist() == [S, 0, 0]
    return np.stack([np.asarray(r) for r in rows]), before, after


@pytest.mark.parametrize("path", ["forward", "kind", "kind, kernels on",
                                  "kind, chunks of 64, kernels on"])
def test_the_trunk_matches_the_plain_reference(small, path):
    """The full forward; and prefill in chunks (across chunk edges, a padded
    final one), seating and 7 decode steps, every logit row, with XLA's
    updates and with the kernels (interpreted here: the state step, the
    latent append and the absorbed read, and — at chunks of 64 — the chunk's
    scan). A slot that is not running — retired with a prompt's state in it,
    or never seated — keeps every buffer bit-equal with the kernels on."""
    cfg, model, params, ids, want = small
    with jax.default_matmul_precision("highest"):
        if path == "forward":
            assert worst(model.apply(params, ids), want) < 2e-4
            return
        got, before, after = through_the_kind(
            cfg, model, params, ids, path.endswith("on"),
            64 if "chunks of 64" in path else CHUNK)
    assert worst(got, want[0, PROMPT - 1:]) < 2e-4
    if path.endswith("on"):
        assert all(np.array_equal(a, b) for a, b in zip(before, after))
        assert any(a.any() for a in before)     # the retired slot held a state


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_wrong_reading_of_the_config_is_another_model(small, control):
    """Each reading the configuration's ``assumed`` excludes (a control of
    the chip's comparison, ``benchmark/kinds/backlog_delta_latent.py``)
    moves the reference's logits by far more than the system differs from
    the sound one."""
    _, _, params, ids, want = small
    assert worst(controlled(control, params, ids), want) > 2e-2


# ------------------------------------------------------------------- KDA
def test_a_chunk_then_steps_are_the_reference_s_mixer(small):
    """``mix_chunk`` over a full chunk and a padded one (``valid`` 11 of a
    bucket of 16) then ``mix_step`` token by token, with the full maps and
    the q/k gains: the reference's KDA branch over the whole sequence,
    across both edges."""
    cfg, _, params, _, _ = small
    c = ref.PUBLISHED
    assert cfg.kda_rank == 0 and cfg.kda_qk_norm
    p = jax.tree.map(lambda a: a[0], params["layers"][0])
    assert p["kda_wf"].shape == p["kda_wg"].shape == (64, 64) \
        and p["kda_qk_scale"].shape == (2, 16)
    T = 32 + 11 + 5
    y = jax.random.normal(jax.random.PRNGKey(11), (1, T, cfg.d_model))
    shapes = kda.state_shapes(cfg, 1)
    St = jnp.zeros((1,) + shapes["kda"], F32)
    W = jnp.zeros((1,) + shapes["conv"], F32)
    with jax.default_matmul_precision("highest"):
        want, S_want = ref.kda(y[0], p, c)
        _, g = ref.kda_gates(y[0], p, c)
        out = []
        o, s, w = kda.mix_chunk(cfg, p, y[:, :32], St[0], W[0])
        out.append(o)
        pad = jnp.pad(y[:, 32:43], ((0, 0), (0, 5), (0, 0)))
        o, s, w = kda.mix_chunk(cfg, p, pad, s, w, valid=jnp.int32(11))
        out.append(o[:, :11])
        St, W = s[None], w[None]
        for t in range(43, T):
            o, St, W = kda.mix_step(cfg, p, y[:, t:t + 1], St, W,
                                    jnp.int32(0), jnp.asarray([t + 1]), False)
            out.append(o)
    assert -5.0 < float(g.min()) < -1.0       # the bounded gate, at work
    np.testing.assert_allclose(jnp.concatenate(out, 1)[0], want, atol=2e-5)
    np.testing.assert_allclose(St[0, 0], S_want, atol=2e-5)


def test_the_gated_latent_layer_alone_is_the_reference_s():
    """A trunk of ONE layer, the roped latent attention with its gate a head
    and its experts: the full forward against the reference; without the
    rope, with half-split pairs, without the gate or with a value a channel,
    another model."""
    pub = published(num_hidden_layers=1, layer_group_size=1,
                    expert_swiglu_limit_list=[4],
                    share_expert_swiglu_limit_list=[7])
    cfg, model = fam.build(pub, "float32", False)
    assert cfg.mixer_pattern == "A" and cfg.attn_out_gate == "head"
    params = model.init(jax.random.PRNGKey(5))
    assert params["layers"]["w_ogate"].shape == (1, 64, 4)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 70)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, ids)
    want = np.asarray(ref.run_highest(ref.logits, params, ids))
    assert worst(got, want) < 2e-4
    for control in ("rope-dropped", "rope-halves", "out-gate-dropped",
                    "out-gate-per-channel"):
        assert worst(controlled(control, params, ids), want) > 2e-2, control
    ref.configure(published())       # (the module's configuration, back)


# --------------------------------------------------------------- experts
def _layer(pub, key=9):
    """(the reference configured for ``pub``, its model, one expert layer's
    weights of all the router's experts)."""
    ref.configure(pub)
    cfg = fam.model_config(pub, "float32")
    model = build_model(cfg)
    seg = model.init(jax.random.PRNGKey(key))["layers"]
    seg = seg[0] if isinstance(seg, tuple) else seg
    return cfg, model, jax.tree.map(lambda a: a[0], seg)


def test_the_grouped_router_is_the_written_out_rule_ties_included():
    """32 experts in 8 groups of which 4 are kept, top 4: the system's
    ``route`` against the reference's rule on inputs whose scores tie
    exactly, within a group, between groups' sums and at the top-k's edge
    (integer logits and a bias on a grid: both sides compute the same
    floats, and of equal ones the lower index wins on both)."""
    pub = published(num_experts=32, n_routed_experts=32, router_experts=32,
                    n_group=8, topk_group=4)
    cfg, model, w = _layer(pub)
    rng = np.random.default_rng(4)
    N, d, E = 96, cfg.d_model, 32
    y = jnp.asarray(rng.integers(-1, 2, (N, d)), F32)
    # (three inputs alone reach the router: a handful of logits, many ties)
    w["router"] = jnp.zeros((d, E), F32).at[:3].set(
        jnp.asarray(rng.integers(-1, 2, (3, E)), F32) / 2.0)
    w["router_bias"] = jnp.asarray(rng.integers(-2, 3, (E,)), F32) / 8.0
    with jax.default_matmul_precision("highest"):
        idx, wt, kept = model.route(y, w)
        g, _ = ref.router(y, {k: w[k] for k in ("router", "router_bias")},
                          ref.PUBLISHED)
    g = np.asarray(g)
    chosen = np.zeros((N, E), bool)
    np.put_along_axis(chosen, np.asarray(idx), True, axis=1)
    assert (chosen.sum(1) == 4).all()
    assert np.array_equal(chosen, g > 0)
    np.testing.assert_allclose(
        np.take_along_axis(g, np.asarray(idx), axis=1), np.asarray(wt),
        rtol=1e-6)
    # the ties the rule has to settle were there
    biased = np.asarray(jax.nn.sigmoid(y @ w["router"]) + w["router_bias"])
    gs = np.sort(biased.reshape(N, 8, 4), -1)[..., -2:].sum(-1)
    ranked = np.sort(gs, -1)
    assert (ranked[:, 3] == ranked[:, 4]).any()        # the 4th group = 5th
    assert (np.asarray(kept).sum(1) == 4).all()
    # every chosen expert lies in a kept group, and the groups are the rule's
    assert np.asarray(kept)[np.arange(N)[:, None],
                            np.asarray(idx) // 4].all()
    top = np.argsort(-gs, axis=-1, kind="stable")[:, :4]
    want_kept = np.zeros((N, 8), bool)
    np.put_along_axis(want_kept, top, True, axis=1)
    assert np.array_equal(np.asarray(kept), want_kept)
    # without groups the choice is another one for some token
    ref.CONTROL.add("plain-top8")
    try:
        plain, _ = ref.router(y, {k: w[k] for k in ("router",
                                                    "router_bias")},
                              ref.PUBLISHED)
    finally:
        ref.CONTROL.clear()
    assert not np.array_equal(np.asarray(plain) > 0, chosen)
    ref.configure(published())


def test_the_reference_follows_a_swap_at_a_threshold_and_no_other():
    """``router(follow=)``: a choice that swaps the 4th expert for the 5th,
    or the weakest kept group for the best left out, is followed where the
    scores lie within ``gap`` (2 ``gap`` for a group's sum of two); one that
    takes an expert from further down is not."""
    pub = published(num_experts=32, n_routed_experts=32, router_experts=32,
                    n_group=8, topk_group=4)
    cfg, _, w = _layer(pub)
    c = ref.PUBLISHED
    y = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model))
    rw = {k: jnp.asarray(w[k], F32) for k in ("router", "router_bias")}
    rw["router"] = rw["router"] * 40.0       # scores apart, as a trained one
    biased = np.asarray(jax.nn.sigmoid(y @ rw["router"]) + rw["router_bias"])
    own = np.asarray(ref.router(y, rw, c)[0]) > 0
    masked = np.where(np.repeat(np.asarray(
        ref._top(ref.group_scores(jnp.asarray(biased), c), 4)), 4, -1),
        biased, -np.inf)
    order = np.argsort(-masked, -1, kind="stable")
    swap = order[:, [0, 1, 2, 4]]            # the 5th for the 4th
    dist = masked[np.arange(64), order[:, 3]] - masked[np.arange(64),
                                                       order[:, 4]]
    gs = np.asarray(ref.group_scores(jnp.asarray(biased), c))
    rank = np.argsort(-gs, -1, kind="stable")
    margin = gs[np.arange(64), rank[:, 3]] - gs[np.arange(64), rank[:, 4]]
    gap = float(np.median(dist))
    g, (followed, far) = ref.router(y, rw, c, jnp.asarray(swap), gap)
    took = ((np.asarray(g) > 0) != own).any(1)
    # (where the 4th expert stands in the weakest kept group, the groups'
    # swap explains the same choice: followed by the groups' margin then)
    assert took[dist <= gap].all() and int(followed) == took.sum() > 0
    assert (margin[took & (dist > gap)] / 2.0 <= gap).all()
    assert not took[(dist > gap) & (margin / 2.0 > gap)].any()
    assert (dist > gap).any() and not took.all()
    deep = order[:, [0, 1, 2, 7]]            # one from further down
    g, (followed, _) = ref.router(y, rw, c, jnp.asarray(deep), 1e-6)
    assert int(followed) == 0 and np.array_equal(np.asarray(g) > 0, own)
    # a group's swap: the system kept the best group left out
    theirs = np.zeros((64, 8), bool)
    np.put_along_axis(theirs, rank[:, [0, 1, 2, 4]], True, axis=1)
    theirs = np.argsort(-np.where(np.repeat(theirs, 4, -1), biased, -np.inf),
                        -1, kind="stable")[:, :4]
    gap = float(np.median(margin)) / 2.0
    g, (followed, _) = ref.router(y, rw, c, jnp.asarray(theirs), gap)
    changed = ((np.asarray(g) > 0) != own).any(1)
    differs = np.zeros((64, 32), bool)
    np.put_along_axis(differs, theirs, True, axis=1)
    differs = (differs != own).any(1)
    near = margin / 2.0 <= gap
    assert changed[differs & near].all() and not changed[~differs].any()
    assert (differs & near).any() and (differs & ~near & ~changed).any()
    # ... or kept it for the THIRD group where the third, fourth and fifth
    # all stand at the threshold: told from the groups its experts lie in
    wide = gs[np.arange(64), rank[:, 2]] - gs[np.arange(64), rank[:, 4]]
    theirs = np.zeros((64, 8), bool)
    np.put_along_axis(theirs, rank[:, [0, 1, 3, 4]], True, axis=1)
    theirs = np.argsort(-np.where(np.repeat(theirs, 4, -1), biased, -np.inf),
                        -1, kind="stable")[:, :4]
    gap = float(np.median(wide)) / 2.0
    g, _ = ref.router(y, rw, c, jnp.asarray(theirs), gap)
    took = np.zeros((64, 32), bool)
    np.put_along_axis(took, theirs, True, axis=1)
    followed = ((np.asarray(g) > 0) == took).all(1)
    visible = took.reshape(64, 8, 4).any(-1)[np.arange(64), rank[:, 4]]
    assert followed[visible & (wide / 2.0 <= gap)].all()
    assert (visible & (wide / 2.0 <= gap)).any()
    ref.configure(published())


@pytest.mark.parametrize("limits", [(4, 5), (4, 7), (0, 5), (0, 0)])
def test_both_clamps_of_a_layer_are_the_reference_s(limits):
    """One expert layer with the routed experts clamped at one value and
    the shared expert at another (0: not clamped), the weights drawn so
    that a tenth of the pre-activations pass each: the system's sorted rows
    against the reference's every-expert-on-every-token; each clamp dropped
    or mistaken for the other reads differently where they differ."""
    pub = published(num_hidden_layers=1, layer_group_size=6,
                    num_experts=16, n_routed_experts=16,
                    expert_swiglu_limit_list=[limits[0]],
                    share_expert_swiglu_limit_list=[limits[1]])
    cfg, model, w = _layer(pub)
    assert cfg.segment_limits == (tuple(map(float, limits)),)
    y = jax.random.normal(jax.random.PRNGKey(10), (1, 48, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = model.experts(y, w, limits=cfg.segment_limits[0])[0][0]
        want, _ = ref.experts(y[0], w, ref.PUBLISHED, limits)
        np.testing.assert_allclose(got, want, atol=2e-5)
        for control, moves in (("routed-clamp-dropped", limits[0] > 0),
                               ("shared-clamp-routed",
                                limits[0] != limits[1])):
            ref.CONTROL.add(control)
            try:
                other, _ = ref.experts(y[0], w, ref.PUBLISHED, limits)
            finally:
                ref.CONTROL.clear()
            assert (float(jnp.abs(other - want).max())
                    > 1e-2 * float(jnp.abs(want).max())) == moves, control
    ref.configure(published())


# ------------------------------------------------------- the chip's share
def test_eight_shares_of_an_expert_layer_sum_to_the_whole_layer():
    """Each of 8 chips holds one routing group, 4 of the router's 32
    experts; every one routes over all 32, keeps 4 groups, takes its top 4,
    adds its own experts' part and the shared expert; a token whose groups
    leave a chip's out gets the shared expert alone from it. Their parts,
    the shared expert counted once, add up to the reference's layer with all
    32 held; the fifth counter says how many tokens kept each chip's group."""
    whole = dict(num_hidden_layers=1, layer_group_size=6, n_group=8,
                 topk_group=4, router_experts=32,
                 expert_swiglu_limit_list=[4],
                 share_expert_swiglu_limit_list=[7])
    cfg, _, w = _layer(published(num_experts=32, n_routed_experts=32,
                                 **whole))
    limits = cfg.segment_limits[0]
    y = jax.random.normal(jax.random.PRNGKey(10), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(y[0], w, ref.PUBLISHED, limits)
        shared = want - ref.experts(y[0], w, ref.PUBLISHED, limits,
                                    shared=False)[0]
        total, kept = 0.0, 0.0
        for chip in range(8):
            share = build_model(fam.model_config(published(
                num_experts=4, n_routed_experts=4,
                first_expert_held=4 * chip, **whole), "float32"))
            mine = {**w, **{k: w[k][4 * chip:4 * chip + 4]
                            for k in ref.BANKS}}
            out, stats, idx = share.experts(y, mine, limits=limits)
            total = total + out[0] - shared
            kept += float(stats[4])
            # the rows held here are the pairs that chose this group
            assert float(stats[3]) == float((np.asarray(idx) // 4
                                             == chip).sum())
        np.testing.assert_allclose(total + shared, want, atol=2e-5)
    assert kept == 24 * 4               # every token keeps four of the eight
    ref.configure(published())


# ------------------------------------------------------------ the config
def test_the_published_config_counts_the_card_s_parameters():
    big = bailing_hybrid("3.0-flash")
    total, active = big.param_count(), big.param_count(active_only=True)
    assert round(total / 1e9, 2) == 124.41 and round(active / 1e9, 2) == 5.5
    said = conf()["assumed"]["param_count"]
    assert "124.41 B" in said and "5.50 B active" in said
    assert big.mixer_pattern.count("K") == 35 \
        and big.mixer_pattern[:6] == "KKKKKA"
    assert len(big.moe_swiglu_limits) == len(big.moe_shared_swiglu_limits) \
        == 42
    # the cell's share: what a slot and a cached position cost
    share = fam.model_config(conf()["config"], "bfloat16")
    assert share.segments == (("moe", 4), ("moe", 1), ("moe", 1))
    assert share.segment_limits == ((4.0, 5.0), (4.0, 7.0), (4.0, 7.0))
    kind = kind_of(share, 160, jnp.bfloat16)
    assert isinstance(kind, DeltaLatent) and kind.recurrent
    assert kind.state_bytes_per_slot() == 5 * (2 ** 21 + 3 * 3 * 4096 * 2)
    assert kind.bytes_per_token() == 1152
    assert {n: s for n, (s, _) in {**kind.buffers(160, 24576),
                                   **kind.state(160)}.items()} == {
        "c": (1, 160, 576, 24576), "kda": (5, 160, 32, 128, 128),
        "conv": (5, 160, 3, 12288)}


@pytest.mark.parametrize("over,why", [
    (dict(pos_embedding="none"), "rope on qk_rope_head_dim"),
    (dict(hc_mult=4), "bailing_hybrid block"),
    (dict(q_lora_rank=48), "bailing_hybrid block"),
    (dict(attn_out_gate=True), "attn_out_gate is the"),
    (dict(mixer_pattern="", attn_out_gate="head", moe_swiglu_limits=(),
          moe_shared_swiglu_limits=()), "attn_out_gate is the"),
    (dict(kda_rank=-1), "kda_rank .0: full maps."),
    (dict(moe_swiglu_limits=(4, 4)), "every layer's clamp"),
    (dict(swiglu_limit=10.0), "in place of the one swiglu_limit"),
    (dict(moe_n_group=3), "group-limited choice"),
    (dict(moe_topk_group=5), "group-limited choice"),
    (dict(moe_top_k=16), "room for the top-k"),
])
def test_what_the_trunk_does_not_run_is_refused_with_why(over, why):
    with pytest.raises(ValueError, match=why):
        build_model(bailing_hybrid("tiny", **over))


def test_the_importer_and_the_trainer_refuse_the_family():
    with pytest.raises(ValueError, match="unsupported model_type "
                                         "'bailing_hybrid'"):
        config_from_hf(dict(conf()["config"], model_type="bailing_hybrid"))
    model = build_model(bailing_hybrid("tiny"))
    with pytest.raises(ValueError, match="served, not trained"):
        ds.initialize({"train_batch_size": 8,
                       "optimizer": {"type": "adamw", "params": {}}}, model)


# ----------------------------------------------------------- the serving
@pytest.fixture(scope="module")
def served(small):
    cfg, model, params, _, _ = small
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    eng = ds.init_inference(model, params, {"dtype": "float32"}, mesh=mesh)
    return cfg, eng


def test_served_requests_are_solo_generate_s_and_the_spans_say_what_moved(
        served):
    """Three prompts on both sides of a chunk's edge through
    ``ServingEngine`` (chunks of 16, three slots): the tokens of solo
    ``generate()``; the ``decode_step`` spans carry what the step has to
    move from the mirror of the slots' lengths and the share of the running
    tokens that kept the held group, the ``prefill_chunk`` spans their real
    and padded tokens and the keys their walk reads."""
    cfg, eng = served
    srv = ds.ServingEngine(eng, {"slots": 3, "max_len": 128,
                                 "prefill_chunk": 16, "temperature": 0.9,
                                 "top_k": 30, "spans": True})
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (P,)).astype(np.int32)
               for P in (5, 33, 46)]
    outs = srv.serve_batch(prompts, [6, 5, 4], seeds=[1, 2, 3])
    for p, n, seed, got in zip(prompts, (6, 5, 4), (1, 2, 3), outs):
        want = eng.generate(p[None], n, request_seeds=[seed],
                            temperature=0.9, top_k=30, cache_len=128)
        assert np.asarray(got).tolist() == np.asarray(want)[0].tolist()
    steps = [e for e in srv.spans.events() if e.kind == "decode_step"]
    chunks = [e for e in srv.spans.events() if e.kind == "prefill_chunk"]
    meta = steps[-1].meta
    for key in ("slots", "live_positions", "state_bytes_per_slot",
                "cache_bytes_per_token", "state_bytes_step", "kv_bytes_step",
                "weight_bytes_step", "expert_bytes_step", "head_bytes_step",
                "state_share_of_step_bytes", "kv_share_of_step_bytes",
                "experts_touched", "held_rows", "held_rows_share",
                "moe_load_max_over_mean", "held_group_token_share"):
        assert key in meta, key
    kind = srv.kind
    assert isinstance(kind, DeltaLatent)
    assert meta["state_bytes_step"] == 2 * meta["slots"] * kind.slot_bytes
    assert meta["kv_bytes_step"] == meta["live_positions"] * kind.token_bytes
    assert meta["cache_bytes_per_token"] == (32 + 8) * 4
    shares = [e.meta["held_group_token_share"] for e in steps]
    assert all(0.0 <= s <= 1.0 for s in shares) and 0 < sum(shares)
    last = [e.meta for e in chunks if e.meta["final"]]
    assert {(m["tokens_real"], m["tokens_padded"]) for m in last} \
        == {(5, 3), (1, 7), (14, 2)}
    assert all(m["attn_live_keys"] == m["size"] + 16 * m["chunk"]
               and m["attn_kernel"] is False for m in (e.meta for e in chunks))
    srv.close()


def test_the_kind_refuses_what_it_does_not_compose_with(served):
    _, eng = served
    base = {"slots": 2, "max_len": 128, "prefill_chunk": 16}
    for over, why in ((dict(page_size=8, pool_pages=40), "has no pages"),
                      (dict(kv_quant_bits=8, page_size=8, pool_pages=40),
                       "has no pages"),
                      (dict(speculation={"enabled": True}),
                       "roll the delta-rule state back while the latents"),
                      (dict(host_pool_bytes=1 << 20, page_size=8,
                            pool_pages=40), "has no pages")):
        with pytest.raises(ValueError, match=why):
            ds.ServingEngine(eng, {**base, **over})
    kind = kind_of(eng.model.cfg)
    assert set(kind.refuses) == {"paged", "kv_quant", "speculation",
                                 "host_kv", "quantize", "mesh"}
    model, params = eng.model, eng.params
    two = ds.init_inference(model, params, {"dtype": "float32"},
                            mesh=build_mesh(MeshSpec(data=2),
                                            devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="a mesh of several devices"):
        ds.ServingEngine(two, base)


# ----------------------------------------------------- the chunk's kernels
@pytest.mark.parametrize("what,T,max_len,flash,falls", [
    ("the kernel", 64, 128, True, 0),
    ("a bucket of 32", 32, 128, True, 0),
    ("a cache of no whole lane block", 64, 96, True, 1),
    ("the kernels off", 64, 128, False, 0),
])
def test_a_chunk_scanned_by_xla_with_the_kernels_on_is_counted(
        small, what, T, max_len, flash, falls):
    """``Serve/chunk_scan_fallback_builds``: one for every chunk program of
    whole blocks of 64 whose KDA layers were traced onto ``scan_chunked``
    while the kernels are on; no chunk counts as an attention fallback (no
    kernel attends a chunk of this kind); the kind's own answer (what the
    ``prefill_chunk`` span says) agrees."""
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.serving.scheduler import ChunkPlan

    cfg, model, params, ids, _ = small
    reg = get_registry()
    scan = reg.counter("Serve/chunk_scan_fallback_builds")
    attn = reg.counter("Serve/chunk_attention_fallback_builds")
    before = scan.value, attn.value
    cache = init_cache(cfg, 1, max_len, F32)
    text = str(jax.make_jaxpr(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash))(params, ids[:, :T], cache))
    assert (scan.value - before[0], attn.value - before[1]) == (falls, 0)
    took = flash and not falls and T == 64
    assert ("kda_chunk_scan" in text and "pallas_call" in text) == took
    kind = kind_of(cfg, 1, F32)
    kind.flash, kind.max_len = flash, max_len
    meta = kind.chunk_meta(ChunkPlan(start=0, ids=np.zeros(T, np.int32)))
    assert meta["attn_kernel"] is False and meta["scan_kernel"] is took


# ------------------------------------------- what the other families run
# sha256[:16] of the jaxpr of a 3-slot step and of a 64-token chunk through
# ``forward_with_cache`` with the kernels on, each family's ``tiny`` preset,
# AS THE PARENT OF PR 62 TRACED THEM (commit 3018207, this container's JAX;
# the repo's path stripped from the kernels' source notes): the clamp's new
# form (a value a layer, two lists), the gate's (a head), the router's
# groups, the fifth counter and the shared helpers of ``steps.py`` and
# ``latent.py`` leave every program of the cells that were there as it was.
PARENT_S = {
    "glm5_next": ("1d1505a4ee0b720c", "f8d391942c28033a"),
    "mimo_v2_flash": ("82a430e09ecc211d", "665a06832ee6e02d"),
    "solar_open2": ("c92c53b85260e786", "1999a92f5f3c66fa"),
    # (the step's re-taken by PR 63, which changed the kernel's call: one
    # program a slot in ``mla_decode_attention``; its body is in the jaxpr)
    "deepseek_v3": ("696a45285d43a5b4", "d1e7ea67fa10c09f"),
    "glm_moe_dsa": ("3de03a7a57504a34", "a8326a40167c29f6"),
}


def _fingerprint(cfg, T, slots):
    import hashlib
    import re

    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = slots if T == 1 else 1
    cache = jax.eval_shape(lambda: init_cache(
        cfg, batch, 128, cfg.dtype, (slots,) if T == 1 else ()))
    ids = jax.ShapeDtypeStruct((batch, T), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, i, c: forward_with_cache(
        model, p, i, c, flash_decode=True, with_stats=True,
        with_routing=True))(params, ids, cache))
    text = re.sub(r" at [^ ]*?/deepspeed_tpu/", " at deepspeed_tpu/", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("family", PARENT_S)
def test_the_other_families_programs_are_the_parent_s(family):
    from deepspeed_tpu.models import presets

    cfg = getattr(presets, family)("tiny")
    assert (_fingerprint(cfg, 1, 3), _fingerprint(cfg, 64, 1)) \
        == PARENT_S[family]
