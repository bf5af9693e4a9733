"""Mixture-of-Experts layer and MoE transformer trunk.

TPU-native analog of the reference MoE stack (``deepspeed/moe/layer.py:16``,
``sharded_moe.py:477-554`` — GShard top-1/top-2 gating with capacity factor,
all-to-all dispatch to experts, expert-parallel groups orthogonal to DP/TP,
``utils/groups.py:113``).

Design differences that make this TPU-idiomatic:

- **Grouped static-capacity dispatch**: tokens are grouped per batch row
  (the GShard "group" dim), each group gets a static per-expert capacity
  ``C = ceil(S * k * cf / E)``, and dispatch/combine are one-hot einsums —
  so the whole layer is a handful of large MXU matmuls, memory linear in
  batch, and XLA fuses the scatter/gather away.
- **Expert parallelism by sharding**: expert-stacked weights ``(E, d, f)``
  are sharded over the ``expert`` mesh axis; constraining the dispatched
  activations ``(B, E, C, d)`` to the same axis makes GSPMD emit exactly
  the all-to-all the reference hand-codes (``sharded_moe.py:_AllToAll``).
- **Three routers.** ``moe_router="gshard"`` is the capacity dispatch above.
  ``"sigmoid"`` (DeepSeek-V3) has no capacity at any T: :meth:`experts`
  sorts the routed rows by expert and multiplies each expert's rows by its
  own weights (``ops/moe_matmul.py``), training forward, prefill and the
  decode step alike, with a shared MLP on every token beside them.
  ``"zaya"`` (ZAYA1) feeds the same sorted rows from a softmax top-1 chosen
  by an MLP over a state that the trunk carries from layer to layer
  (:meth:`route`).
- **Gating in fp32**: router weights are exempted from the engine's bf16
  compute cast (``fp32_param_names``) so near-tie routing decisions don't
  flap across bf16 rounding, matching ``sharded_moe.py:top1gating``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..platform.mesh import BATCH_AXES, constrain
from .transformer import (TransformerConfig, TransformerLM, _activation,
                          _norm, _swiglu, clamp_gain)

B_AXES = BATCH_AXES
# the zaya router's last matrix at init, over the trunk's 1 / sqrt(fan-in)
ROUTER_OUT_GAIN = 4.0
# What the sorted expert rows of a sigmoid router do not compose with yet
# when served (``inference/kinds``): (subject and verb, how the reasons are
# joined, feature -> reason). The latent cache's list too: it names both.
SERVED = ("a latent (MLA) cache and sigmoid-routed expert layers do not yet "
          "compose with", ", ", {
              "paged": "the paged pool (page_size)",
              "kv_quant": "an int8 KV cache (kv_quant_bits)",
              "speculation": "speculation",
              "quantize": "weight-only quantization",
              "mesh": "a mesh of several devices (tensor/expert parallel)"})


def _capacity(tokens_per_group: int, num_experts: int, capacity_factor: float,
              top_k: int, min_capacity: int = 4,
              drop_tokens: bool = True) -> int:
    """Static per-expert capacity (reference ``sharded_moe.py`` capacity calc).

    ``drop_tokens=False`` sizes the capacity to hold EVERY routed token
    (reference no-drop mode) — O(S) memory per expert, never drops."""
    if not drop_tokens:
        return tokens_per_group
    cap = int(math.ceil(tokens_per_group * top_k * capacity_factor / num_experts))
    return max(cap, min_capacity)


def topk_gating(logits: jnp.ndarray, top_k: int, capacity: int):
    """GShard-style top-k gating with static capacity, for ONE token group.

    Args:
      logits: (T, E) router logits (fp32) for a group of T tokens.
      top_k: 1 or 2 (reference ``top1gating``/``top2gating``).
      capacity: per-expert static capacity C.

    Returns:
      combine: (T, E, C) fp32 combine weights (0 for dropped tokens).
      dispatch: (T, E, C) bool dispatch mask.
      aux_loss: scalar load-balancing loss (GShard eq. 4).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)      # (T, E)

    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    remaining = probs
    # running per-expert fill count, advanced across the k passes
    fill = jnp.zeros((E,), jnp.int32)
    gates_sum = jnp.zeros((T,), jnp.float32)
    top1_mask = None

    for k in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                          # (T,)
        mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)                # (T, E)
        if k == 0:
            top1_mask = mask
        # position of each token within its chosen expert's buffer:
        # cumulative count of earlier tokens that chose the same expert,
        # offset by the fill left by previous k-passes.
        pos_in_expert = (jnp.cumsum(mask, axis=0) - mask) + fill[None, :]  # (T, E)
        pos = jnp.sum(pos_in_expert * mask, axis=-1)                  # (T,)
        kept = pos < capacity
        gate = jnp.sum(probs * mask, axis=-1) * kept                  # (T,)
        onehot_pos = jax.nn.one_hot(jnp.minimum(pos, capacity - 1), capacity,
                                    dtype=jnp.float32)                # (T, C)
        sel = (mask.astype(jnp.float32) * kept[:, None])              # (T, E)
        combine = combine + gate[:, None, None] * sel[:, :, None] * onehot_pos[:, None, :]
        dispatch = dispatch | (sel[:, :, None] * onehot_pos[:, None, :] > 0)
        gates_sum = gates_sum + gate
        fill = fill + jnp.sum(mask * kept[:, None].astype(jnp.int32), axis=0)
        # mask out the chosen expert for the next pass
        remaining = remaining * (1 - mask)

    # normalize combine weights over the selected experts (top2gating renorm)
    if top_k > 1:
        denom = jnp.maximum(gates_sum, 1e-9)
        combine = combine / denom[:, None, None]

    # aux loss: E * sum_e( mean_tokens(route_frac_e) * mean_tokens(prob_e) )
    me = jnp.mean(probs, axis=0)                                      # (E,)
    ce = jnp.mean(top1_mask.astype(jnp.float32), axis=0)              # (E,)
    aux_loss = jnp.sum(me * ce) * E
    return combine, dispatch, aux_loss


def held_layout(idx, held: int, first_held: int, bm: int):
    """The sorted layout of the rows a device multiplies when it holds
    ``held`` experts from ``first_held`` on: ``idx`` (N, k) are the experts
    chosen, of ALL the router's. A pair that chose an expert held elsewhere
    sorts behind every held one and gets no row. Returns (row_token (R,) the
    token of every row, pair_row (M,) a pair's row, pair_held (M,) whether
    it has one, block_expert (R / bm,) the held expert of every block of
    ``bm`` rows, used: the blocks that hold rows, counts (held,) the rows of
    each held expert)."""
    N, k = idx.shape
    M = N * k
    e = idx.reshape(M) - first_held
    e = jnp.where((e >= 0) & (e < held), e, held)
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    is_held = e_sorted < held
    es = jnp.minimum(e_sorted, held - 1)
    counts = jnp.bincount(e, length=held + 1)[:held].astype(jnp.int32)
    padded = (counts + bm - 1) // bm * bm
    p_end = jnp.cumsum(padded)
    first = jnp.cumsum(counts) - counts
    R = -(-(M + min(held, M) * (bm - 1)) // bm) * bm         # every case fits
    dest = jnp.where(is_held, (p_end - padded)[es]
                     + jnp.arange(M, dtype=jnp.int32) - first[es], R)
    row_token = jnp.zeros((R,), jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.where(is_held, dest, 0))
    pair_held = jnp.zeros((M,), bool).at[order].set(is_held)
    used = p_end[-1] // bm
    blocks = jnp.arange(R // bm, dtype=jnp.int32)
    block_expert = jnp.minimum(jnp.searchsorted(
        p_end, jnp.maximum(jnp.minimum(blocks, used - 1), 0) * bm,
        side="right"), held - 1)
    return row_token, pair_row, pair_held, block_expert, used, counts


def kept_groups(biased, n_group: int, topk_group: int):
    """DeepSeek-V3's group-limited choice: ``biased`` (N, E) float32 scores
    (the selection bias added) in ``n_group`` equal groups of neighbouring
    experts; a group's score is the sum of its two best, the ``topk_group``
    best groups are kept (of equal ones the first, as ``lax.top_k`` ranks).
    Returns (N, n_group) bool."""
    N, E = biased.shape
    best2, _ = jax.lax.top_k(biased.reshape(N, n_group, E // n_group), 2)
    _, groups = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
    return jnp.any(groups[..., None] == jnp.arange(n_group), axis=1)


class MoETransformerLM(TransformerLM):
    """TransformerLM with the dense FFN replaced by an expert-parallel MoE
    bank in every layer (Mixtral-style; the reference interleaves dense/MoE
    via its layer list — here ``num_experts`` governs the whole trunk).
    Only the MLP half of the layer differs; attention is inherited."""

    # ------------------------------------------------------------- MoE MLP
    @jax.named_scope("moe_mlp")
    def _mlp_block(self, y, p, state=None):
        """y: (B, S, d) post-norm activations. Groups = batch rows.

        A segment's FFN kind is what its stacked weights hold: a layer
        without a router is a dense layer of this trunk
        (``moe_first_dense``). ``state`` (B, S, router_hidden): the zaya
        router's, from the layer before; the result then has a third part,
        the state this layer leaves."""
        cfg = self.cfg
        if "router" not in p:
            return super()._mlp_block(y, p)
        if cfg.moe_router == "zaya":
            B, S, d = y.shape
            idx, w, state = self.route(y.reshape(B * S, d), p,
                                       state.reshape(B * S, -1))
            out, _, idx = self.experts(y, p, routed=(idx, w))
            return out, idx, state.reshape(B, S, -1)
        if cfg.moe_router == "sigmoid":
            # no aux loss to give (noaux_tc): the layer's aux is its
            # routing, the chosen experts (B, S, k) — see _fold_aux
            out, _, idx = self.experts(y, p)
            return out, idx
        B, S, d = y.shape
        E = cfg.num_experts
        # Eval uses the (larger) eval capacity factor so fewer tokens drop
        # (reference ``eval_capacity_factor``); the flag is a trace-time
        # constant set by the engine's eval step.
        factor = cfg.moe_capacity_factor
        if getattr(self, "moe_eval_mode", False):
            factor = cfg.moe_eval_capacity_factor or 2.0 * factor
        C = _capacity(S, E, factor, cfg.moe_top_k,
                      min_capacity=cfg.moe_min_capacity,
                      drop_tokens=cfg.moe_drop_tokens)

        logits = y.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (B,S,E)
        gate = jax.vmap(lambda lg: topk_gating(lg, cfg.moe_top_k, C))
        combine, dispatch, aux = gate(logits)      # (B,S,E,C) x2, (B,)

        # dispatch: (B,S,E,C) x (B,S,d) -> (B,E,C,d). The batch dim enters
        # sharded over (data, expert); constraining it to 'data' and E to
        # 'expert' is the token all-to-all of the reference's _AllToAll
        # autograd fn (sharded_moe.py:299) — GSPMD emits it.
        xs = jnp.einsum("bsec,bsd->becd", dispatch.astype(y.dtype), y)
        xs = constrain(xs, P(("data", "zero"), "expert", None, None))

        u = jnp.einsum("becd,edf->becf", xs, p["w_in"].astype(y.dtype))
        u = self._expert_bias(u, p, "b_in")
        if cfg.is_glu:
            g = jnp.einsum("becd,edf->becf", xs, p["w_gate"].astype(y.dtype))
            u = jax.nn.silu(g) * u
        else:
            # same dispatch as the dense trunk: unknown names fail loudly
            # instead of silently running experts with the wrong nonlinearity
            # (gelu_exact Megatron-MoE imports reached this path)
            u = _activation(u, cfg.activation)
        u = constrain(u, P(("data", "zero"), "expert", None, "model"))
        out = jnp.einsum("becf,efd->becd", u, p["w_out"].astype(y.dtype))
        out = self._expert_bias(out, p, "b_out")
        out = constrain(out, P(("data", "zero"), "expert", None, None))

        # combine: (B,S,E,C) x (B,E,C,d) -> (B,S,d)  (the return all-to-all)
        res = jnp.einsum("bsec,becd->bsd", combine.astype(y.dtype), out)
        return res, jnp.mean(aux).astype(jnp.float32)

    def _expert_bias(self, u, p, name):
        if self.cfg.use_bias and name in p:
            return u + p[name][:, None, :].astype(u.dtype)  # (E,f) -> (E,1,f)
        return u

    @staticmethod
    def _bank(p, name, dtype):
        """Dense view of a (possibly int8/int4) expert bank at its point
        of consumption. The decode engine keeps expert banks quantized in
        HBM; the 3-D batched-expert einsum has no Pallas WOQ kernel (yet),
        so the dequant happens per-use inside the decode step — in-scan,
        never hoisted to a whole-bank bf16 copy across steps."""
        w = p[name]
        from ..inference.quantization import QuantizedTensor, dequantize

        if isinstance(w, QuantizedTensor):
            return dequantize(w, dtype)
        return w.astype(dtype)

    # -------------------------------------------------------- inference MoE
    @jax.named_scope("moe_mlp_infer")
    def _mlp_block_infer(self, y, p):
        """Single-group dispatch of the GShard router for the T=1 decode
        step (reference ``DeepSpeedMoEInference``,
        ``ops/transformer/inference/moe_inference.py:159``).

        The training dispatch groups tokens per batch row, which at T=1
        degenerates to ``min_capacity`` slots per row on every expert.
        Decode instead flattens the B tokens into ONE routing group with
        capacity C = B, so no token is dropped — and EVERY expert
        multiplies C rows whether routed to it or not: E·B·d·f, E/k times
        the routed rows, on the MXU each step (measured, not hidden: at 128
        experts top-6 that is 21x). The sigmoid router does not come here:
        :meth:`experts` multiplies routed rows only, at any T. Prefill
        (T>1) keeps the training per-row dispatch."""
        if "router" not in p or self.cfg.moe_router != "gshard":
            return self._mlp_block(y, p)
        cfg = self.cfg
        B, T, d = y.shape
        E = cfg.num_experts
        tg = B * T
        C = tg
        yt = y.reshape(tg, d)
        logits = yt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        combine, dispatch, aux = topk_gating(logits, cfg.moe_top_k, C)

        # (tg,E,C) x (tg,d) -> (E,C,d); the expert axis carries the same
        # all-to-all the training path's constraint emits.
        xs = jnp.einsum("tec,td->ecd", dispatch.astype(y.dtype), yt)
        xs = constrain(xs, P("expert", None, None))
        u = jnp.einsum("ecd,edf->ecf", xs, self._bank(p, "w_in", y.dtype))
        u = self._expert_bias(u, p, "b_in")
        if cfg.is_glu:
            g = jnp.einsum("ecd,edf->ecf", xs, self._bank(p, "w_gate", y.dtype))
            u = jax.nn.silu(g) * u
        else:
            u = _activation(u, cfg.activation)
        u = constrain(u, P("expert", None, "model"))
        out = jnp.einsum("ecf,efd->ecd", u, self._bank(p, "w_out", y.dtype))
        out = self._expert_bias(out, p, "b_out")
        out = constrain(out, P("expert", None, None))
        res = jnp.einsum("tec,ecd->td", combine.astype(y.dtype), out)
        return res.reshape(B, T, d), aux.astype(jnp.float32)

    # --------------------------------------------- routed rows, no capacity
    @jax.named_scope("moe_route")
    def route(self, yt, p, state=None):
        """Routing of (N, d) tokens, in float32 up to the chosen weights.
        Returns (idx (N, k) i32, weights (N, k) f32, the router's state).

        ``"sigmoid"`` (DeepSeek-V3 ``noaux_tc``): sigmoid scores; the top-k
        of score + ``router_bias`` are chosen — with ``moe_n_group`` > 1
        among the experts of the ``moe_topk_group`` best groups alone
        (:func:`kept_groups`: the group-limited choice, over ALL the router's
        experts whichever are held here) — and a chosen expert's weight is
        its UNBIASED score, normalised over the chosen and scaled. No state:
        None in; out None, or with groups the groups kept (N, n_group) bool.

        ``"zaya"`` (ZAYA1, arXiv:2511.17127): ``r = y Wd + bd`` into
        ``router_hidden``; **depth averaging** ``s = r + gamma * state``,
        ``state`` (N, router_hidden) float32 what the layer before left at
        the SAME token (zeros before the first); ``p = softmax(W3 gelu(W2
        gelu(W1 rmsnorm(s))))``; the expert chosen is the argmax of p +
        ``router_bias`` (a balancing bias, selection only) and its weight
        its own ``p``, unnormalised. Returns ``s`` as the state."""
        cfg = self.cfg
        if cfg.moe_router == "zaya":
            return self._route_zaya(yt, p, state)
        score = jax.nn.sigmoid(jnp.dot(
            yt.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        biased = score + p["router_bias"].astype(jnp.float32)
        kept = None
        if cfg.moe_n_group > 1:
            kept = kept_groups(biased, cfg.moe_n_group, cfg.moe_topk_group)
            biased = jnp.where(jnp.repeat(
                kept, cfg.num_experts // cfg.moe_n_group, axis=-1),
                biased, -jnp.inf)
        _, idx = jax.lax.top_k(biased, cfg.moe_top_k)
        w = jnp.take_along_axis(score, idx, axis=-1)
        if cfg.moe_norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), w * cfg.moe_routed_scale, kept

    def _route_zaya(self, yt, p, state):
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

        def dot(a, name):
            return jnp.dot(a, p[name].astype(f32), precision=hi)

        s = dot(yt.astype(f32), "router") + p["router_bd"].astype(f32) \
            + p["router_gamma"].astype(f32) * state
        t = _norm(s, p["router_norm"], None, "rmsnorm", self.cfg.norm_eps)
        t = _activation(dot(_activation(dot(t, "router_w1"), "gelu_exact"),
                            "router_w2"), "gelu_exact")
        prob = jax.nn.softmax(dot(t, "router_w3"), axis=-1)
        idx = jnp.argmax(prob + p["router_bias"].astype(f32), axis=-1)[:, None]
        return (idx.astype(jnp.int32),
                jnp.take_along_axis(prob, idx, axis=-1), s)

    BANKS = ("w_gate", "w_in", "w_out")

    def experts(self, y, p, banks=None, layer=None, routed=None,
                limits=None, live=None):
        """The routed expert layer on (B, T, d) (``routed``: the (idx, w) a
        caller that threads a router's state took from :meth:`route` itself;
        None: the stateless sigmoid router's, here): every token's k
        rows sorted by expert, each expert's rows padded to whole blocks of
        the dtype's sublane tile and multiplied by that expert alone
        (``ops/moe_matmul.py``), the weighted rows gathered back per token,
        the shared MLP added once. No capacity and no drop at any T: the
        sorted layout has room for every row whichever experts they choose.
        ``banks`` (with ``layer``, this layer's index in them): the
        segment's stacked expert weights ``{name: (L, E, ·, ·)}`` in place
        of ``p``'s own — a layer loop passes them so that no layer's bank is
        sliced out (``ops/moe_matmul.py``).

        **Told which experts it holds** (``moe_experts_held`` from
        ``moe_first_held`` on; the banks then hold those alone) the layer
        routes over all ``num_experts``, sorts and multiplies only the rows
        that chose a held one, weights them by the weights normalised over
        ALL k chosen and adds nothing for the absent: its part of the sum
        (the model-configs guide's chip's share).

        ``limits``: the (routed, shared) clamps of the layer's segment
        (``cfg.segment_limits``; None: ``swiglu_limit`` for both).

        Returns (out (B, T, d), stats, idx): ``stats`` f32 [most rows one
        expert got, experts touched, rows multiplied (padding included),
        rows that chose a held expert] — the counters the serving spans
        carry (rows routed = B·T·k is static) — and with ``moe_n_group`` > 1
        a fifth: the tokens (of the rows ``live`` (B,) bool marks; None:
        all) whose kept groups hold an expert held here; ``idx`` (B, T, k)
        i32 the experts chosen."""
        from ..ops.moe_matmul import block_rows, experts_swiglu

        cfg = self.cfg
        B, T, d = y.shape
        N, k = B * T, cfg.moe_top_k
        yt = y.reshape(N, d)
        idx, w, kept = routed + (None,) if routed is not None \
            else self.route(yt, p)
        bank = banks if banks is not None else p
        limit, shared_limit = limits or (cfg.swiglu_limit,) * 2
        with jax.named_scope("moe_experts"):
            bm = block_rows(y.dtype)
            # every expert held (held_experts = E from 0 on) is the same
            # layout: every pair has a row
            row_token, pair_row, pair_held, block_expert, used, counts = \
                held_layout(idx, cfg.held_experts, cfg.moe_first_held, bm)
            out = experts_swiglu(yt[row_token], bank["w_gate"], bank["w_in"],
                                 bank["w_out"], block_expert, used, bm=bm,
                                 layer=layer, limit=limit)
            # a row no block wrote is never read: pairs held elsewhere add 0
            rows = jnp.where(pair_held[:, None], out[pair_row], 0)
            routed = jnp.sum(rows.reshape(N, k, d).astype(jnp.float32)
                             * w[..., None], axis=1).astype(y.dtype)
        stats = [jnp.max(counts), jnp.sum(counts > 0), used * bm,
                 jnp.sum(counts)]
        if kept is not None:
            # the groups that hold an expert held here, by the kept groups
            size = cfg.num_experts // cfg.moe_n_group
            first = cfg.moe_first_held // size
            last = (cfg.moe_first_held + cfg.held_experts - 1) // size
            here = jnp.any(kept[:, first:last + 1], axis=-1).reshape(B, T)
            if live is not None:
                here &= live[:, None]
            stats.append(jnp.sum(here))
        stats = jnp.stack(stats).astype(jnp.float32)
        return (self._with_shared(routed, yt, p, shared_limit).reshape(
            B, T, d), stats, idx.reshape(B, T, k))

    def _with_shared(self, routed, yt, p, limit=None):
        """``routed`` (N, d) with the shared MLP's rows added, if any;
        ``limit``: its clamp (None: ``swiglu_limit``)."""
        if not self.cfg.moe_shared_d_ff:
            return routed
        limit = self.cfg.swiglu_limit if limit is None else limit
        with jax.named_scope("moe_shared"):
            u = _swiglu(yt @ p["ws_gate"].astype(yt.dtype),
                        lambda: yt @ p["ws_in"].astype(yt.dtype), limit)
            return routed + u @ p["ws_out"].astype(yt.dtype)

    def _fold_aux(self, aux):
        """Aux losses add up; the sigmoid router's aux is its routing
        (expert layers, B, S, k), kept as the scan stacked it."""
        return aux if jnp.issubdtype(aux.dtype, jnp.integer) \
            else jnp.sum(aux)

    def _join_aux(self, auxes: list):
        """With the sigmoid router ``apply(..., return_aux=True)`` returns
        the trunk's routing, the expert segments' (layers, B, S, k)
        together in layer order (dense segments have none), from the same
        program as the logits: what a comparison with a reference that
        follows the system's choice at a near-tie needs. Else the sum of
        the aux losses."""
        routing = [a for a in auxes if jnp.issubdtype(a.dtype, jnp.integer)]
        if routing:
            return jnp.concatenate(routing)
        return sum(auxes[1:], auxes[0])

    # ----------------------------------------------------------------- init
    def init(self, rng) -> dict:
        params = super().init(rng)
        cfg = self.cfg
        d, f, E = cfg.d_model, cfg.expert_dim, cfg.num_experts
        Eh = cfg.held_experts       # the banks hold this device's share
        depth = cfg.n_layer
        segs = self.segment_params(params["layers"])

        def dense(key, shape, scale):
            return jax.random.normal(key, shape, jnp.float32) * scale

        for i, ((kind, L), layers, limits) in enumerate(zip(
                cfg.segments, segs, cfg.segment_limits)):
            if kind != "moe":
                continue
            # base init skips the dense FFN of an expert segment
            k = iter(jax.random.split(jax.random.fold_in(rng, 1 + i), 8))
            if cfg.moe_router == "zaya":
                layers.update(self._init_zaya_router(next(k), L))
            else:
                layers["router"] = dense(next(k), (L, d, E), 0.02)
            gain = clamp_gain(cfg, limits[0])
            layers["w_in"] = dense(next(k), (L, Eh, d, f),
                                   gain / math.sqrt(d))
            layers["w_out"] = dense(next(k), (L, Eh, f, d),
                                    1.0 / (math.sqrt(2 * depth * f)
                                           * gain ** 2))
            if cfg.is_glu:
                layers["w_gate"] = dense(next(k), (L, Eh, d, f),
                                         gain / math.sqrt(d))
            if cfg.use_bias:
                layers["b_in"] = jnp.zeros((L, Eh, f), jnp.float32)
                layers["b_out"] = jnp.zeros((L, Eh, d), jnp.float32)
            if cfg.moe_router == "sigmoid":
                # a trained model's selection bias is small and not zero;
                # drawn so, that a path which drops it chooses differently
                layers["router_bias"] = dense(next(k), (L, E), 0.02)
            if cfg.moe_shared_d_ff:
                fs = cfg.moe_shared_d_ff
                gain = clamp_gain(cfg, limits[1])
                layers["ws_in"] = dense(next(k), (L, d, fs),
                                        gain / math.sqrt(d))
                layers["ws_gate"] = dense(next(k), (L, d, fs),
                                          gain / math.sqrt(d))
                layers["ws_out"] = dense(next(k), (L, fs, d),
                                         1.0 / (math.sqrt(2 * depth * fs)
                                                * gain ** 2))
        return params

    def _init_zaya_router(self, key, L: int) -> dict:
        """A layer's zaya router. At the trunk's normal scales a random
        router is flat (every p near 1 / E) and its top-1 a coin toss
        between near-ties; a trained one is decided and, by its balancing
        bias, even. The last matrix is drawn ``ROUTER_OUT_GAIN`` wide so
        that the softmax spreads; the two matrices behind a gelu are drawn
        with columns that sum to 0, so that what every token shares (the
        gelu's positive mean) is no expert's fixed advantage (on the chip,
        48 rows 512 positions deep: 9.8 of 16 experts touched a layer
        against 7.8 without; 14.7 with the residual biases small too,
        ``transformer.RES_SCALE_SD``); ``gamma`` and the balancing bias so
        that a path which drops either chooses differently."""
        cfg = self.cfg
        d, R, E = cfg.d_model, cfg.router_hidden, cfg.num_experts
        k = iter(jax.random.split(key, 8))

        def normal(shape, sd, centred=False):
            w = sd * jax.random.normal(next(k), shape, jnp.float32)
            # a gelu's output has a positive mean, the same for every
            # token; a matrix whose columns sum to 0 over its inputs hands
            # none of it on as one expert's fixed advantage
            return w - w.mean(axis=-2, keepdims=True) if centred else w

        return {"router": normal((L, d, R), 1.0 / math.sqrt(d)),
                "router_bd": normal((L, R), 0.1),
                "router_gamma": 0.5 + normal((L, 1), 0.1),
                "router_norm": jnp.ones((L, R), jnp.float32),
                "router_w1": normal((L, R, R), 1.0 / math.sqrt(R)),
                "router_w2": normal((L, R, R), 1.0 / math.sqrt(R), True),
                "router_w3": normal((L, R, E),
                                    ROUTER_OUT_GAIN / math.sqrt(R), True),
                "router_bias": normal((L, E), 0.02)}

    # ---------------------------------------------------------------- specs
    def param_specs(self) -> dict:
        specs = super().param_specs()
        cfg = self.cfg
        segs = self.segment_params(specs["layers"])
        for (kind, _), layers in zip(cfg.segments, segs):
            if kind != "moe":
                continue
            layers["router"] = P(None, None, None)
            if cfg.moe_router == "zaya":
                layers.update(
                    router_bd=P(None, None), router_gamma=P(None, None),
                    router_norm=P(None, None), router_bias=P(None, None),
                    **{f"router_w{i}": P(None, None, None) for i in (1, 2, 3)})
            layers["w_in"] = P(None, "expert", None, "model")
            layers["w_out"] = P(None, "expert", "model", None)
            if cfg.is_glu:
                layers["w_gate"] = P(None, "expert", None, "model")
            if cfg.use_bias:
                layers["b_in"] = P(None, "expert", "model")
                layers["b_out"] = P(None, "expert", None)
            if cfg.moe_router == "sigmoid":
                layers["router_bias"] = P(None, None)
            if cfg.moe_shared_d_ff:
                layers["ws_in"] = P(None, None, "model")
                layers["ws_gate"] = P(None, None, "model")
                layers["ws_out"] = P(None, "model", None)
        return specs

    def fp32_param_names(self) -> tuple[str, ...]:
        """Leaf names kept in fp32 by the engine's compute cast (router
        precision governs tie-breaking stability)."""
        from .cca import FP32_NAMES as cca

        names = ("router", "router_bias", "router_bd", "router_gamma",
                 "router_norm", "router_w1", "router_w2", "router_w3") \
            + (cca if self.cfg.attention == "cca" else ())
        if self.cfg.mixer_pattern:
            from . import kda, mhc

            names += kda.FP32_NAMES + mhc.FP32_NAMES
        return names
