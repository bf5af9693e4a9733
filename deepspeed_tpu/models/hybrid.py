"""A trunk of one mixer a layer (NemotronH, ``model_type: nemotron_h``).

Layer ``i`` is ``cfg.block_pattern[i]`` alone: ``x <- x + mixer_i(RMSNorm_i(
x))``, no FFN beside it; then the final norm and an untied head.

- ``M``: a Mamba-2 mixer (``models/ssm.py``).
- ``*``: causal GQA attention with NO position code (the family applies
  none), the trunk's own ``_attention_block``.
- ``E``: latent experts. The router is DeepSeek-V3's ``noaux_tc`` over ALL
  ``num_experts`` (``MoETransformerLM.route``: sigmoid, selection bias,
  normalised top-k x scale). The routed experts live in a latent of
  ``moe_latent_dim`` under the model width: ``u = y W_dn``; expert e is
  ``relu(u W1_e)^2 W2_e`` (not gated: one up matrix); ``routed = (sum_chosen
  w_e expert_e(u)) W_up``. A shared expert ``relu(y Ws1)^2 Ws2`` on the full
  width is added to every token. **The layer is told which experts it
  holds** (``moe_first_held`` .. + ``moe_experts_held``): it routes over all
  of them, sorts only the rows that chose a held expert and computes those —
  its part of the sum, which is linear in the experts up to ``W_up`` (the
  model-configs guide's chip's share; ``tests/unit/test_hybrid_trunk.py``
  adds the shares up to the whole layer).

``P`` (Falcon-H1, ``model_type: falcon_h1``; every layer of the trunk, or
none) is another block: the Mamba-2 mixer AND rotary GQA attention on the
same normed input, both added to the stream, then a gated FFN behind a norm
of its own, every branch times its ``cfg.mup`` scalar where the model
publishes it (:func:`parallel_qkv`, :func:`parallel_close`; the cache path is
``inference/kinds/parallel.py``):

    y = RMS_1(x);  x = x + Attn(y m_ai) m_ao + Mamba2(y) m_so
    x = x + W_down(silu(W_gate RMS_2(x) m_g) * W_up RMS_2(x)) m_d

Segments are runs of equal letters (``TransformerConfig.segments``), each
scanned over its own stacked weights; ``params["layers"]`` is the tuple of
them. The cache path is ``inference/kinds/hybrid.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import ssm
from .moe import MoETransformerLM, held_layout
from .transformer import TransformerLM, _norm, _rope

KINDS = "ME*P"


def parallel_qkv(cfg, y, p, positions):
    """A ``P`` layer's q (B, T, H, hd), k, v (B, T, KV, hd) from the normed
    input ``y``: the input times ``attn_in``, the keys times ``key``, then
    the rotation over the whole head (halves paired, float32 angles)."""
    B, T, _ = y.shape
    m = cfg.mup
    u = y * jnp.asarray(m.attn_in, y.dtype) if m.attn_in != 1.0 else y
    q, k, v = (u @ p[name].astype(u.dtype) for name in ("wq", "wk", "wv"))
    if m.key != 1.0:
        k = k * jnp.asarray(m.key, k.dtype)
    q = q.reshape(B, T, cfg.n_head, cfg.head_dim)
    k = k.reshape(B, T, cfg.kv_heads, cfg.head_dim)
    q, k = _rope(q, k, positions, cfg.rope_theta, cfg.rotary_dim,
                 halves=cfg.rope_halves)
    return q, k, v.reshape(B, T, cfg.kv_heads, cfg.head_dim)


@jax.named_scope("mlp")
def gated_ffn(cfg, y2, p):
    """A ``P`` layer's FFN on its own normed input: the gate's
    pre-activation times ``mlp_gate``, the output times ``mlp_down``."""
    m = cfg.mup
    u = (y2 @ p["w_gate"].astype(y2.dtype)) * jnp.asarray(m.mlp_gate,
                                                          y2.dtype)
    u = jax.nn.silu(u) * (y2 @ p["w_up"].astype(y2.dtype))
    return (u @ p["w_down"].astype(y2.dtype)) * jnp.asarray(m.mlp_down,
                                                           y2.dtype)


def parallel_close(cfg, x, o, mixed, p):
    """A ``P`` layer behind its two mixers: the attention's heads ``o`` (B,
    T, H, hd) through ``wo`` and the mixer's output ``mixed`` (B, T, d) onto
    the stream, each times its multiplier, then the FFN."""
    B, T, _ = x.shape
    m = cfg.mup
    o = o.reshape(B, T, -1) @ p["wo"].astype(x.dtype)
    x = x + o * jnp.asarray(m.attn_out, x.dtype) \
        + mixed * jnp.asarray(m.ssm_out, x.dtype)
    y2 = _norm(x, p["ln2_scale"], None, cfg.norm, cfg.norm_eps)
    return x + gated_ffn(cfg, y2, p)


class HybridLM(TransformerLM):
    """init / apply / param_specs for a ``block_pattern`` trunk."""

    route = MoETransformerLM.route

    def __init__(self, config, attention_fn=None):
        super().__init__(config, attention_fn=attention_fn)
        c = config
        pat = c.block_pattern
        if len(pat) != c.n_layer or set(pat) - set(KINDS):
            raise ValueError(
                f"block_pattern {pat!r} has to name each of the {c.n_layer} "
                f"layers' mixer, one of {KINDS!r}")
        if "P" in pat and (set(pat) != {"P"} or c.pos_embedding != "rope"
                           or not c.rope_halves or not c.is_glu):
            raise ValueError(
                "'P' is the Falcon-H1 block, every layer of its trunk: "
                "rotary attention (pos_embedding='rope', rope_halves) beside "
                "the Mamba-2 mixer, a gated FFN (activation='silu_glu')")
        if (c.use_bias or c.norm != "rmsnorm" or not c.causal
                or c.pos_embedding != ("rope" if "P" in pat else "none")
                or c.objective != "clm"
                or c.loop_steps > 1 or c.sandwich_norm or c.post_ln
                or c.parallel_residual or c.tie_embeddings
                or c.attention != "mha" or attention_fn is not None):
            raise ValueError(
                "a block_pattern trunk is the NemotronH block: a causal LM, "
                "RMSNorm before each layer's one mixer, no biases, no "
                "position code (pos_embedding='none'), an untied head")
        if set(pat) & set("MP") and (
                min(c.ssm_heads, c.ssm_head_dim, c.ssm_state) <= 0
                or c.ssm_heads % c.ssm_groups or c.ssm_conv < 2):
            raise ValueError("'M' / 'P' layers need ssm_heads / ssm_head_dim "
                             "/ ssm_state, ssm_groups dividing the heads")
        if "E" in pat:
            E, held = c.num_experts, c.held_experts
            if (c.moe_router != "sigmoid" or c.moe_top_k > E or held > E
                    or c.moe_first_held % held or c.moe_first_held >= E):
                raise ValueError(
                    "'E' layers take the sigmoid router over num_experts and "
                    "hold moe_experts_held of them from a multiple of that on")

    # ----------------------------------------------------------------- init
    def init(self, rng) -> dict:
        cfg = self.cfg
        d, depth = cfg.d_model, cfg.n_layer
        k_embed, k_head = jax.random.split(rng)
        return {
            # (a table and a head are drawn over their multipliers, as
            # every branch is: _init_run)
            "tok_embed": jax.random.normal(k_embed, (cfg.vocab_size, d),
                                           jnp.float32)
            * (0.02 / cfg.mup.embed),
            "layers": tuple(
                self._init_run(jax.random.fold_in(rng, 100 + i), kind, n,
                               depth)
                for i, (kind, n) in enumerate(cfg.segments)),
            "lnf_scale": jnp.ones((d,), jnp.float32),
            "lm_head": jax.random.normal(k_head, (d, cfg.vocab_size),
                                         jnp.float32)
            * (0.02 / cfg.mup.head),
        }

    def _init_run(self, key, kind: str, n: int, depth: int) -> dict:
        """Stacked weights of a run of ``n`` layers of ``kind``. Every
        layer adds ONE branch to the stream, so an output projection is
        scaled by 1 / sqrt(depth) (``rescale_prenorm_residual``); a ``P``
        layer adds three, so by 1 / sqrt(3 depth). **Every matrix is drawn
        over the ``cfg.mup`` scalars that multiply what it gives**, so that
        a branch behind its multiplier adds to the stream what it adds in a
        family without them (and the scores are not flat under a key
        multiplier of 2^-6.5): with the usual draw the multipliers would
        shrink every branch to nothing beside the residual, and a path that
        dropped a mixer would agree with one that kept it."""
        cfg = self.cfg
        d, m = cfg.d_model, cfg.mup
        if kind == "M":
            return ssm.init_params(cfg, key, n, depth)
        k = iter(jax.random.split(key, 10))

        def dense(shape, fan_in, branch: bool = False, over: float = 1.0):
            return jax.random.normal(next(k), shape, jnp.float32) \
                / (math.sqrt(fan_in * (depth if branch else 1)) * over)

        if kind == "P":
            depth *= 3
        out = {"ln1_scale": jnp.ones((n, d), jnp.float32)}
        if kind in "*P":
            h, kv, hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
            out.update(wq=dense((n, d, h * hd), d, over=m.attn_in),
                       wk=dense((n, d, kv * hd), d, over=m.attn_in * m.key),
                       wv=dense((n, d, kv * hd), d, over=m.attn_in),
                       wo=dense((n, h * hd, d), h * hd, True, m.attn_out))
        if kind == "*":
            return out
        if kind == "P":
            f = cfg.ffn_dim
            out.update(ssm.init_params(cfg, next(k), n, depth),
                       ln2_scale=jnp.ones((n, d), jnp.float32),
                       w_gate=dense((n, d, f), d, over=m.mlp_gate),
                       w_up=dense((n, d, f), d),
                       w_down=dense((n, f, d), f, True, m.mlp_down))
            return out
        E, held = cfg.num_experts, cfg.held_experts
        lat, f, fs = cfg.moe_latent_dim or d, cfg.expert_dim, \
            cfg.moe_shared_d_ff
        out.update(
            router=jax.random.normal(next(k), (n, d, E), jnp.float32) * 0.02,
            # a trained model's selection bias is small and not zero; drawn
            # so, that a path which drops it chooses differently
            router_bias=jax.random.normal(next(k), (n, E), jnp.float32) * 0.02,
            w1=dense((n, held, lat, f), lat),
            w2=dense((n, held, f, lat), f, lat == d))
        if lat != d:
            out.update(w_dn=dense((n, d, lat), d),
                       w_up=dense((n, lat, d), lat, True))
        if fs:
            out.update(ws_in=dense((n, d, fs), d),
                       ws_out=dense((n, fs, d), fs, True))
        return out

    def param_specs(self) -> dict:
        """Every leaf replicated: a mesh is refused for these block kinds
        (``serving/engine.py``), so no rule here is under a test."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)

    def fp32_param_names(self) -> tuple:
        return ("router", "router_bias") + ssm.FP32_NAMES

    # -------------------------------------------------------------- mixers
    @jax.named_scope("latent_experts")
    def latent_experts(self, y, p):
        """The ``E`` mixer on (B, T, d). Returns (out, stats, idx):
        ``stats`` f32 [most rows one held expert got, held experts touched,
        rows multiplied (padding included), rows that chose a held expert];
        ``idx`` (B, T, k) i32 the experts chosen, of all ``num_experts``."""
        from ..ops.moe_matmul import block_rows, experts_relu2

        cfg = self.cfg
        B, T, d = y.shape
        k, Eh = cfg.moe_top_k, cfg.held_experts
        N = B * T
        yt = y.reshape(N, d)
        idx, w, _ = self.route(yt, p)
        u = yt @ p["w_dn"].astype(y.dtype) if "w_dn" in p else yt
        bm = block_rows(y.dtype)
        row_token, pair_row, pair_held, block_expert, used, counts = \
            held_layout(idx, Eh, cfg.moe_first_held, bm)
        out = experts_relu2(u[row_token], p["w1"], p["w2"], block_expert,
                            used, bm=bm)
        # a row no block wrote is never read: pairs held elsewhere add 0
        rows = jnp.where(pair_held[:, None], out[pair_row], 0)
        routed = jnp.sum(rows.reshape(N, k, -1).astype(jnp.float32)
                         * w[..., None], axis=1).astype(y.dtype)
        if "w_up" in p:
            routed = routed @ p["w_up"].astype(y.dtype)
        stats = jnp.stack([jnp.max(counts), jnp.sum(counts > 0), used * bm,
                           jnp.sum(counts)]).astype(jnp.float32)
        if cfg.moe_shared_d_ff:
            with jax.named_scope("moe_shared"):
                h = jnp.square(jax.nn.relu(yt @ p["ws_in"].astype(y.dtype)))
                routed = routed + h @ p["ws_out"].astype(y.dtype)
        return routed.reshape(B, T, d), stats, idx.reshape(B, T, k)

    def _mixer(self, kind: str, x, p, positions, attn_mask):
        """One layer of ``kind`` on the whole sequence (no cache): (x, the
        experts an ``E`` layer chose (B, S, k), else None)."""
        cfg = self.cfg
        if kind == "*":
            return x + self._attention_block(x, p, positions, attn_mask), None
        y = _norm(x, p["ln1_scale"], None, cfg.norm, cfg.norm_eps)
        if kind == "E":
            out, _, idx = self.latent_experts(y, p)
            return x + out, idx
        B = x.shape[0]
        empty = {name: jnp.zeros(shape, jnp.float32 if name == "ssm"
                                 else x.dtype)
                 for name, shape in ssm.state_shapes(cfg, B).items()}
        mixed = ssm.mix_chunk(cfg, p, y, empty["ssm"], empty["conv"])[0]
        if kind == "M":
            return x + mixed, None
        with jax.named_scope("parallel_mixers"):
            o = self.attention_fn(*parallel_qkv(cfg, y, p, positions),
                                  mask=None)
        return parallel_close(cfg, x, o, mixed, p), None

    def _trunk(self, params, input_ids, attn_mask, remat_policy):
        """Embed + the layers: (B, S) -> ((B, S, d) before the final norm,
        the ``E`` layers' routing (expert layers, B, S, k) in layer order:
        what a comparison with a reference that follows the system's choice
        at a near-tie needs, from the same program as the logits)."""
        if attn_mask is not None or remat_policy is not None:
            raise NotImplementedError(
                "a block_pattern trunk is served, not trained: no padding "
                "mask (the recurrence has none) and no remat policy")
        x, positions = self._embed(params, input_ids)
        routing = []
        for (kind, _), seg in zip(self.cfg.segments, params["layers"]):
            x, idx = lax.scan(
                lambda x, p, kind=kind: self._mixer(kind, x, p, positions,
                                                    None), x, seg)
            if kind == "E":
                routing.append(idx)
        return x, jnp.concatenate(routing) if routing else jnp.float32(0.0)
