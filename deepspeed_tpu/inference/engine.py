"""InferenceEngine: TP-sharded, jit-compiled generation.

Reference: ``deepspeed/inference/engine.py:39`` — wraps the model, builds the
TP group, converts dtype, injects kernels, captures CUDA graphs, and serves
``generate``. Here: params are device_put against the model's sharding specs
over a ``model``-axis mesh (TP == AutoTP without the module-graph walking,
since the sharding rules ARE the policy), the decode loop is one jitted
``lax.scan`` over a static KV cache (graph capture subsumed by XLA), the
serving tree fuses the attention projections into one column-sharded
[wq|wk|wv] GEMM, and int8/int4 WOQ keeps weights quantized END-TO-END —
the decode step consumes them through the fused dequant-in-VMEM Pallas
GEMM (ops/woq_matmul.py), so each token re-reads int8 bytes from HBM, not
a hoisted bf16 copy (docs/WOQ_DECODE.md).
"""

from __future__ import annotations

import copy
import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import spans as _spans
from ..platform.mesh import MeshSpec, build_mesh, fit_specs
from ..utils.logging import log_dist
from .config import InferenceConfig
from .decode import decode_tokens, generate_tokens, prefill_tokens
from .quantization import (dequantize_params, quantize_params,
                           quantized_bytes, quantized_shardings)
from .sampling import per_request_keys, sample_logits

# Compiled generate programs kept per engine (each pins an executable).
_MAX_COMPILED_SHAPES = 32


def model_with_dtype(model, dtype):
    """Shallow-clone a model so its config compute dtype matches ``dtype``
    (the model reads ``cfg.dtype`` for every cast — the engine's dtype knob
    must actually reach it)."""
    if model.cfg.dtype == dtype:
        return model
    clone = copy.copy(model)
    clone.cfg = dataclasses.replace(model.cfg, dtype=dtype)
    return clone


class InferenceEngine:
    """Owns sharded params + compiled prefill/decode/generate."""

    @_spans.timed_init("inference")
    def __init__(self, model, params, config: InferenceConfig | dict | None = None,
                 mesh: Optional[Mesh] = None):
        self.config = InferenceConfig.from_any(config)
        cfg = self.config
        if cfg.dequant_per_step:
            from ..utils.logging import warning_once

            warning_once(
                "inference config: dequant_per_step is obsolete — decode "
                "now keeps weights quantized end-to-end and dequantizes "
                "at each consumption site (the fused WOQ GEMM); the knob "
                "is accepted for config compat but changes nothing.")
        self.compute_dtype = cfg.compute_dtype
        self.model = model_with_dtype(model, self.compute_dtype)
        if getattr(self.model.cfg, "num_experts", 1) > 1:
            # MoE prefill routes through the training dispatch; serve with
            # the (larger) eval capacity factor so fewer tokens drop
            # (reference eval_capacity_factor). Clone before flagging so a
            # shared training model doesn't inherit eval routing.
            if self.model is model:
                self.model = copy.copy(model)
            self.model.moe_eval_mode = True
        num_experts = int(getattr(self.model.cfg, "num_experts", 1) or 1)
        if cfg.expert_parallel > 1:
            # reference expert-parallel serving (moe_inference.py:159 builds
            # the ep group); here the serving mesh carries an 'expert' axis
            # and the MoE dispatch's sharding constraints do the all-to-all
            if num_experts % cfg.expert_parallel != 0:
                raise ValueError(
                    f"expert_parallel={cfg.expert_parallel} must divide "
                    f"num_experts={num_experts} (dense models serve with "
                    "expert_parallel=1)")
        self.mesh = mesh or build_mesh(MeshSpec(
            data=-1, expert=cfg.expert_parallel, model=cfg.tensor_parallel))

        # Same fp32 exemptions as the training engine's compute cast
        # (runtime/engine.py _cast_compute): leaves the model names — MoE
        # routers above all — stay fp32 so near-tie routing decisions
        # don't flap across bf16 rounding at serve time.
        keep = set(getattr(self.model, "fp32_param_names", lambda: ())())

        def _cast(path, p):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in keep or not jnp.issubdtype(p.dtype, jnp.floating):
                return p
            return p.astype(self.compute_dtype)

        cast = jax.tree_util.tree_map_with_path(_cast, params)
        specs = self.model.param_specs()
        # Fuse the attention projections into one [wq | wk | wv] weight
        # for the serving tree: the decode step runs ONE batched GEMM over
        # the shared post-norm activations instead of three skinny ones
        # (reference qkv_gemm fusion, csrc/transformer/inference). The
        # column-concat keeps Megatron column sharding: spec stays
        # (None, None, "model").
        self._fused = self._can_fuse_qkv(cast)
        if self._fused:
            cast = self._fuse_qkv_params(cast)
            specs = self._fuse_qkv_specs(specs)
        # a TP-sharded dim the mesh does not divide (an odd vocab) replicates
        specs = fit_specs(specs, cast, self.mesh)
        if cfg.quantize:
            # WOQ x TP: quantize straight into the sharded layout — the
            # shardings for the quantized tree come from the same
            # param_specs the dense path uses (scales follow their weights;
            # quantized_shardings docs), and each leaf's spec travels in
            # its aux data so the decode-side kernel dispatch can
            # shard_map accordingly. eval_shape first so nothing is ever
            # materialized unsharded.
            quant = partial(quantize_params, group_size=cfg.quant_group_size,
                            bits=cfg.quant_bits, specs=specs)
            q_shapes = jax.eval_shape(quant, cast)
            shardings = quantized_shardings(specs, q_shapes, self.mesh)
            with self.mesh:
                self.params = jax.jit(quant, out_shardings=shardings)(cast)
            # the decode consumption sites read this flag off the model
            # (shared code paths can't thread an engine handle through);
            # clone first so a shared training model isn't flagged
            if self.model is model:
                self.model = copy.copy(model)
            self.model.woq_kernel = cfg.woq_kernel_resolved()
            log_dist(f"inference: int{cfg.quant_bits} WOQ, "
                     f"{quantized_bytes(self.params)/2**20:.0f}"
                     f" MiB weights, tp={cfg.tensor_parallel}, "
                     f"kernel={self.model.woq_kernel}", ranks=[0])
        else:
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s if s is not None else P()),
                specs, is_leaf=lambda x: x is None or isinstance(x, P))
            self.params = jax.device_put(cast, shardings)
        if cfg.decode_chunk < 0:
            raise ValueError(f"decode_chunk must be >= 0, got "
                             f"{cfg.decode_chunk}")
        if cfg.tp_comm_quant not in (0, 8):
            raise ValueError(f"tp_comm_quant must be 0 (off) or 8 (int8), "
                             f"got {cfg.tp_comm_quant}")
        if cfg.tp_comm_quant:
            # stamped on the model like woq_kernel: the shared decode step
            # can't thread an engine handle through. Clone first so a
            # shared training model isn't flagged.
            if self.model is model:
                self.model = copy.copy(model)
            self.model.tp_quant = cfg.tp_comm_quant
            log_dist(f"inference: int{cfg.tp_comm_quant} quantized TP "
                     f"decode collective (tp={cfg.tensor_parallel}; "
                     "wo/w_out psums two-sided int8, logits stay fp)",
                     ranks=[0])
        self._gen_cache: OrderedDict = OrderedDict()
        # split prefill/decode program caches: used by request tracing AND
        # by the chunked-decode early-stop path (decode_chunk > 0)
        self._prefill_cache: OrderedDict = OrderedDict()
        self._decode_cache: OrderedDict = OrderedDict()
        self._rng = jax.random.PRNGKey(cfg.seed)
        self._fwd = jax.jit(self._forward_impl)
        # Request tracing (observability): ring buffer + Serve/* registry.
        # Built lazily-enough that the disabled path allocates nothing and
        # generate() stays on the single fused program with zero added
        # host syncs.
        self.tracer = None
        if cfg.observability:
            from ..observability.tracing import RequestTracer
            from ..utils.timer import peak_hbm_bw_for
            from .quantization import decode_weight_bytes

            try:
                peak_bw = peak_hbm_bw_for(jax.devices()[0])
            except ValueError as e:
                # Unknown hardware must not break serving — latencies still
                # trace; only the MBU attribution goes dark.
                log_dist(f"inference observability: MBU disabled ({e})",
                         ranks=[0])
                peak_bw = None
            self.tracer = RequestTracer(
                ring_size=cfg.trace_ring_size,
                bytes_per_step=decode_weight_bytes(self.params),
                peak_bw=peak_bw)

    # ------------------------------------------------------------ qkv fuse
    def _can_fuse_qkv(self, params) -> bool:
        """Only decoder trunks that generate get the fused serving layout
        (the training ``apply`` path reads per-projection names; encoder /
        feature towers only ever run ``forward``, which would pay the
        unfuse slicing for nothing)."""
        layers = params.get("layers") if isinstance(params, dict) else None
        return (getattr(self.model.cfg, "objective", None) == "clm"
                and isinstance(layers, dict)
                and all(k in layers for k in ("wq", "wk", "wv")))

    def _fuse_qkv_params(self, params):
        layers = dict(params["layers"])
        layers["wqkv"] = jnp.concatenate(
            [layers.pop("wq"), layers.pop("wk"), layers.pop("wv")], axis=-1)
        if all(k in layers for k in ("bq", "bk", "bv")):
            layers["bqkv"] = jnp.concatenate(
                [layers.pop("bq"), layers.pop("bk"), layers.pop("bv")],
                axis=-1)
        return {**params, "layers": layers}

    def _fuse_qkv_specs(self, specs):
        layers = dict(specs["layers"])
        for k in ("wq", "wk", "wv"):
            layers.pop(k, None)
        layers["wqkv"] = P(None, None, "model")
        if "bq" in layers:
            for k in ("bq", "bk", "bv"):
                layers.pop(k, None)
            layers["bqkv"] = P(None, "model")
        return {**specs, "layers": layers}

    def _unfused(self, params):
        """Split the serving tree's fused qkv back into per-projection
        leaves (XLA slices; only the cold ``forward`` path pays this)."""
        if not self._fused:
            return params
        cfg = self.model.cfg
        qd = cfg.n_head * cfg.head_dim
        kvd = cfg.kv_heads * cfg.head_dim
        layers = dict(params["layers"])
        w = layers.pop("wqkv")
        layers["wq"], layers["wk"], layers["wv"] = (
            w[..., :qd], w[..., qd:qd + kvd], w[..., qd + kvd:])
        if "bqkv" in layers:
            b = layers.pop("bqkv")
            layers["bq"], layers["bk"], layers["bv"] = (
                b[..., :qd], b[..., qd:qd + kvd], b[..., qd + kvd:])
        return {**params, "layers": layers}

    # -------------------------------------------------------------- forward
    def _materialized(self, params):
        if self.config.quantize:
            return dequantize_params(params, self.compute_dtype)
        return params

    def _forward_impl(self, params, input_ids):
        return self.model.apply(self._unfused(self._materialized(params)),
                                input_ids)

    def forward(self, input_ids) -> jnp.ndarray:
        """Full forward (no cache): (B, S) → (B, S, V) logits."""
        with self.mesh:
            return self._fwd(self.params, jnp.asarray(input_ids))

    __call__ = forward

    # ------------------------------------------------------------- generate
    def _generate_impl(self, params, input_ids, rng, *, max_new: int,
                       temperature: float, top_k: int, top_p: float,
                       greedy: bool, cache_len=None):
        # Quantized trees stay int8/int4 through the whole decode scan —
        # the step's consumption sites dispatch per-use (generate_tokens
        # docs). Only the prefill materializes (compute-bound; dense is
        # right there). ``dequant_per_step`` is subsumed: decode never
        # re-reads a dequantized copy anymore.
        return generate_tokens(
            self.model, params,
            input_ids, rng, max_new=max_new,
            sampler=self._sampler(temperature, top_k, top_p, greedy),
            eos_token_id=self.config.eos_token_id,
            cache_dtype=self.compute_dtype,
            flash_decode=self.config.flash_decode_resolved(),
            materialize=self._materialized if self.config.quantize else None,
            cache_len=cache_len)

    def _sampler(self, temperature: float, top_k: int, top_p: float,
                 greedy: bool):
        return partial(sample_logits, temperature=temperature, top_k=top_k,
                       top_p=top_p, greedy=greedy)

    def _prefill_impl(self, params, input_ids, rng, *, max_new: int,
                      temperature: float, top_k: int, top_p: float,
                      greedy: bool, cache_len=None):
        return prefill_tokens(
            self.model, params, input_ids, rng, max_new=max_new,
            sampler=self._sampler(temperature, top_k, top_p, greedy),
            eos_token_id=self.config.eos_token_id,
            cache_dtype=self.compute_dtype,
            flash_decode=self.config.flash_decode_resolved(),
            materialize=self._materialized if self.config.quantize else None,
            cache_len=cache_len)

    def _decode_impl(self, params, carry, *, steps: int, temperature: float,
                     top_k: int, top_p: float, greedy: bool,
                     return_carry: bool = False):
        return decode_tokens(
            self.model, params, carry, steps=steps,
            sampler=self._sampler(temperature, top_k, top_p, greedy),
            eos_token_id=self.config.eos_token_id,
            flash_decode=self.config.flash_decode_resolved(),
            return_carry=return_carry)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def generate(self, input_ids, max_new_tokens: Optional[int] = None, *,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 greedy: bool = False, rng: Optional[jax.Array] = None,
                 request_seeds=None, cache_len: Optional[int] = None):
        """(B, S) prompt ids → (B, max_new_tokens) continuations.

        Sampled calls draw from the engine's persistent PRNG stream (pass
        ``rng`` explicitly for reproducibility). ``request_seeds`` — one
        int per row — switches to per-request sampling streams instead:
        each row's draws are folded from its own seed, so the same request
        reproduces bit-identically whether served alone, in any static
        batch, or through the continuous-batching scheduler
        (``serving.ServingEngine`` uses the same per-row chains).
        ``cache_len`` overrides the tight ``S + max_new`` KV allocation —
        bucket it to serve many shapes from one compiled program, and pin
        it to the serving engine's ``max_len`` to reproduce a served
        request exactly (cache width is part of the sampled bit-stream).
        One program is compiled per (shape, knobs) tuple and kept in a
        bounded LRU.
        """
        # Non-CLM guard lives in generate_tokens (shared with HybridEngine);
        # re-check here so the error surfaces before a jit trace is built.
        objective = getattr(getattr(self.model, "cfg", None), "objective", "clm")
        if objective != "clm":
            raise ValueError(
                f"generate() needs a causal LM head; this model's objective "
                f"is {objective!r} — use forward() (MLM logits / feature "
                "hidden states) instead")
        input_ids = jnp.asarray(input_ids, jnp.int32)
        max_new = int(max_new_tokens or self.config.max_out_tokens)
        if request_seeds is not None:
            if rng is not None:
                raise ValueError("pass either rng or request_seeds, not both")
            if len(request_seeds) != input_ids.shape[0]:
                raise ValueError(
                    f"request_seeds has {len(request_seeds)} entries for a "
                    f"batch of {input_ids.shape[0]}")
            rng = per_request_keys(request_seeds)
        rng = rng if rng is not None else self._next_rng()
        if cache_len is not None:
            cache_len = int(cache_len)
        # rng shape is part of the program signature: a (B, 2) per-row key
        # stack samples through vmapped draws, a (2,) key through one
        key = (input_ids.shape, tuple(rng.shape), max_new, cache_len,
               float(temperature), int(top_k), float(top_p), bool(greedy))
        knobs = dict(temperature=temperature, top_k=top_k, top_p=top_p,
                     greedy=greedy)
        if self.config.decode_chunk > 0:
            return self._chunked_generate(input_ids, rng, key, max_new,
                                          knobs, cache_len)
        if self.tracer is not None:
            return self._traced_generate(input_ids, rng, key, max_new,
                                         knobs, cache_len)
        # Fast path: ONE fused prefill+decode program, nothing read back to
        # the host until the caller consumes the tokens — tracing disabled
        # means zero added synchronization.
        fn = self._cached(self._gen_cache, key, lambda: jax.jit(
            partial(self._generate_impl, max_new=max_new,
                    cache_len=cache_len, **knobs)))
        with self.mesh:
            return fn(self.params, input_ids, rng)

    @staticmethod
    def _cached(cache: OrderedDict, key, build, cap: int = _MAX_COMPILED_SHAPES):
        """Get-or-build with the engine's bounded-LRU policy (ONE policy:
        the fused / prefill / decode caches here and the serving engine's
        program cache all go through this)."""
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
            if len(cache) > cap:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return fn

    def _traced_generate(self, input_ids, rng, key, max_new: int,
                         knobs: dict, cache_len=None):
        """Request-traced generation: prefill and decode as two compiled
        programs so their wall times are separable (TTFT vs per-token
        decode). Costs one host sync between the phases; tokens match the
        fused path bit-for-bit (same sampler chain, same rng splits)."""
        B, S = input_ids.shape
        cold = key not in self._prefill_cache
        pf = self._cached(self._prefill_cache, key, lambda: jax.jit(
            partial(self._prefill_impl, max_new=max_new,
                    cache_len=cache_len, **knobs)))
        # The carry (KV cache above all) is dead after the decode call:
        # donate it so the scan reuses the prefill cache buffers in place —
        # matching the fused path, where the cache lives in the scan carry
        # and is never copied. Without donation each traced request would
        # hold two full caches and pay a copy the tracer then mis-attributes
        # to decode time.
        dc = self._cached(self._decode_cache, key, lambda: jax.jit(
            partial(self._decode_impl, steps=max_new - 1, **knobs),
            donate_argnums=(1,)))
        clock = self.tracer.clock
        t0 = clock()
        with self.mesh:
            carry = pf(self.params, input_ids, rng)
            jax.block_until_ready(carry)
            t1 = clock()
            out = dc(self.params, carry)
            jax.block_until_ready(out)
        t2 = clock()
        self.tracer.observe(batch=B, prompt_len=S, new_tokens=max_new,
                            prefill_s=t1 - t0, decode_s=t2 - t1, cold=cold)
        return out

    def _chunked_generate(self, input_ids, rng, key, max_new: int,
                          knobs: dict, cache_len=None):
        """Decode in ``decode_chunk``-step chunks with a host-side
        ``done.all()`` check between chunks: a batch where every row hit
        eos stops paying for the dead tail of max_new_tokens. Costs one
        host sync per chunk; tokens are bit-identical to the fused path
        (post-eos rows emit eos there too, and the early-stopped tail is
        eos-filled here)."""
        import numpy as np

        chunk = int(self.config.decode_chunk)
        eos = self.config.eos_token_id
        B, S = input_ids.shape
        cold = key not in self._prefill_cache
        clock = self.tracer.clock if self.tracer is not None else None
        pf = self._cached(self._prefill_cache, key, lambda: jax.jit(
            partial(self._prefill_impl, max_new=max_new,
                    cache_len=cache_len, **knobs)))
        t0 = clock() if clock else 0.0
        parts = []
        with self.mesh:
            carry = pf(self.params, input_ids, rng)
            if clock:
                jax.block_until_ready(carry)
            t1 = clock() if clock else 0.0
            remaining = max_new - 1
            if remaining == 0:   # prefill's token is the whole output
                parts.append(np.asarray(carry.tok)[:, None])
            first = True
            while remaining > 0:
                steps = min(chunk, remaining)
                # a decode chunk program compiling MID-request (e.g. the
                # ragged final chunk of a budget an earlier early-stopped
                # request never reached) is a cold sample too — its compile
                # seconds must stay out of the latency reservoirs
                cold = cold or (key, steps) not in self._decode_cache
                # same donation contract as the traced path: the carry's
                # KV cache is dead after the call — reuse it in place
                dc = self._cached(
                    self._decode_cache, (key, steps), lambda: jax.jit(
                        partial(self._decode_impl, steps=steps,
                                return_carry=True, **knobs),
                        donate_argnums=(1,)))
                seg, carry = dc(self.params, carry)
                # chunk returns [carry_tok, d1..d_steps]; the carry token
                # is the previous chunk's last emitted column
                parts.append(np.asarray(seg if first else seg[:, 1:]))
                first = False
                remaining -= steps
                if remaining > 0 and eos is not None \
                        and bool(np.asarray(carry.done).all()):
                    parts.append(np.full((B, remaining), eos, np.int32))
                    break
        out = jnp.asarray(np.concatenate(parts, axis=1))
        if self.tracer is not None:
            t2 = clock()
            self.tracer.observe(batch=B, prompt_len=S, new_tokens=max_new,
                                prefill_s=t1 - t0, decode_s=t2 - t1,
                                cold=cold)
        return out

    def metrics_snapshot(self) -> dict:
        """Serving metrics: request count, TTFT / per-token-latency
        percentiles, tokens/s, achieved weight-GB/s and decode MBU, plus
        the most recent request records. ``{"tracing": False}`` when the
        engine was built without ``observability`` (the zero-sync path
        records nothing)."""
        if self.tracer is None:
            return {"tracing": False, "requests": 0}
        return {"tracing": True, **self.tracer.snapshot()}

    def publish_metrics(self, monitor, step: Optional[int] = None) -> int:
        """Push the ``Serve/*`` registry through a monitor fan-out — a
        :class:`~deepspeed_tpu.monitor.monitor.MonitorMaster` or anything
        with ``write_events([(name, value, step)])``.

        Unlike the training engine (whose step loop flushes its sinks at
        report boundaries), serving has no universal cadence — the
        serving loop owns it: call this from a timer or every N requests.
        ``step`` defaults to the request count. Returns the number of
        events written (0 when tracing is off)."""
        if self.tracer is None:
            return 0
        from ..observability.metrics import publish_registry

        return publish_registry(self.tracer.registry, monitor, step,
                                default_step_counter="Serve/requests")


def init_inference(model, params=None, config: InferenceConfig | dict | None = None,
                   mesh: Optional[Mesh] = None, **kwargs) -> InferenceEngine:
    """Public entry point (reference ``deepspeed.init_inference``,
    ``deepspeed/__init__.py:269``)."""
    if params is None:
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return InferenceEngine(model, params, config, mesh=mesh, **kwargs)
