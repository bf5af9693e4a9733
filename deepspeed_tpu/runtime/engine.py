"""Training engine.

TPU-native analog of ``DeepSpeedEngine`` (``runtime/engine.py:175``, 3.5 kLoC).
The reference wraps the model and orchestrates forward/backward/step
imperatively with hooks, streams, and bucketed collectives; here the entire
micro-step pipeline — gradient accumulation (``lax.scan`` over micro-batches,
replacing the ``is_gradient_accumulation_boundary`` bookkeeping), mixed
precision casts, loss scaling, ZeRO-sharded gradient reduction, clipping, and
the optimizer update — is one jitted, donated function. XLA's latency-hiding
scheduler provides the comm/compute overlap that the reference hand-codes
with side streams (``overlap_comm``).

API shape follows the reference: ``initialize(config, model, ...)`` returns an
engine with ``train_batch`` / ``eval_batch`` / ``save_checkpoint`` /
``load_checkpoint`` / ``client_lr_scheduler``-style accessors.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..observability import spans as _spans
from ..observability.spans import TRAIN_PHASE, TRAIN_STEP
from ..ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUAL_NAMES
from ..platform.accelerator import get_accelerator
from ..platform.mesh import (BATCH_AXES, MeshSpec, build_mesh, dp_world_size,
                             fit_specs)
from ..utils.logging import log_dist, logger
from ..utils.timer import ThroughputTimer, WallClockTimers, peak_flops_for
from .loss_scaler import (LossScaleState, grads_finite, init_loss_scale,
                          update_loss_scale)
from .lr_schedules import build_schedule
from .onebit import in_warmup
from .optimizers import OptState, Optimizer, build_optimizer
from .sparse_grads import SparseGradRows
from .zero.partitioning import ZeroPartitioner, shardings_from_specs


class TrainState(NamedTuple):
    step: jnp.ndarray              # i32 global step
    master_params: Any             # fp32, ZeRO-sharded per stage
    opt_state: OptState            # same sharding as master
    loss_scale: LossScaleState
    skipped_steps: jnp.ndarray     # i32 (fp16 overflow skips)
    # 1-bit compression error-feedback residuals (worker/server), per data
    # rank (reference runtime/fp16/onebit/adam.py worker_error/server_error);
    # () when compression is off.
    comm_err: Any = ()


# Activation names the trunk tags with jax.ad_checkpoint.checkpoint_name
# (models/transformer.py _layer, models/t5.py): the residual stream entering
# each layer and the attention's output in one of two forms, whichever the
# trunk produced — the projected attn_out where the attention function names
# nothing itself (dense, latent, ring / Ulysses, sparse, T5), or the flash
# kernel's own flash_o (B, S, H*hd) and flash_lse (B, H, S), which spare the
# backward the kernel's forward and cost it one wo product. A name that does
# not occur in a trace costs nothing. The offload policy below moves exactly
# these to pinned host memory during the forward — the TPU shape of the
# reference's cpu_checkpointing + contiguous_checkpointing
# (activation_checkpointing/checkpointing.py:1036): HBM holds ~one layer's
# activations while host RAM holds the rest, and XLA's latency-hiding
# scheduler overlaps the D2H/H2D streams with layer compute.
OFFLOAD_ACTIVATION_NAMES = ("layer_in", "attn_out", *FLASH_RESIDUAL_NAMES)


def _remat_policy(cfg: Config):
    if not cfg.remat.enabled:
        return None
    name = cfg.remat.policy
    cp = jax.checkpoint_policies
    table = {
        "none": None,
        "full": cp.nothing_saveable,
        "save_nothing": cp.nothing_saveable,
        "dots_saveable": cp.dots_saveable,
        # Save ONLY the tagged layer-boundary activations (the residual
        # stream entering each layer + the attention's output: the flash
        # kernel's o and lse, so the backward never runs its forward again,
        # or the projected attn_out under any other attention) and
        # recompute everything else in the backward. Under flash attention
        # this is ~4x less saved HBM per layer than dots_saveable (which
        # keeps every projection/MLP dot output) — the policy that lets a
        # 1B-param decoder train on one 16 GiB chip without host offload.
        "save_names": cp.save_only_these_names(*OFFLOAD_ACTIVATION_NAMES),
        # save_names + the pre-activation MLP intermediate (~3x the saved
        # bytes of save_names, still ~40% of dots_saveable): trades ~1 GiB
        # of HBM at 1B/mbs4 for skipping the w_in matmul recompute in the
        # backward — the largest single dot in the layer.
        "save_names_mlp": cp.save_only_these_names(
            *OFFLOAD_ACTIVATION_NAMES, "mlp_h"),
    }
    if name == "offload_dots":
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(OFFLOAD_ACTIVATION_NAMES),
            offload_src="device", offload_dst="pinned_host")
    return table.get(name, cp.dots_saveable)


class Engine:
    """Owns mesh, sharded state, and the compiled train/eval steps."""

    @_spans.timed_init("train")
    def __init__(self, config: Config | dict | str | None, model,
                 mesh: Optional[Mesh] = None, seed: Optional[int] = None,
                 params=None, abstract_state: bool = False):
        self.config = Config.from_any(config)
        self.model = model
        # AOT-probe mode (params-per-chip ceiling search): state is a tree
        # of sharding-annotated ShapeDtypeStructs — NOTHING is materialized
        # in device or host memory, so configs far past the OOM line can
        # still be compile-probed via compile_train_step. Only
        # compile_train_step is usable on such an engine.
        self._abstract = bool(abstract_state)
        # pretrained initial weights (HF import, numpy/jax trees): become
        # the fp32 master instead of model.init(rng) — the zero.Init-style
        # born-sharded construction still applies (passed as a jit argument,
        # resharded by out_shardings, never baked in as constants)
        self._initial_params = params
        de = self.config.data_efficiency
        self.curriculum = None
        if de.curriculum_learning.enabled:
            from ..data_pipeline.curriculum import CurriculumScheduler

            self.curriculum = CurriculumScheduler.from_config(
                de.curriculum_learning)
        self._ltd = de.random_ltd if de.random_ltd.enabled else None
        self._ltd_tokens = -1
        self._warned_device_batch = False
        self._flops_nominal_checked = False
        self._comp = self.config.compression.enabled_techniques()
        self._moq = None
        if self._comp:
            from ..compression import convert_to_compressed

            self.model = model = convert_to_compressed(
                model, self.config.compression)
            wq = self.config.compression.weight_quantization
            if wq.enabled and wq.start_bits and wq.start_bits > wq.bits:
                from ..compression.moq import MoQScheduler

                self._moq = MoQScheduler(wq)
                self._moq_probe_batch = None
        if self.config.lora.enabled:
            from .lora import convert_to_lora

            self.model = model = convert_to_lora(
                model, rank=self.config.lora.rank,
                alpha=self.config.lora.alpha)
        self._pld = self.config.progressive_layer_drop.enabled
        if self._pld:
            from .progressive_layer_drop import convert_to_progressive_layer_drop

            pld = self.config.progressive_layer_drop
            self.model = model = convert_to_progressive_layer_drop(
                model, theta=pld.theta, gamma=pld.gamma)
        # Frozen-param mask (LoRA base weights): a static bool pytree; the
        # update step restores frozen leaves AFTER the optimizer math, so
        # neither gradients nor weight decay can drift them.
        self._frozen_mask = (model.frozen_param_mask()
                             if hasattr(model, "frozen_param_mask") else None)
        if self.config.checkpoint.use_node_local_storage:
            raise ValueError(
                "checkpoint.use_node_local_storage is not supported: the "
                "orbax store is one logical checkpoint written collectively "
                "(per-host shard files are an artifact of the reference's "
                "torch.save layout); point save_dir at local storage instead")
        if self.config.prescale_gradients:
            raise ValueError(
                "prescale_gradients has no effect under XLA: the gradient "
                "reduction order is compiler-managed (no pre-allreduce "
                "division point exists), and fp16 overflow is handled by "
                "dynamic loss scaling — remove the flag")
        from ..models import why_not_trained

        why = why_not_trained(getattr(model, "cfg", None))
        if why:
            raise ValueError(why)
        mcfg = self.config.moe
        if mcfg.enabled:
            # ds_config moe section overrides the model's MoE knobs
            # (reference wires these through the engine into MOELayer)
            if getattr(model.cfg, "num_experts", 1) != mcfg.num_experts:
                raise ValueError(
                    f"config.moe.num_experts={mcfg.num_experts} but the model "
                    f"was built with {getattr(model.cfg, 'num_experts', 1)}")
            model.cfg = dataclasses.replace(
                model.cfg, moe_top_k=mcfg.top_k,
                moe_capacity_factor=mcfg.capacity_factor,
                moe_eval_capacity_factor=mcfg.eval_capacity_factor,
                moe_min_capacity=mcfg.min_capacity,
                moe_drop_tokens=mcfg.drop_tokens,
                moe_aux_loss_weight=mcfg.aux_loss_weight)
        if self.config.comms_logger.enabled:
            from ..comm.comm import comms_logger as _cl

            _cl.enabled = True
            _cl.verbose = self.config.comms_logger.verbose
        # the one-shot HLO collective census runs for the comms logger's
        # summary AND for the commscope observatory's static-bytes side
        # of the achieved-bandwidth ledger (observability/commscope.py)
        _cs_cfg = self.config.observability.commscope
        self._comms_logged = not (self.config.comms_logger.enabled
                                  or bool(_cs_cfg
                                          and _cs_cfg.get("enabled")))
        if self._ltd is not None:
            from ..data_pipeline.random_ltd import convert_to_random_ltd

            self.model = model = convert_to_random_ltd(model,
                                                       seed=self._ltd.seed)
        self.acc = get_accelerator()
        m = self.config.mesh
        self.mesh = mesh or build_mesh(self._mesh_spec(m))
        if self._ltd is not None and int(self.mesh.shape.get("pipe", 1)) > 1:
            raise ValueError(
                "random_ltd is not supported with pipeline parallelism: the "
                "pipe shard_map scans stage-local layer slices, so the "
                "first/last-layer-full rule would apply per stage, not "
                "globally; disable one of the two")
        # PLD composes with pipeline parallelism: PLDMixin._scan_layers
        # recovers the global layer index from lax.axis_index("pipe") so the
        # depth-scaled keep probability follows the paper's global-depth
        # rule even on stage-local slices (see progressive_layer_drop.py).
        self.dp_world = dp_world_size(self.mesh)
        el = self.config.elasticity
        if el.enabled:
            from ..elasticity import ElasticityError, elastic_batch_for

            explicit = [f for f in ("train_batch_size",
                                    "train_micro_batch_size_per_gpu",
                                    "gradient_accumulation_steps")
                        if isinstance(getattr(self.config, f), int)]
            if explicit and not el.ignore_non_elastic_batch_info:
                raise ElasticityError(
                    f"elasticity.enabled with explicit {explicit}: the "
                    "elastic schema owns the batch arithmetic (set "
                    "ignore_non_elastic_batch_info to drop the explicit "
                    "values, reference behavior)")
            batch, micro, gas = elastic_batch_for(el, self.dp_world)
            self.config = self.config.model_copy(update={
                "train_batch_size": batch,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": gas,
            })
            log_dist(f"elasticity: world={self.dp_world} → global={batch} "
                     f"micro={micro} gas={gas}", ranks=[0])
        self.config = self.config.resolve_batch_sizes(self.dp_world)
        self.seed = self.config.seed if seed is None else seed

        zcfg = self.config.zero_optimization
        self.offload = False
        self.partitioner = ZeroPartitioner(zcfg, self.mesh)
        gc = self.config.gradient_compression
        self.grad_comp: Optional[str] = (
            gc.type if gc.enabled
            else ("int8" if zcfg.zero_quantized_gradients else None))
        # bucketed backward-overlap dispatch (comm/compressed.py): bucket
        # size in fp32 elements, defaulting to the reference's
        # reduce_bucket_size knob. 0 buckets = the fused flat spelling.
        self.grad_overlap: bool = bool(self.grad_comp and gc.overlap)
        self._grad_bucket_elems: int = (
            (int(gc.bucket_elems) or int(zcfg.reduce_bucket_size))
            if self.grad_overlap else 0)
        if self._grad_bucket_elems and self.grad_comp != "fp":
            # every QUANTIZED bucket pads to whole per-rank scale blocks,
            # so a bucket smaller than world * BLOCK moves MORE bytes
            # than it carries — clamp to the padding quantum (the wire
            # summary still reports the padding that remains). fp buckets
            # reduce with a plain unpadded pmean: no padding to clamp for.
            from ..comm.compressed import BLOCK

            floor = int(self.mesh.shape["data"]) * BLOCK
            if self._grad_bucket_elems < floor:
                log_dist(
                    f"gradient_compression: bucket_elems="
                    f"{self._grad_bucket_elems} is below the padding "
                    f"quantum data_world*{BLOCK}={floor} (each bucket "
                    "pads to whole per-rank scale blocks) — clamped to "
                    f"{floor}", ranks=[0])
                self._grad_bucket_elems = floor
        if self.grad_comp and zcfg.stage >= 3 \
                and not (self.partitioner.hpz or self.partitioner.mics):
            raise ValueError(
                "gradient compression (qgZ / 1-bit) under ZeRO-3 requires "
                "zero_hpz_partition_size > 1 or mics_shard_size > 0: compute "
                "params must not be sharded over the compressed 'data' axis")
        from .onebit import ONEBIT_TYPES, OnebitConfig

        opt_type = self.config.optimizer.type.lower().replace("-", "_")
        opt_type = {"onebitadam": "onebit_adam", "onebitlamb": "onebit_lamb",
                    "zerooneadam": "zero_one_adam"}.get(opt_type, opt_type)
        self.onebit: Optional[OnebitConfig] = None
        if opt_type in ONEBIT_TYPES:
            self.onebit = OnebitConfig.from_params(opt_type,
                                                   self.config.optimizer.params)
            if zcfg.stage != 0:
                raise ValueError(
                    f"{opt_type} requires ZeRO stage 0 (replicated masters): "
                    "the compressed momentum collective assumes every rank "
                    "holds the full momentum (reference 1-bit optimizers "
                    "have the same restriction)")
            if self.grad_comp:
                raise ValueError(
                    f"{opt_type} already compresses its own communication; "
                    "disable gradient_compression")
            if self.config.fp16.enabled:
                raise ValueError(
                    f"{opt_type} does not support fp16 dynamic loss scaling "
                    "(no overflow-skip on the compressed-momentum path; one "
                    "bad step would poison the error-feedback residuals) — "
                    "use bf16, the TPU default")
            if self.config.gradient_clipping:
                raise ValueError(
                    f"{opt_type} does not support gradient_clipping: in the "
                    "compressed phase the global gradient is never "
                    "materialized, so a global-norm clip cannot be computed "
                    "(same restriction as the reference 1-bit optimizers)")
            # moments init/shape come from the plain Adam state tree
            base = {k: v for k, v in self.config.optimizer.params.items()
                    if k in ("lr", "betas", "eps", "weight_decay")}
            self.optimizer = build_optimizer("adamw", base)
        else:
            self.optimizer = build_optimizer(opt_type,
                                             self.config.optimizer.params)
        base_lr = float(self.config.optimizer.params.get("lr", 1e-3))
        sched_cfg = self.config.scheduler
        self.lr_schedule = build_schedule(sched_cfg.type if sched_cfg else None,
                                          sched_cfg.params if sched_cfg else {}, base_lr)
        self.remat_policy = _remat_policy(self.config)
        self.compute_dtype = self.config.compute_dtype

        # ---------------- sharding trees
        rng = jax.random.PRNGKey(self.seed)
        abstract = jax.eval_shape(self.model.init, rng)
        shapes = jax.tree.map(lambda a: a.shape, abstract)
        self._shapes = shapes
        # a TP-sharded dim the mesh does not divide (an odd vocab) replicates
        model_specs = fit_specs(self.model.param_specs(), shapes, self.mesh)
        stacked = self.model.stacked_fn() if hasattr(self.model, "stacked_fn") else (lambda s: False)
        self.compute_specs = self.partitioner.compute_specs(model_specs, shapes, stacked)
        self.master_specs = self.partitioner.master_specs(model_specs, shapes, stacked)
        self.compute_shardings = shardings_from_specs(self.mesh, self.compute_specs)
        self.master_shardings = shardings_from_specs(self.mesh, self.master_specs)
        # static layer-aligned bucket plan for the compressed/overlapped
        # grad reduction (one bucket when overlap is off — the fused flat
        # spelling, numerically unchanged)
        self._stacked_fn = stacked
        self._grad_plan = None
        if self.grad_comp:
            from ..comm.compressed import plan_buckets

            leaf_shapes = [tuple(s) for s in jax.tree.leaves(
                shapes, is_leaf=lambda x: isinstance(x, tuple))]
            self._grad_plan = plan_buckets(
                leaf_shapes, [stacked(s) for s in leaf_shapes],
                self._grad_bucket_elems)
            if self.grad_overlap and len(self._grad_plan.buckets) == 1:
                log_dist(
                    "gradient_compression.overlap: the whole grad tree "
                    f"fits one bucket ({self._grad_plan.total_elems} <= "
                    f"bucket_elems={self._grad_bucket_elems}) — the "
                    "reduction compiles to the fused flat spelling with "
                    "nothing to overlap; lower gradient_compression."
                    "bucket_elems (or zero_optimization.reduce_bucket_"
                    "size) below the param count to get bucketed "
                    "dispatch", ranks=[0])

        self.param_count = sum(int(np.prod(a.shape))
                               for a in jax.tree.leaves(abstract))
        log_dist(f"engine: {self.param_count / 1e6:.1f}M params | zero stage "
                 f"{zcfg.stage} | mesh {dict(self.mesh.shape)} | "
                 f"micro={self.config.train_micro_batch_size_per_gpu} "
                 f"gas={self.config.gradient_accumulation_steps} "
                 f"global={self.config.train_batch_size}", ranks=[0])

        # ---------------- ZeRO-Offload / Infinity: host-resident optimizer
        zoff = zcfg.offload_optimizer
        self.offload = zoff.device in ("cpu", "nvme")
        if self.offload and self._frozen_mask is not None:
            raise ValueError(
                "lora + offload_optimizer: the host optimizer has no "
                "frozen-leaf masking yet — train adapters with the device "
                "optimizer (LoRA state is small; offload buys nothing)")
        self.param_offload = False
        if zcfg.offload_param.enabled and not self.offload:
            raise ValueError(
                "offload_param requires offload_optimizer device cpu/nvme: "
                "ZeRO-Infinity param streaming operates against the "
                "host-resident optimizer (set zero_optimization."
                "offload_optimizer.device)")
        if self.offload and self._ltd is not None:
            raise ValueError(
                "random_ltd is not supported with offload_optimizer (the "
                "host-optimizer grad step is not rebuilt on schedule "
                "changes); disable one of the two")
        if self.offload and self._pld:
            raise ValueError(
                "progressive_layer_drop is not supported with "
                "offload_optimizer (the host-optimizer grad step never sets "
                "the schedule step); disable one of the two")
        if self.offload and self._comp:
            raise ValueError(
                "compression is not supported with offload_optimizer (the "
                "host-optimizer grad step does not carry the static "
                "active-technique argument); disable one of the two")
        if self.grad_comp and self.offload:
            raise ValueError(
                "gradient_compression / zero_quantized_gradients is not "
                "supported with offload_optimizer (the host-optimizer path "
                "syncs gradients outside the compressed collective); disable "
                "one of the two")
        if self.offload and self.onebit is not None:
            raise ValueError("1-bit optimizers are device-side algorithms; "
                             "offload_optimizer is not supported with them")
        if self.offload:
            self._init_offload(rng, zoff)
            self._post_init()
            return

        # ---------------- init state (sharded at construction: the zero.Init
        # analog — params are born partitioned, never materialized replicated)
        self._comm_err_shapes = {}
        if self.onebit is not None:
            from .onebit import comm_err_shapes

            self._comm_err_shapes = comm_err_shapes(
                self.param_count, int(self.mesh.shape["data"]))
        elif self.grad_comp in ("onebit", "int8"):
            # error-feedback residuals for BOTH compressed grad modes
            # (int8 historically dropped its quantization error every
            # step — the residual pair makes it unbiased like 1-bit),
            # sized from the bucket plan so each bucket's padded window
            # is a static slice of one flat vector per role
            from ..comm.compressed import plan_comm_err_shapes

            self._comm_err_shapes = plan_comm_err_shapes(
                self._grad_plan, int(self.mesh.shape["data"]))
        comm_err_shardings = {k: NamedSharding(self.mesh, P("data"))
                              for k in self._comm_err_shapes}
        # Moment shardings follow the master EXCEPT for moments the
        # optimizer doesn't keep (Lion's nu, momentum-SGD's...), which are
        # (0,)-shaped placeholders: a rank-2 ZeRO spec on those fails the
        # init jit's out_shardings before the old post-init fixup could
        # ever run (found by the 1B Lion bench candidate).
        abstract_opt = jax.eval_shape(self.optimizer.init,
                                      jax.tree.map(
                                          lambda shp: jax.ShapeDtypeStruct(
                                              shp, jnp.float32),
                                          self._shapes,
                                          is_leaf=lambda x: isinstance(x, tuple)))

        def _moment_shardings(mtree):
            return jax.tree.map(
                lambda s, x: (NamedSharding(self.mesh, P())
                              if x.shape == (0,) else s),
                self.master_shardings, mtree)

        self.state_shardings = TrainState(
            step=NamedSharding(self.mesh, P()),
            master_params=self.master_shardings,
            opt_state=OptState(mu=_moment_shardings(abstract_opt.mu),
                               nu=_moment_shardings(abstract_opt.nu),
                               count=NamedSharding(self.mesh, P())),
            loss_scale=LossScaleState(*(NamedSharding(self.mesh, P()),) * 3),
            skipped_steps=NamedSharding(self.mesh, P()),
            comm_err=comm_err_shardings,
        )
        with self.mesh:
            if self._abstract:
                shape_state = jax.eval_shape(self._init_state, rng)
                self.state = jax.tree.map(
                    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                      sharding=s),
                    shape_state, self.state_shardings)
            elif self._initial_params is not None:
                init_fn = jax.jit(self._init_state_from,
                                  out_shardings=self.state_shardings)
                self.state: TrainState = init_fn(self._initial_params)
                self._initial_params = None   # free the host copy
            else:
                init_fn = jax.jit(self._init_state,
                                  out_shardings=self.state_shardings)
                self.state = init_fn(rng)

        self._build_train_step()
        self._eval_step = jax.jit(self._eval_step_impl,
                                  in_shardings=(self.state_shardings.master_params,
                                                self._batch_sharding(gas_dim=False)))
        self._post_init()

    def _build_train_step(self) -> None:
        """Create the jitted train step. The random-LTD kept-token count is a
        STATIC argument — the jit cache keys on (shapes, ltd_tokens), so each
        schedule quantum is one retrace and previously compiled (seqlen, r)
        variants stay cached (curriculum + LTD compose).

        With the offload_dots remat policy, the state shardings move from
        ``out_shardings`` to a constraint on the returned state: explicit
        out_shardings make jax annotate every output's buffer placement,
        and XLA's SPMD partitioner RET_CHECKs on those side-effect
        annotations when host-offloaded rematerialization is also present
        (spmd_partitioner.cc:5743, reproduced on jax 0.9.0). The constraint
        pins the same placement without the output annotations."""
        offload_remat = (self.config.remat.enabled
                         and self.config.remat.policy == "offload_dots")
        if offload_remat:
            def step_constrained(state, batch, ltd, comp, warm):
                new_state, metrics = self._train_step_impl(
                    state, batch, ltd, comp, warm)
                new_state = jax.tree.map(
                    jax.lax.with_sharding_constraint, new_state,
                    self.state_shardings)
                return new_state, metrics

            self._train_step = jax.jit(
                step_constrained,
                donate_argnums=(0,),
                static_argnums=(2, 3, 4),
                in_shardings=(self.state_shardings, self._batch_sharding()),
            )
            return
        self._train_step = jax.jit(
            self._train_step_impl,
            donate_argnums=(0,),
            static_argnums=(2, 3, 4),
            in_shardings=(self.state_shardings, self._batch_sharding()),
            out_shardings=(self.state_shardings, None),
        )

    def _mesh_spec(self, m) -> MeshSpec:
        """Resolve the ``zero`` sub-axis (ZeRO++ hpZ / MiCS subgroup) from the
        zero config. An explicit ``mesh.data`` is the TOTAL data-parallel
        degree; the subgroup is carved out of it (data = total / zero)."""
        zc = self.config.zero_optimization
        hpz = int(zc.zero_hpz_partition_size)
        mics = int(zc.mics_shard_size or 0)
        if hpz > 1 and mics > 0 and hpz != mics:
            raise ValueError(
                f"zero_hpz_partition_size ({hpz}) and mics_shard_size ({mics}) "
                "both set but disagree; they share the mesh 'zero' sub-axis")
        mzero = int(getattr(m, "zero", 1) or 1)
        if mzero < 1:
            raise ValueError(
                "mesh.zero cannot be auto (-1): the hpZ/MiCS subgroup size "
                "must be explicit (zero_hpz_partition_size / mics_shard_size)")
        want = hpz if hpz > 1 else (mics if mics > 0 else 1)
        if mzero > 1 and want > 1 and mzero != want:
            raise ValueError(
                f"mesh.zero ({mzero}) conflicts with the configured "
                f"hpZ/MiCS subgroup size ({want})")
        zsize = max(mzero, want)
        if zc.zero_quantized_weights and hpz <= 1:
            raise ValueError(
                "zero_quantized_weights needs a cross-subgroup weight gather "
                "to quantize: set zero_optimization.zero_hpz_partition_size "
                "> 1 (under MiCS or a bare mesh.zero the master shard never "
                "spans 'data', so there is no gather to compress)")
        data = m.data
        if data != -1 and zsize > 1:
            if data % zsize != 0:
                raise ValueError(
                    f"data-parallel degree {data} not divisible by "
                    f"hpZ/MiCS subgroup size {zsize}")
            data //= zsize
        return MeshSpec(data=data, model=m.model, pipe=m.pipe, seq=m.seq,
                        expert=m.expert, zero=zsize)

    def _post_init(self):
        from ..observability.metrics import MetricsRegistry

        self.timers = WallClockTimers()
        # One registry per engine: Train/* from the step loop, Memory/*
        # from the HBM watermark, Comm/* from the collective census —
        # the training half of the unified metric namespace
        # (docs/OBSERVABILITY.md). Recording is host-side floats only.
        self.metrics = MetricsRegistry()
        obs = self.config.observability
        self._trace_window = None
        if obs.trace_steps:
            from ..observability.xla import TraceWindow

            self._trace_window = TraceWindow(
                obs.trace_steps, obs.trace_dir,
                sync_fn=lambda: jax.block_until_ready(
                    self.compute_params if self.offload else self.state))
        # span ring + flight recorder + step-time anomaly detector (the
        # training half of the serving engine's observability trio); all
        # default-off, each None costing one `is not None` on the hot path
        self.spans = None
        if obs.spans:
            from ..observability.spans import SpanRecorder

            self.spans = SpanRecorder(obs.spans_ring)
        self.flight = None
        if obs.flight_dir:
            from ..observability.flight import FlightRecorder

            self.flight = FlightRecorder(
                obs.flight_dir, spans=self.spans,
                snapshots={"train": self.metrics_snapshot},
                max_dumps=obs.flight_max_dumps, job_name="train",
                registry=self.metrics)
        self._step_anomaly = None
        if obs.slo:
            from ..observability.slo import MedianMADDetector, SLOConfig

            slo = SLOConfig.from_any(obs.slo)
            if slo.step_time_mad_k:
                self._step_anomaly = MedianMADDetector(
                    slo.step_time_mad_k, slo.step_time_window,
                    slo.step_time_min_samples)
            # an enabled knob the training engine has no machinery for
            # must not be silently ignored (same stance as
            # MonitorConfig.any_enabled): ttft/tpot/error-rate and the
            # compile-storm detector are serving-side — the operator who
            # set them believes detection is on
            unwired = [k for k in ("ttft_p99_s", "tpot_p99_s",
                                   "error_rate",
                                   "compile_storm_threshold")
                       if getattr(slo, k)]
            if unwired:
                log_dist(
                    f"observability.slo: {unwired} are serving-side "
                    "knobs — the training engine only wires "
                    "step_time_mad_k; set them under the serving "
                    "config's `slo` block instead", level="WARNING")
        # communication observatory (observability/commscope.py):
        # per-step exposed-collective anatomy + achieved-bandwidth
        # ledger over the TraceWindow capture, plus straggler detection
        # on per-step stamps. None (default) = one `is not None` per
        # step, zero new programs/syncs.
        self.commscope = None
        self._hlo_by_kind = None
        if obs.commscope and obs.commscope.get("enabled"):
            from ..observability.commscope import (CommScope,
                                                   CommScopeConfig)

            self.commscope = CommScope(
                CommScopeConfig.from_any(obs.commscope),
                registry=self.metrics, spans=self.spans,
                flight=self.flight, n_devices=len(jax.devices()))
            if self.flight is not None:
                self.flight.add_snapshot_provider(
                    "commscope", self.commscope.snapshot)
        # goodput/badput wall-time ledger (observability/goodput.py):
        # Train/goodput_* decomposition of step dispatch vs compile /
        # inter-step idle / checkpoint / preemption. None (default) =
        # zero clock reads added to train_batch.
        self.goodput = None
        self._gp_stepped = False
        if obs.goodput:
            from ..observability.goodput import GoodputLedger

            self.goodput = GoodputLedger(registry=self.metrics,
                                         prefix="Train")
        # live telemetry server (observability/server.py): /metrics,
        # /healthz, /goodput, /flight + POST /flight/dump for the
        # training process. Off (default) = zero threads. Started at the
        # END of _post_init — a probe racing construction must find
        # global_steps / the resilience fields already in place.
        self.telemetry = None
        mb, gas = self.config.train_micro_batch_size_per_gpu, self.config.gradient_accumulation_steps
        try:
            peak = peak_flops_for(self.acc.current_device()) * len(jax.devices())
        except ValueError as e:
            # Unknown hardware must not abort training — only the MFU stat.
            log_dist(f"MFU reporting disabled: {e}", level="WARNING")
            peak = 0.0
        self.throughput = ThroughputTimer(
            batch_size=int(self.config.train_batch_size),
            steps_per_output=self.config.steps_per_print,
            flops_per_sample=self._flops_per_sample(),
            peak_flops=peak,
        )
        self.global_steps = 0
        self.monitor = None
        if self.config.monitor.any_enabled():
            from ..monitor.monitor import MonitorMaster

            self.monitor = MonitorMaster(self.config.monitor)
        self.flops_profiler = None
        if self.config.flops_profiler.enabled:
            from ..profiling import FlopsProfiler

            self.flops_profiler = FlopsProfiler(self.config.flops_profiler, self)
        # ---- resilience (docs/RESILIENCE.md) ----
        res = self.config.resilience
        # non-finite/skip sentinel state (counted exactly per step on the
        # offload path, per report window on the in-device path)
        self._max_bad_steps = int(res.max_consecutive_bad_steps or 0)
        self._bad_step_streak = 0
        self._skipped_total_prev = 0.0
        # chaos: simulated SIGTERM preemption at a fixed step (env-gated;
        # None in production — the per-step cost is one `is not None`)
        from ..resilience import chaos as _chaos

        self._chaos_preempt = _chaos.preempt_step()
        # elastic-restart visibility: the agent exports the incarnation
        # index and the previous incarnation's exit code; recording them
        # here puts Train/restarts in every sink (incl. the Prometheus
        # textfile) from the first report boundary of the new incarnation
        try:
            restarts = int(os.environ.get("DSTPU_ELASTIC_RESTART", "0") or 0)
        except ValueError:
            restarts = 0
        if restarts > 0:
            self.metrics.counter("Train/restarts").inc(restarts)
            try:
                last_rc = os.environ.get("DSTPU_ELASTIC_LAST_RC")
                if last_rc is not None:
                    self.metrics.gauge("Train/last_exit_code").set(
                        float(int(last_rc)))
            except ValueError:
                pass
        # auto-resume LAST: the engine is fully built, so this is exactly
        # a user-issued load_checkpoint (verified-tag fallback included)
        if res.resume == "auto" and not self._abstract:
            from .checkpoint.engine import auto_resume

            auto_resume(self, res.resume_dir)
        # config-gated telemetry server, after every field a probe can
        # read exists (global_steps, the sentinel state, the registry)
        tele = obs.telemetry
        if tele and tele.get("enabled"):
            from ..observability.server import TelemetryConfig

            tc = TelemetryConfig.from_any(tele)
            self.serve_telemetry(port=tc.port, host=tc.host,
                                 token=tc.token)

    def _host_grad_outputs(self) -> bool:
        """Do grad outputs land directly in pinned host memory? On a TPU
        whose ``memory_kinds()`` lists ``pinned_host``, yes.
        DSTPU_HOST_GRAD_OUTS=0/1 force-overrides."""
        force = os.environ.get("DSTPU_HOST_GRAD_OUTS")
        if force is not None:
            return force != "0"
        return (self.acc.current_device().platform == "tpu"
                and self.acc.supports_host_offload())

    def _init_offload(self, rng, zoff):
        """ZeRO-Offload/Infinity mode: fp32 master + moments in host DRAM
        (NVMe tier for moments), C++ host optimizer, device holds only the
        compute copy. Reference: stage_1_and_2.py:1096 + swap_tensor/."""
        from .offload import HostOffloadOptimizer

        # fp16 under offload (reference CPU Adam runs under fp16 with
        # dynamic loss scaling, stage_1_and_2.py:1096): the scale state
        # lives host-side — the grad step returns a grads_finite flag, an
        # overflow skips the host optimizer step, and the scale backs
        # off/grows with the shared update_loss_scale rules.
        self._offload_ls = init_loss_scale(self.config.fp16)

        # ZeRO-Infinity param offload: the bf16 compute copy lives in pinned
        # host memory; the model streams each layer's slice into HBM inside
        # the scan (reference partitioned_param_swapper.py:36 +
        # parameter_offload.py:342). HBM never holds the full model.
        zoff_param = self.config.zero_optimization.offload_param
        self.param_offload = zoff_param.enabled
        if self.param_offload:
            # Gate on the backend exposing pinned_host (memory_kinds()), not
            # on the platform name. On CPU the streaming path stays
            # live-but-inert (CI coverage).
            tpu_plat = self.acc.current_device().platform == "tpu"
            has_pinned = self.acc.supports_host_offload()
            on_tpu = tpu_plat and has_pinned
            self.model.params_on_host = (not tpu_plat) or has_pinned
            if on_tpu:
                stacked = (self.model.stacked_fn()
                           if hasattr(self.model, "stacked_fn")
                           else (lambda s: False))
                thresh = int(self.config.zero_optimization
                             .param_persistence_threshold or 0)
                self.compute_shardings = jax.tree.map(
                    lambda sh, shp: (NamedSharding(
                        self.mesh, sh.spec, memory_kind="pinned_host")
                        if stacked(shp) and int(np.prod(shp)) >= thresh
                        else sh),
                    self.compute_shardings, self._shapes)
            elif tpu_plat:
                log_dist("offload_param: this TPU backend exposes no "
                         "pinned_host memory kind — param streaming is "
                         "inert (params stay in HBM)", ranks=[0])
            else:
                log_dist("offload_param: non-TPU platform — params stay in "
                         "(host-backed) device memory; streaming is inert",
                         ranks=[0])

        fp32_names = tuple(getattr(self.model, "fp32_param_names", lambda: ())())
        if self._abstract:
            # AOT-probe mode: no host master, no device compute copy — just
            # the sharded shape/dtype skeleton compile_train_step needs
            def _sds(path, shp, sh):
                name = (path[-1].key if hasattr(path[-1], "key")
                        else str(path[-1]))
                dt = jnp.float32 if name in fp32_names else self.compute_dtype
                return jax.ShapeDtypeStruct(shp, dt, sharding=sh)

            self.compute_params = jax.tree_util.tree_map_with_path(
                _sds, self._shapes, self.compute_shardings,
                is_leaf=lambda x: isinstance(x, tuple))
            self.host_opt = None
        else:
            if self._initial_params is not None:
                host_master = jax.tree.map(
                    lambda a: np.asarray(a, np.float32), self._initial_params)
                self._initial_params = None
            else:
                with self.mesh:
                    init_params = jax.jit(self._init_master)(rng)
                host_master = jax.tree.map(np.asarray, init_params)
                del init_params
            self.host_opt = HostOffloadOptimizer(
                host_master, self.optimizer, zoff,
                compute_dtype=self.compute_dtype, fp32_names=fp32_names,
                compute_shardings=self.compute_shardings)
            with self.mesh:
                self.compute_params = self.host_opt.device_compute_params()
        # Grad outputs land directly in pinned host memory (when the backend
        # really supports it): XLA's latency-hiding scheduler overlaps the
        # per-layer D2H with the remaining backward compute — the reference's
        # overlap-CPU-Adam-with-backward streams (stage_1_and_2.py:1096)
        # compiled into the step. Grads KEEP their compute sharding (no
        # replication, no gather inserted); only the memory space changes.
        # Gated on memory_kinds(); DSTPU_HOST_GRAD_OUTS=0/1 force-overrides.
        # sparse_gradients: plan which embedding leaves ship row-sparse
        # over the D2H (reference sparse embedding allreduce,
        # engine.py:2427). Static top-k bound = one touched row per batch
        # token; only worth it when that bound is under half the vocab.
        self._sparse_plan = {}
        if self.config.sparse_gradients:
            names = tuple(getattr(self.model, "sparse_grad_names",
                                  lambda: ())())
            tokens = self.train_batch_size * int(
                getattr(getattr(self.model, "cfg", None), "max_seq", 0) or 0)
            for path, shape in jax.tree_util.tree_flatten_with_path(
                    self._shapes,
                    is_leaf=lambda x: isinstance(x, tuple))[0]:
                name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if name in names and len(shape) == 2 and tokens \
                        and tokens < shape[0] // 2:
                    self._sparse_plan[name] = min(int(tokens), int(shape[0]))
            if self._sparse_plan:
                log_dist(f"sparse_gradients: row-sparse D2H for "
                         f"{sorted(self._sparse_plan)} (k={self._sparse_plan})",
                         ranks=[0])
        grad_outs = None
        if self._host_grad_outputs():
            pin = lambda s: NamedSharding(self.mesh, s.spec,
                                          memory_kind="pinned_host")

            def _out_sharding(path, s):
                name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
                if name in self._sparse_plan:
                    rep = NamedSharding(self.mesh, P(),
                                        memory_kind="pinned_host")
                    return SparseGradRows(indices=rep, values=rep)
                return pin(s)

            grad_outs = jax.tree_util.tree_map_with_path(
                _out_sharding, self.compute_shardings)
        self._grad_step = jax.jit(
            self._grad_step_impl,
            in_shardings=(self.compute_shardings, self._batch_sharding(),
                          NamedSharding(self.mesh, P())),
            **({"out_shardings": (grad_outs, None)} if grad_outs else {}))
        self._eval_offload = jax.jit(
            lambda cp, b: self.model.loss(cp, b),
            in_shardings=(self.compute_shardings,
                          self._batch_sharding(gas_dim=False)))
        log_dist(f"offload: optimizer states on "
                 f"{'NVMe' if zoff.device == 'nvme' else 'host DRAM'} "
                 f"({self.param_count / 1e6:.1f}M params)", ranks=[0])

    def _init_master(self, rng):
        return jax.tree.map(lambda a: a.astype(jnp.float32),
                            self.model.init(rng))

    def fp32_params(self):
        """Full (host) fp32 master tree — the zero_to_fp32 /
        consolidated-state-dict analog, e.g. for export_hf_checkpoint."""
        if self.offload:
            return self.host_opt.master_tree()
        return jax.tree.map(lambda a: np.asarray(a, np.float32),
                            self.state.master_params)

    def _grad_step_impl(self, compute_params, batch, scale):
        """Forward+backward only — the update happens on the host. Gradient
        clipping runs on-device (one fused epilogue) so the host never
        reallocates clipped copies; grads leave the step already final
        (unscaled — fp16's loss scale is divided back out before clipping,
        with a grads_finite flag so the caller can skip the host step)."""
        grads, loss = self._gas_scan(compute_params, batch, scale)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        finite = (grads_finite(grads) if self.config.fp16.enabled
                  else jnp.bool_(True))
        grads = jax.tree.map(lambda g: g / scale, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        clip = self.config.gradient_clipping
        if clip and clip > 0:
            coef = jnp.minimum(jnp.float32(1.0), clip / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * coef, grads)
        grads = self._sparsify_grads(grads)
        return grads, {"loss": loss, "grad_norm": gnorm,
                       "grads_finite": finite}

    def _sparsify_grads(self, grads):
        """Replace planned embedding-grad leaves with (indices, values)
        pairs selected ON DEVICE (top-k by row max-abs; the static bound
        guarantees every touched row is included), so the offload D2H
        moves k·(d+1) floats instead of V·d."""
        if not self._sparse_plan:
            return grads

        def fn(path, g):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            k = self._sparse_plan.get(name)
            if k is None or g.ndim != 2:
                return g
            score = jnp.max(jnp.abs(g), axis=1)
            _, idx = jax.lax.top_k(score, k)
            idx = idx.astype(jnp.int32)
            return SparseGradRows(indices=idx,
                                  values=jnp.take(g, idx, axis=0))

        return jax.tree_util.tree_map_with_path(fn, grads)

    def _train_batch_offload(self, batch: dict) -> dict:
        self.throughput.start()
        if self.curriculum is not None:
            batch = self._apply_data_efficiency(batch)
        if not isinstance(next(iter(batch.values())), jax.Array):
            batch = self._make_global(batch)
        # the clock reads below are the result's bwd_s / host_step_s; the
        # spans come from the seam, as in the on-device path
        n_step = self.global_steps + 1
        with self._span(TRAIN_STEP, name="train.step", step=n_step):
            t0 = time.perf_counter()
            scale = self._offload_ls.scale
            with self._span(TRAIN_PHASE, name="train.bwd", step=n_step,
                            phase="bwd"):
                with self.mesh:
                    grads, metrics = self._grad_step(self.compute_params,
                                                     batch, scale)
                # reading the norm back waits for the step; with
                # pinned-host grad outputs the device->host DMAs already
                # ran inside the step, overlapped with the tail of
                # backward by XLA's latency-hiding scheduler.
                gnorm = float(metrics["grad_norm"])
                finite = bool(metrics["grads_finite"])
            t_bwd = time.perf_counter() - t0
            lr = float(self.lr_schedule(jnp.int32(self.global_steps)))
            t1 = time.perf_counter()
            with self._span(TRAIN_PHASE, name="train.host_step",
                            step=n_step, phase="host_step"):
                if finite:
                    with self.mesh:
                        self.compute_params = self.host_opt.step(grads, lr)
                else:
                    log_dist("offload fp16: non-finite grads, skipping "
                             f"host step (loss scale {float(scale):.0f})",
                             ranks=[0])
                self._offload_ls = update_loss_scale(
                    self._offload_ls, metrics["grads_finite"],
                    self.config.fp16)
            t_host = time.perf_counter() - t1
            self.global_steps += 1
        if self.commscope is not None:
            t2 = t1 + t_host
            self.commscope.on_step(
                self.global_steps, t0, t2,
                traced=(self._trace_window is not None
                        and self._trace_window.active))
            self.commscope.observe_stamps(self.global_steps,
                                          {jax.process_index(): t2})
        out = {"loss": float(metrics["loss"]), "grad_norm": gnorm, "lr": lr,
               "loss_scale": float(scale), "skipped": 0 if finite else 1,
               "bwd_s": t_bwd, "host_step_s": t_host}
        # offload reads the finite flag back every step anyway — the
        # sentinel counts exactly, window 1
        self._note_bad_steps((not finite) or not math.isfinite(out["loss"]),
                             1, out["loss"])
        if self.global_steps % self.config.steps_per_print == 0:
            stats = self.throughput.stop(report=True)
            log_dist(f"step={self.global_steps} loss={out['loss']:.4f} "
                     f"lr={lr:.3e} gnorm={gnorm:.3f}", ranks=[0])
            # same registry namespace as the in-device path, plus the
            # offload-specific phase split (backward vs host optimizer)
            self._record_step_metrics(out, stats, extra_gauges={
                "Train/bwd_s": t_bwd, "Train/host_step_s": t_host})
            self._emit_monitor_events()
        else:
            self.throughput.stop(report=False)
        if self.flops_profiler and self.flops_profiler.should_fire():
            self.flops_profiler.profile(batch)
        return out

    # ------------------------------------------------------------------ util
    def _flops_per_sample(self) -> float:
        cfg = getattr(self.model, "cfg", None)
        if cfg is not None and hasattr(cfg, "flops_per_token"):
            # flops_per_token() is ALREADY fwd+bwd (6N + attention term);
            # multiplying by 3 here triple-counted and inflated reported
            # TFLOPS/MFU 3x (round-3 audit)
            return cfg.flops_per_token() * getattr(cfg, "max_seq", 1)
        return 0.0

    def _batch_sharding(self, gas_dim: bool = True):
        # batches are dicts of arrays shaped (gas, global_micro, ...) for train
        # and (global_batch, ...) for eval
        if gas_dim:
            return NamedSharding(self.mesh, P(None, BATCH_AXES))
        return NamedSharding(self.mesh, P(BATCH_AXES))

    def _init_state(self, rng) -> TrainState:
        master = jax.tree.map(lambda a: a.astype(jnp.float32), self.model.init(rng))
        return self._state_around(master)

    def _init_state_from(self, params) -> TrainState:
        master = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        return self._state_around(master)

    def _state_around(self, master) -> TrainState:
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            master_params=master,
            opt_state=self.optimizer.init(master),
            loss_scale=init_loss_scale(self.config.fp16),
            skipped_steps=jnp.zeros((), jnp.int32),
            comm_err={k: jnp.zeros(s, jnp.float32)
                      for k, s in self._comm_err_shapes.items()},
        )

    # ------------------------------------------------------------- train step
    @staticmethod
    def _spec_has(spec, axis: str) -> bool:
        if not isinstance(spec, P):
            return False
        for e in spec:
            names = e if isinstance(e, (tuple, list)) else (e,)
            if axis in names:
                return True
        return False

    def _cast_compute(self, master):
        """bf16/fp16 compute cast; leaves named in the model's
        ``fp32_param_names()`` (e.g. MoE routers) stay fp32.

        With ZeRO++ qwZ (``zero_quantized_weights`` + hpZ), leaves whose
        secondary (compute) shard drops the ``data`` axis are gathered as
        int8 + per-row scales instead of bf16 — the cross-subgroup weight
        all-gather moves 2x fewer bytes (4x vs fp32), the TPU shape of the
        reference's quantized weight gather
        (``runtime/zero/partition_parameters.py:1032``)."""
        keep = set(getattr(self.model, "fp32_param_names", lambda: ())())
        qwz = (self.config.zero_optimization.zero_quantized_weights
               and self.partitioner.hpz)

        def cast(path, p, mspec, cspec):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name in keep:
                return p
            if qwz and self._spec_has(mspec, "data") \
                    and not self._spec_has(cspec, "data"):
                from ..ops.quant import rowwise_dequant, rowwise_quant_int8

                q, s = rowwise_quant_int8(p)
                # Pin the int8 payload (and scales) to the secondary-shard
                # sharding: GSPMD emits the 'data'-axis all-gather on int8.
                q = jax.lax.with_sharding_constraint(q, cspec)
                s = jax.lax.with_sharding_constraint(
                    s, P(*(tuple(cspec)[:p.ndim - 1] if len(tuple(cspec))
                           else ()), None))
                return rowwise_dequant(q, s, self.compute_dtype)
            return p.astype(self.compute_dtype)

        cp = jax.tree_util.tree_map_with_path(cast, master, self.master_specs,
                                              self.compute_specs)
        return jax.lax.with_sharding_constraint(cp, self.compute_specs)

    def _gas_scan(self, compute_params, batch, scale):
        """Gradient-accumulation scan: (params, (gas, B, ...) batch) →
        (summed grads, mean loss). Runs either directly under jit (GSPMD
        inserts the cross-data grad reduction) or inside the manual-data
        shard_map of the compressed path (no data reduction inserted; the
        carry is seeded from the device-varying batch, so it needs no
        explicit pcast-to-varying)."""
        cfg = self.config
        gas = int(cfg.gradient_accumulation_steps)

        def loss_fn(cp, mb):
            loss = self.model.loss(cp, mb, remat_policy=self.remat_policy)
            return loss * scale / gas

        grad_fn = jax.value_and_grad(loss_fn, argnums=0)
        acc_name = cfg.data_types.grad_accum_dtype or "float32"
        acc_dtype = jnp.dtype({"fp32": "float32", "bf16": "bfloat16",
                               "fp16": "float16"}.get(acc_name, acc_name))

        def gas_body(carry, mb):
            g_acc, loss_acc = carry
            scaled_loss, g = grad_fn(compute_params, mb)
            g_acc = jax.tree.map(lambda a, gg: a + gg.astype(acc_dtype), g_acc, g)
            return (g_acc, loss_acc + scaled_loss / scale), None

        # Seed the accumulator from the FIRST micro-batch instead of zeros:
        # XLA materializes a zeros-initialized carry as a live grad-sized
        # buffer alongside each micro's grads (round-5 OOM dump: 1.17 GiB
        # of broadcast(0) for the two MLP grad leaves alone at 1B params),
        # while seeding aliases the first grads straight into the carry.
        # gas == 1 skips the scan machinery entirely.
        first = jax.tree.map(lambda t: t[0], batch)
        scaled_loss0, g0 = grad_fn(compute_params, first)
        grads0 = jax.tree.map(lambda g: g.astype(acc_dtype), g0)
        carry = (grads0, scaled_loss0 / scale)
        if gas == 1:
            return carry
        rest = jax.tree.map(lambda t: t[1:], batch)
        (grads, loss), _ = lax.scan(gas_body, carry, rest)
        return grads, loss

    def _compressed_grads(self, compute_params, batch, scale, comm_err):
        """Per-rank local grads under a manual-``data`` shard_map + explicit
        bucketed reduction (qgZ int8 / 1-bit error feedback / fp). The fast
        sub-axes (zero/expert/seq/model) stay GSPMD-managed inside — only the
        slow data hop moves compressed bytes.

        With ``gradient_compression.overlap`` the reduction runs per
        layer-aligned bucket (``comm/compressed.py plan_buckets``): each
        bucket's collective depends only on its own layers' grads, so
        XLA's latency-hiding scheduler dispatches bucket i's quantized
        wire time against the remaining backward / the neighbouring
        buckets' quantize compute instead of serializing ONE flat
        collective after the whole backward. Both compressed modes carry
        error-feedback residuals in the ``comm_err`` state (unscaled —
        true gradient units, loss-scale-change safe); fp mode is bitwise
        identical to the fused flat spelling by construction."""
        from ..comm.compressed import bucketed_grad_reduce

        D = int(self.mesh.shape["data"])
        mode = self.grad_comp
        plan = self._grad_plan
        stacked_fn = self._stacked_fn

        def body(cp, b, ce):
            grads, loss = self._gas_scan(cp, b, scale)
            # scale is divided out per bucket BEFORE compressing so the
            # error-feedback residuals are stored in true gradient units —
            # otherwise a dynamic loss-scale change would leave stale
            # residuals off by the scale ratio.
            red, nw, ns = bucketed_grad_reduce(
                grads, plan, mode=mode, axis="data",
                stacked_fn=stacked_fn, scale=scale,
                worker_err=ce["worker"][0] if "worker" in ce else None,
                server_err=ce["server"][0] if "server" in ce else None)
            if nw is not None:
                ce = {"worker": nw[None], "server": ns[None]}
            loss = lax.pmean(loss, "data")
            return red, loss, ce

        # check_vma=False: grads/loss really are replicated over 'data' (they
        # come out of an all-gather of identical chunks + a pmean), but the
        # vma inference can't prove it and would reject the P() out_specs.
        fn = jax.shard_map(
            body, mesh=self.mesh, axis_names=frozenset({"data"}),
            in_specs=(P(), P(None, "data"), P("data")),
            out_specs=(P(), P(), P("data")), check_vma=False)
        return fn(compute_params, batch, comm_err)

    def _train_step_impl(self, state: TrainState, batch: dict,
                         ltd_tokens: int = 0, comp_active: tuple = (),
                         onebit_warmup: bool = False):
        cfg = self.config
        if self._ltd is not None:
            # static per-trace constant; set before the loss is traced
            self.model.set_ltd_tokens(ltd_tokens)
        if self._comp:
            self.model.set_compression_active(comp_active)
        if self._pld:
            # traced scalar: the keep-prob schedule is continuous, no retrace
            self.model.set_pld_step(state.step)
        if self.onebit is not None:
            from .onebit import onebit_train_step

            new_master, new_opt, new_ce, loss, gnorm, lr = onebit_train_step(
                self, state, batch, jnp.float32(1.0), onebit_warmup)
            if self._pld:
                self.model.set_pld_step(None)   # don't leak the tracer
            new_state = TrainState(
                step=state.step + 1, master_params=new_master,
                opt_state=new_opt, loss_scale=state.loss_scale,
                skipped_steps=state.skipped_steps, comm_err=new_ce)
            return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr,
                               "loss_scale": jnp.float32(1.0),
                               "skipped": jnp.int32(0)}
        scale = state.loss_scale.scale

        compute_params = self._cast_compute(state.master_params)

        new_comm = state.comm_err
        if self.grad_comp:
            grads, loss, new_comm = self._compressed_grads(
                compute_params, batch, scale, state.comm_err)
        else:
            grads, loss = self._gas_scan(compute_params, batch, scale)

        # ZeRO >= 2: constrain grads to the master (partitioned) sharding so the
        # cross-data reduction lowers to reduce-scatter, not all-reduce (in the
        # compressed path the reduction already happened; this slices locally).
        grad_specs = self.partitioner.grad_spec_tree(self.master_specs)
        if grad_specs is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_specs)

        if self.grad_comp:  # compressed path already unscaled inside
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / scale, grads)
        finite = grads_finite(grads) if cfg.fp16.enabled else jnp.bool_(True)
        # Never let an overflow step poison the error-feedback residuals.
        if self.grad_comp and self._comm_err_shapes:
            new_comm = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                    new_comm, state.comm_err)

        # gradient clipping (reference engine gradient_clipping / global norm)
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)))
            clip = jnp.minimum(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * clip, grads)
        else:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)))

        lr = self.lr_schedule(state.step)

        def do_update(_):
            new_master, new_opt = self.optimizer.update(
                state.master_params, state.opt_state, grads, lr)
            if self._frozen_mask is not None:
                # static selection: XLA dead-code-eliminates the frozen
                # leaves' optimizer math entirely
                new_master = jax.tree.map(
                    lambda frozen, new, old: old if frozen else new,
                    self._frozen_mask, new_master, state.master_params)
            return new_master, new_opt, jnp.int32(0)

        def skip_update(_):
            return state.master_params, state.opt_state, jnp.int32(1)

        new_master, new_opt, skipped = lax.cond(finite, do_update, skip_update, None)
        new_ls = update_loss_scale(state.loss_scale, finite, cfg.fp16)

        if self._pld:
            self.model.set_pld_step(None)   # the traced step must not leak
        new_state = TrainState(
            step=state.step + 1,
            master_params=new_master,
            opt_state=new_opt,
            loss_scale=new_ls,
            skipped_steps=state.skipped_steps + skipped,
            comm_err=new_comm,
        )
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "loss_scale": scale, "skipped": skipped}
        return new_state, metrics

    def _check_flops_nominal(self, batch: dict) -> None:
        """One-time honesty check on MFU accounting: flops_per_sample is
        computed from the model config's *nominal* lengths (max_seq, or
        max_src/max_tgt for encoder-decoder), so if the actual batches
        carry a different token count the reported TFLOPS/MFU scale with
        the mismatch. Warn loudly rather than silently report wrong MFU
        (the headline number must not depend on a config default)."""
        if self._flops_nominal_checked:
            return
        self._flops_nominal_checked = True
        cfg = getattr(self.model, "cfg", None)
        nominal = getattr(cfg, "max_seq", None) if cfg is not None else None
        ids = batch.get("input_ids") if isinstance(batch, dict) else None
        if not nominal or ids is None or getattr(ids, "ndim", 0) < 2:
            return
        actual = ids.shape[-1]
        labels = batch.get("labels")
        if hasattr(cfg, "max_src") and getattr(labels, "ndim", 0) >= 2:
            actual += labels.shape[-1]   # encoder-decoder: separate targets
        if actual != nominal:
            log_dist(
                f"WARNING: MFU/TFLOPS accounting assumes {nominal} "
                f"tokens/sample (model config nominal lengths) but batches "
                f"carry {actual}; reported MFU is off by ~{nominal/actual:.2f}x "
                "— set max_seq (or max_src/max_tgt) to the real lengths",
                ranks=[0])

    def _eval_step_impl(self, master_params, batch: dict):
        cp = self._cast_compute(master_params)
        if self._ltd is not None:
            # eval ALWAYS runs the full sequence — token dropping is a
            # training-cost technique, not an eval semantic
            self.model.set_ltd_tokens(0)
        if self._comp:
            # eval sees the fully-compressed network (what would be exported)
            self.model.set_compression_active(
                tuple(sorted(n for n, _ in self._comp)))
        if self._pld:
            self.model.set_pld_step(None)   # eval runs every layer
        if getattr(self.model.cfg, "num_experts", 1) > 1:
            # trace-time flag: eval capacity factor (reference
            # eval_capacity_factor) applies in this trace only — finally
            # guarantees a failed trace can't leak it into a later train trace
            self.model.moe_eval_mode = True
            try:
                return self.model.loss(cp, batch)
            finally:
                self.model.moe_eval_mode = False
        return self.model.loss(cp, batch)

    # ------------------------------------------------------------ public API
    def _make_global(self, batch: dict, gas_dim: bool = True) -> dict:
        """Per-host numpy batch → global sharded jax.Arrays.

        Train batches: (gas * micro * local_dp, ...) per host, reshaped to
        (gas, local_batch, ...) then assembled along the batch dim.
        """
        cfg = self.config
        gas = int(cfg.gradient_accumulation_steps)
        sharding = self._batch_sharding(gas_dim)

        def to_global(x):
            x = np.asarray(x)
            if gas_dim:
                local = x.shape[0] // gas
                x = x.reshape((gas, local) + x.shape[1:])
            return jax.make_array_from_process_local_data(sharding, x)

        return {k: to_global(v) for k, v in batch.items()}

    # ------------------------------------------------- data efficiency hooks
    def _ltd_schedule_tokens(self, step: int, seq_len: int) -> int:
        """Linear kept-token schedule start_tokens → seq_len, quantized
        (reference random-LTD scheduler semantics). Returns seq_len exactly
        once the schedule completes, so 'finished' is reachable even when
        seq_len is not a multiple of difficulty_step."""
        c = self._ltd
        frac = min(1.0, step / max(1, c.total_steps))
        if frac >= 1.0:
            return seq_len
        r = int(c.start_tokens + (seq_len - c.start_tokens) * frac)
        r = r // c.difficulty_step * c.difficulty_step
        return max(min(r, seq_len), min(c.start_tokens, seq_len))

    def _apply_data_efficiency(self, batch: dict) -> dict:
        """Curriculum seqlen truncation (host-side, before global assembly —
        each new length is one extra compiled shape) + random-LTD kept-token
        schedule (a static jit argument: each quantum is one retrace)."""
        is_host = not isinstance(next(iter(batch.values())), jax.Array)
        seq = int(batch["input_ids"].shape[-1])
        if self.curriculum is not None and is_host:
            L = min(self.curriculum(self.global_steps), seq)
            batch = {k: (v[..., :L] if getattr(v, "ndim", 0) >= 2
                         and v.shape[-1] == seq else v)
                     for k, v in batch.items()}
            seq = L
        elif self.curriculum is not None and not self._warned_device_batch:
            self._warned_device_batch = True
            log_dist("curriculum_learning: batch arrived as pre-assembled "
                     "jax.Arrays — seqlen truncation only applies to host "
                     "batches; the curriculum is NOT in effect", ranks=[0])
        if self._ltd is not None:
            r = self._ltd_schedule_tokens(self.global_steps, seq)
            if r >= seq:
                r = 0          # schedule finished: full sequence again
            self._ltd_tokens = r
        return batch

    def _moq_eigenvalue(self) -> float:
        """Dominant Hessian eigenvalue of the current loss on the cached
        probe batch (the reference's pre-narrowing curvature check,
        engine.py:2116-2127). Few power iterations: MoQ needs the decay
        trend, not a tight estimate."""
        from ..utils.eigenvalue import max_eigenvalue

        params = jax.tree.map(lambda a: a.astype(jnp.float32),
                              self.state.master_params)
        probe = {k: jnp.asarray(v) for k, v in self._moq_probe_batch.items()}
        if jax.process_count() > 1:
            # the captured probe is host-local (one addressable shard per
            # process, different data on each): agree on process 0's copy
            # so every host schedules the same bit widths — divergent
            # comp_active tuples would desync the SPMD programs
            from jax.experimental import multihost_utils

            probe = multihost_utils.broadcast_one_to_all(probe)
        with self.mesh:
            eig, _ = max_eigenvalue(lambda p: self.model.loss(p, probe),
                                    params, iters=4)
        return float(eig)

    @_spans.timed_init("compile_train_step")
    def _compiled_step(self, batch: dict):
        """AOT-lower/compile the step program that ``train_batch`` would
        run for this batch's shapes, WITHOUT executing it — nothing
        touches device memory, so configs that would OOM can be probed."""
        if not isinstance(next(iter(batch.values())), jax.Array):
            batch = self._make_global(batch)
        if self.offload:
            # offload engines: the device program is the grad step (the
            # update runs on the host) — its footprint IS the HBM question
            with self.mesh:
                return self._grad_step.lower(
                    self.compute_params, batch,
                    jax.ShapeDtypeStruct((), jnp.float32)).compile()
        comp_active = tuple(sorted(
            n for n, off in self._comp if self.global_steps >= off))
        if self._moq is not None and "weight_quantization" in comp_active:
            # mirror train_batch: compile the program that will actually
            # run (current scheduled bit-width), so the memory numbers
            # describe it and the cached executable is reusable
            comp_active = self._moq.annotate(comp_active)
        warm = (in_warmup(self.onebit, self.global_steps)
                if self.onebit is not None else False)
        with self.mesh:
            return self._train_step.lower(
                self.state, batch, max(0, self._ltd_tokens), comp_active,
                warm).compile()

    def compile_train_step(self, batch: dict) -> dict:
        """AOT-compile the train step and return the compiler's
        buffer-assignment summary (``*_size_in_bytes``). This is how
        memory levers are *measured* (autotuner feasibility): the numbers
        are the compiler's own."""
        from ..profiling.flops_profiler import compiled_memory_analysis

        return compiled_memory_analysis(self._compiled_step(batch))

    def compiled_step_text(self, batch: dict) -> str:
        """Optimized HLO text of the step program ``train_batch`` runs for
        this batch's shapes (AOT-compiled, nothing executes): where a
        caller checks which kernels (``tpu_custom_call``) and collectives
        the compiler put in."""
        return self._compiled_step(batch).as_text()

    def cost_census(self, batch: dict) -> dict:
        """Per-program capacity census of the train step: static FLOPs /
        HBM bytes / collective bytes (compiler + HLO truth), joined with
        achieved ``train_step`` wall times from the span ring when spans
        are enabled — the training row of the capacity report
        (docs/OPERATIONS.md capacity-planning runbook). Backends without
        cost/memory analysis degrade to null-valued fields, never raise."""
        from ..observability.capacity import ProgramCensus, roofline_peaks

        pf, bw = roofline_peaks()
        census = ProgramCensus(peak_flops=pf, peak_bw=bw)
        census.measure("train_step", self._compiled_step(batch))
        if self.spans is not None:
            census.attach_spans(self.spans.events())
        return census.report()

    def grad_comm_summary(self) -> Optional[dict]:
        """Static wire summary of the gradient-communication spelling:
        mode, bucket plan, and exact payload bytes per step vs the fp32
        flat all-reduce it replaces (``comm.compressed.plan_wire_mbytes``).
        The ``achieved`` input of the capacity advisor's
        ``quantized_collectives`` lever; None when the explicit grad
        path is off (GSPMD owns the reduction — nothing to report)."""
        if not self.grad_comp or self._grad_plan is None:
            return None
        from ..comm.compressed import plan_wire_mbytes

        D = int(self.mesh.shape["data"])
        out = plan_wire_mbytes(self._grad_plan, D, self.grad_comp)
        # report the overlap the PLAN actually delivers, not the config
        # intent: bucket_elems larger than the tree degrades to one fused
        # bucket, which has nothing to overlap (the advisor's achieved
        # block must not claim otherwise)
        out.update({"active": True,
                    "overlap": bool(self.grad_overlap
                                    and len(self._grad_plan.buckets) > 1),
                    "overlap_requested": self.grad_overlap,
                    "error_feedback": bool(self._comm_err_shapes),
                    "data_world": D})
        return out

    def observe_device_stamps(self, step: int, stamps: dict) -> list:
        """Cross-host/device per-step completion stamps → the commscope
        straggler detector (observability/commscope.py). The seam a
        multi-host launcher feeds after gathering each process's stamp;
        single-process training feeds its own automatically. No-op
        (returns []) when the observatory is off."""
        if self.commscope is None:
            return []
        return self.commscope.observe_stamps(step, stamps)

    def comm_observatory(self, trace_source=None,
                         n_steps: Optional[int] = None,
                         path: Optional[str] = None) -> dict:
        """The communication observatory report: step anatomy (exposed
        vs overlapped collective time), the per-kind achieved
        bus-bandwidth ledger (static HLO bytes / measured trace wall),
        and the straggler snapshot — docs/OBSERVABILITY.md
        "Communication observatory".

        ``trace_source`` defaults to ``observability.trace_dir`` (the
        TraceWindow target); ``n_steps`` defaults to the configured
        ``trace_steps`` window length. On a backend whose profiler
        emits no device op timeline (CPU) every anatomy/ledger row
        degrades to nulls with one warning — never a raise."""
        if self.commscope is None:
            raise RuntimeError(
                "observability.commscope is not enabled — set "
                'observability.commscope={"enabled": true} (and '
                "trace_steps for the profiler window) to build the "
                "observatory")
        obs = self.config.observability
        if trace_source is None:
            trace_source = obs.trace_dir
        if self._hlo_by_kind is not None:
            self.commscope.set_collective_bytes(self._hlo_by_kind)
        if n_steps is None and obs.trace_steps:
            a, b = (int(s) for s in obs.trace_steps)
            n_steps = b - a + 1
        report = self.commscope.analyze(trace_source, n_steps=n_steps)
        # the quantized/overlapped grad-communication spelling, if on:
        # static wire bytes vs the fp32 equivalent — the capacity
        # advisor's quantized_collectives lever reads this as its
        # achieved block (score self-demotes to the REMAINING measured
        # exposed fraction)
        report["quantized"] = self.grad_comm_summary()
        if path:
            import json
            from pathlib import Path as _Path

            p = _Path(path)
            p.parent.mkdir(parents=True, exist_ok=True)
            tmp = p.with_name(p.name + ".tmp")
            tmp.write_text(json.dumps(report, indent=2, default=str),
                           encoding="utf-8")
            os.replace(tmp, p)
        return report

    # ----------------------------------------------------------- resilience
    def _note_bad_steps(self, bad: bool, window: int, last_loss: float) -> None:
        """Non-finite sentinel: ``bad`` covers ``window`` consecutive
        optimizer steps (1 on the offload path, ``steps_per_print`` on the
        in-device path). K consecutive bad steps halt with a typed error —
        a collapsed run (loss-scale death spiral, NaN weights) must stop
        burning budget, and the supervisor must see a *typed* cause."""
        if not self._max_bad_steps:
            return
        self._bad_step_streak = self._bad_step_streak + window if bad else 0
        if self._bad_step_streak >= self._max_bad_steps:
            from ..resilience.guards import NonFiniteLossError

            if self.flight is not None:
                # the halt is the post-mortem moment: freeze the black box
                # BEFORE unwinding so the dump shows the collapse window
                self.flight.note("nonfinite_halt",
                                 streak=self._bad_step_streak,
                                 last_loss=last_loss,
                                 step=self.global_steps)
                self.flight.dump("nonfinite_halt")
            raise NonFiniteLossError(
                f"halting: {self._bad_step_streak} consecutive bad optimizer "
                f"steps (threshold {self._max_bad_steps}) — non-finite loss "
                "or every step skipped on overflow; last loss "
                f"{last_loss!r} at global step {self.global_steps}. Resume "
                "from the last good checkpoint with a lower lr / higher "
                "initial loss scale.",
                streak=self._bad_step_streak, last_loss=last_loss)

    def _sentinel_at_boundary(self, loss: float) -> None:
        """In-device path: evaluate the sentinel from the report window's
        ``skipped_steps`` delta (the boundary already synced the state, so
        reading the counter adds no extra device wait)."""
        if not self._max_bad_steps:
            return
        window = int(self.config.steps_per_print)
        skipped_total = float(self.state.skipped_steps)
        all_skipped = (skipped_total - self._skipped_total_prev) >= window
        self._skipped_total_prev = skipped_total
        self._note_bad_steps(all_skipped or not math.isfinite(loss),
                             window, loss)

    # -------------------------------------------------------- observability
    def _span(self, kind, **fields):
        """The seam (observability/spans.py) on this engine's ring and its
        clock: every timed piece of host code in this file goes through
        here."""
        ring = self.spans
        return _spans.span(
            ring, ring.clock if ring is not None else time.perf_counter,
            kind, **fields)

    def _record_step_metrics(self, metrics: dict, stats: Optional[dict],
                             extra_gauges: Optional[dict] = None) -> None:
        """Step metrics → the engine registry (Train/* + Memory/*)."""
        gauges = {"Train/loss": metrics["loss"], "Train/lr": metrics["lr"],
                  "Train/grad_norm": metrics["grad_norm"]}
        if "loss_scale" in metrics:
            gauges["Train/loss_scale"] = metrics["loss_scale"]
        if extra_gauges:
            gauges.update(extra_gauges)
        if stats:
            gauges["Train/samples_per_sec"] = stats["samples_per_sec"]
            for key in ("tflops", "mfu"):
                if key in stats:
                    gauges[f"Train/{key}"] = stats[key]
            self.metrics.histogram("Train/step_time_s").observe(
                stats["step_time_s"])
            if self._step_anomaly is not None \
                    and self._step_anomaly.observe(stats["step_time_s"]):
                self.metrics.counter("Train/step_time_regressions").inc()
                med, mad = self._step_anomaly.stats()
                self.metrics.gauge("Train/step_time_baseline_s").set(med)
                log_dist(
                    f"step-time regression: {stats['step_time_s']:.4f}s vs "
                    f"rolling median {med:.4f}s (MAD {mad:.4f}s) at step "
                    f"{self.global_steps}", ranks=[0], level="WARNING")
                if self.flight is not None:
                    self.flight.note("step_time_regression",
                                     step_s=stats["step_time_s"],
                                     median_s=med, mad_s=mad,
                                     step=self.global_steps)
        self.metrics.set_gauges(gauges)
        if metrics.get("skipped"):
            self.metrics.counter("Train/skipped_steps").inc(
                metrics["skipped"])
        if self.config.observability.hbm_watermark:
            from ..observability.xla import sample_memory

            # HBM watermark at the step boundary (one host call per report
            # window; zeros on backends that don't expose memory_stats)
            sample_memory(self.metrics, self.acc)

    def _emit_monitor_events(self, extra: Optional[list] = None) -> None:
        """Flush the registry (+ any hand-built events) through the monitor
        fan-out — CSV/TB/WandB and the JSONL/Prometheus sinks alike."""
        if not self.monitor:
            return
        events = self.metrics.to_events(self.global_steps)
        if extra:
            events.extend(extra)
        self.monitor.write_events(events)
        self.monitor.flush()

    def metrics_snapshot(self) -> dict:
        """Machine-readable view of the training registry (the serving
        analog lives on ``InferenceEngine.metrics_snapshot``)."""
        snap = self.metrics.snapshot()
        if self.goodput is not None:
            snap["goodput"] = self.goodput.snapshot()
        return snap

    def health(self) -> dict:
        """Liveness/readiness snapshot for the telemetry probes (the
        training analog of ``ServingEngine.health()``): a training
        process is ``ready`` while it can take steps — i.e. it hasn't
        halted on the non-finite sentinel (a halted engine only stays
        alive long enough for a post-mortem scrape)."""
        snap = self.metrics.snapshot()
        streak = getattr(self, "_bad_step_streak", 0)
        # getattr: the telemetry server starts inside _post_init, a few
        # lines before the resilience fields land — a probe racing
        # construction must degrade, not 500
        max_bad = getattr(self, "_max_bad_steps", 0)
        halted = bool(max_bad and streak >= max_bad)
        hist = snap["histograms"].get("Train/step_time_s", {})
        return {
            "state": "halted" if halted else "training",
            "ready": not halted,
            "global_steps": self.global_steps,
            "bad_step_streak": streak,
            "skipped_steps": int(
                snap["counters"].get("Train/skipped_steps", 0)),
            "last_step_s": hist.get("last"),
            "step_time_regressions": int(
                snap["counters"].get("Train/step_time_regressions", 0)),
        }

    def serve_telemetry(self, port: Optional[int] = None,
                        host: Optional[str] = None,
                        token: Optional[str] = None) -> int:
        """Start the live telemetry plane for the training process
        (``/metrics`` ``/healthz`` ``/readyz`` ``/goodput`` ``/flight``
        + token-gated ``POST /flight/dump``; the serving-only endpoints
        — ``/requests``, ``/drain``, ``/slo/reload`` — 404 cleanly).
        Returns the bound port; idempotent. Config gate:
        ``observability.telemetry = {"enabled": true, ...}``."""
        if self.telemetry is not None:
            return self.telemetry.port
        from ..observability.server import (TelemetryConfig, TelemetryHooks,
                                            TelemetryServer, flight_summary)

        tc = TelemetryConfig.from_any(self.config.observability.telemetry
                                      or None)
        host = host if host is not None else (
            tc.host if tc is not None else "127.0.0.1")
        port = port if port is not None else (tc.port if tc is not None
                                              else 0)
        token = token if token is not None else (
            tc.token if tc is not None else "")

        def refresh():
            if self.goodput is not None:
                self.goodput.export()

        hooks = TelemetryHooks(
            registry=self.metrics,
            step_fn=lambda: int(self.global_steps),
            refresh_fn=refresh,
            health_fn=self.health,
            goodput_fn=(self.goodput.export if self.goodput is not None
                        else None),
            flight_fn=((lambda: flight_summary(self.flight))
                       if self.flight is not None else None),
            dump_fn=((lambda: self.dump_flight("manual"))
                     if self.flight is not None else None))
        server = TelemetryServer(hooks, host=host, port=port, token=token)
        # bind FIRST: a failed bind must not leave a dead server object
        # that the idempotency guard then treats as running
        bound = server.start()
        self.telemetry = server
        return bound

    def dump_flight(self, reason: str = "manual"):
        """Freeze the flight recorder (observability/flight.py) now;
        None when no recorder is configured or the dump cap is reached."""
        if self.flight is None:
            return None
        return self.flight.dump(reason)

    def close(self) -> None:
        """Teardown: close any open XLA trace window, the telemetry
        server's listener thread, and the monitor's file handles. Safe
        to call more than once."""
        if self._trace_window is not None:
            self._trace_window.close()
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
        if self.monitor:
            self.monitor.close()

    def train_batch(self, batch: dict) -> dict:
        """One optimizer step over train_batch_size samples (micro-stepping,
        grad accumulation, and the update are all inside the compiled step;
        in offload mode the update runs on the host optimizer instead)."""
        gp = self.goodput
        if gp is None:
            return self._train_batch_impl(batch)
        # goodput attribution: the call window is productive step
        # dispatch (the first call — which builds the XLA program — is
        # the compile window); gaps between calls land in queue_empty
        # (data/host time) via the ledger's gap rule. Two clock reads.
        # Accounted on SUCCESS only: a first call that raises must not
        # flip the compiled-once flag (the retry pays the real compile
        # and must be attributed to it), and an aborted window reads as
        # idle gap rather than fake productive time.
        t0 = gp.clock()
        first = not self._gp_stepped
        out = self._train_batch_impl(batch)
        self._gp_stepped = True
        gp.on_train_step(t0, gp.clock(), compiled=first)
        return out

    def _train_batch_impl(self, batch: dict) -> dict:
        if self._abstract:
            raise RuntimeError(
                "engine was built with abstract_state=True (AOT probe "
                "mode): no state is materialized — only compile_train_step "
                "is available")
        if self._chaos_preempt is not None \
                and self.global_steps == self._chaos_preempt:
            from ..resilience import chaos as _chaos

            _chaos.deliver_preemption()
        self._check_flops_nominal(batch)
        if self._trace_window is not None:
            # windowed XLA capture: opens entering trace_steps[0], closes
            # after trace_steps[1] completes (observability/xla.py)
            self._trace_window.on_step(self.global_steps)
        if self.offload:
            return self._train_batch_offload(batch)
        wcb = self.config.wall_clock_breakdown
        # the step as the seam sees it (observability/spans.py): one
        # train_step span and its parts, batch_prep / step_dispatch /
        # step_sync, whenever a ring or a profiler capture records; the
        # timers beside them only feed wall_clock_breakdown's log line.
        # The step is numbered as it will be when it is done
        n_step = self.global_steps + 1
        cs = self.commscope
        t_step0 = cs.clock() if cs is not None else 0.0
        with self._span(TRAIN_STEP, name="train.step", step=n_step):
            self.throughput.start()
            if wcb:
                self.timers.start("batch_prep")
            with self._span(TRAIN_PHASE, name="train.batch_prep",
                            step=n_step, phase="batch_prep"):
                if self.curriculum is not None or self._ltd is not None:
                    batch = self._apply_data_efficiency(batch)
                if not isinstance(next(iter(batch.values())), jax.Array):
                    batch = self._make_global(batch)
            if wcb:
                self.timers.stop("batch_prep")
            if self._moq is not None and self._moq_probe_batch is None:
                # small fixed probe batch for the curvature power iteration:
                # captured AFTER globalization (pre-converted jax batches
                # arrive in the (gas, batch, ...) layout — flatten it), one
                # row per data shard (the trunk's batch constraint needs
                # dp-divisibility)
                from ..models.transformer import mesh_dp_world

                rows = max(1, mesh_dp_world(self.mesh))

                def probe_rows(v):
                    # read only host-local shards: np.asarray on a globalized
                    # array raises on a multi-process mesh (remote shards)
                    if isinstance(v, jax.Array) and not v.is_fully_addressable:
                        a = np.asarray(v.addressable_shards[0].data)
                    else:
                        a = np.asarray(v)
                    if a.ndim >= 2:
                        a = a.reshape((-1,) + a.shape[2:])
                    if len(a) < rows:        # tiny shard: tile up to dp rows
                        a = np.resize(a, (rows,) + a.shape[1:])
                    return a[:rows]

                self._moq_probe_batch = {k: probe_rows(v)
                                         for k, v in batch.items()}
            comp_active = tuple(sorted(
                n for n, off in self._comp if self.global_steps >= off))
            if self._moq is not None and "weight_quantization" in comp_active:
                self._moq.maybe_step(self.global_steps, self._moq_eigenvalue)
                comp_active = self._moq.annotate(comp_active)
            warm = (in_warmup(self.onebit, self.global_steps)
                    if self.onebit is not None else False)
            if wcb:
                self.timers.start("step_dispatch")
            with self._span(TRAIN_PHASE, name="train.dispatch",
                            step=n_step, phase="step_dispatch"), self.mesh:
                self.state, metrics = self._train_step(
                    self.state, batch, max(0, self._ltd_tokens),
                    comp_active, warm)
            if wcb:
                self.timers.stop("step_dispatch")
            self.global_steps += 1
            boundary = self.global_steps % self.config.steps_per_print == 0
            if wcb or boundary:
                # sync FIRST, then floatify: float() on the metrics arrays
                # is itself a device wait, and running it before the
                # step_sync span would bury the whole device-execution
                # time in no span
                if wcb:
                    self.timers.start("step_sync")
                with self._span(TRAIN_PHASE, name="train.sync",
                                step=n_step, phase="step_sync"):
                    jax.block_until_ready(self.state.step)
                if wcb:
                    self.timers.stop("step_sync")
                metrics = {k: float(v) for k, v in metrics.items()}
                stats = self.throughput.stop(report=True)
                if wcb:
                    # wall-clock breakdown → registry gauges (log() also
                    # prints the reference-style "time (ms)" line and
                    # resets). Gauges record per step; sinks still flush
                    # only at boundaries.
                    for name, ms in self.timers.log(reset=True).items():
                        self.metrics.gauge(f"Train/time_{name}_ms").set(ms)
                if boundary:
                    self._sentinel_at_boundary(metrics["loss"])
                    log_dist(
                        f"step={self.global_steps} "
                        f"loss={metrics['loss']:.4f} "
                        f"lr={metrics['lr']:.3e} "
                        f"gnorm={metrics['grad_norm']:.3f}", ranks=[0])
                    # recording + emission stay on the report cadence even
                    # under wall_clock_breakdown (the HBM watermark and sink
                    # flush are documented as per-boundary, never per-step)
                    self._record_step_metrics(metrics, stats)
                    extra = []
                    if self._moq is not None and any(
                            n.startswith("weight_quantization")
                            for n in comp_active):
                        # observability for the quantization schedule (the
                        # reference logs its quantizer's bit switches too);
                        # only while QAT is actually active per its offset
                        extra.append(("Train/moq_bits", self._moq.bits,
                                      self.global_steps))
                    self._emit_monitor_events(extra)
            else:
                self.throughput.stop(report=False)
        if cs is not None:
            # per-step host window + this process's completion stamp
            # (multi-host launchers gather and feed cross-host stamps
            # through observe_device_stamps; a lone process's single
            # stamp leaves the straggler detector honestly inert).
            # traced= marks steps inside the TraceWindow so the
            # Perfetto rebase anchors the capture to THEM, not to
            # whatever pre-window steps were also stamped
            t_step1 = cs.clock()
            cs.on_step(
                self.global_steps, t_step0, t_step1,
                traced=(self._trace_window is not None
                        and self._trace_window.active))
            cs.observe_stamps(
                self.global_steps, {jax.process_index(): t_step1})
        # Profiler fires OUTSIDE the throughput window (its extra timed step
        # + one-time AOT compile must not pollute samples/s accounting).
        if self.flops_profiler and self.flops_profiler.should_fire():
            self.flops_profiler.profile(batch)
        if not self._comms_logged:
            # comms_logger: count the GSPMD-inserted collectives from the
            # compiled HLO once (the Python ledger only sees explicit comm.*
            # wrappers), plus the ledger summary. NOTE: the AOT
            # lower().compile() duplicates the step compile once — an
            # accepted, opt-in diagnostics cost (post-optimization HLO is
            # the only place the inserted collectives exist).
            self._comms_logged = True
            try:
                from ..comm.hlo_analysis import collective_summary

                with self.mesh:
                    compiled = self._train_step.lower(
                        self.state, batch, max(0, self._ltd_tokens),
                        comp_active, warm).compile()
                summ = collective_summary(compiled)
                # static per-step wire bytes by kind: kept for the
                # commscope ledger join (comm_observatory) — the
                # achieved-bandwidth denominator comes from the trace,
                # the numerator from here
                self._hlo_by_kind = summ
                if self.commscope is not None:
                    self.commscope.set_collective_bytes(summ)
                for key, d in sorted(summ.items()):
                    log_dist(f"comms | HLO {key}: n={int(d['count'])} "
                             f"vol={d['mbytes']:.1f} MB", ranks=[0])
                    # collective census → Comm/* gauges: per-step wire
                    # bytes by kind, exact from the compiled program
                    self.metrics.set_gauges({
                        f"Comm/hlo/{key}/count": d["count"],
                        f"Comm/hlo/{key}/mbytes": d["mbytes"]})
                if self.config.comms_logger.enabled:
                    from ..comm.comm import comms_logger as _cl

                    for name, value, _ in _cl.as_monitor_events(
                            self.global_steps):
                        self.metrics.gauge(name).set(value)
                    _cl.log_summary()
                # no emit here: the Comm/* gauges ride the next report
                # boundary's flush (an emit now would duplicate this
                # step's Train/* rows in every sink)
            except Exception as e:   # best-effort per backend
                log_dist(f"comms_logger: HLO summary unavailable ({e})")
        return metrics

    def eval_batch(self, batch: dict) -> float:
        if not isinstance(next(iter(batch.values())), jax.Array):
            batch = self._make_global(batch, gas_dim=False)
        with self.mesh:
            if self.offload:
                return float(self._eval_offload(self.compute_params, batch))
            return float(self._eval_step(self.state.master_params, batch))

    @property
    def lr(self) -> float:
        step = (jnp.int32(self.global_steps) if self.offload
                else self.state.step)
        return float(self.lr_schedule(step))

    @property
    def train_micro_batch_size_per_device(self) -> int:
        return int(self.config.train_micro_batch_size_per_gpu)

    @property
    def train_batch_size(self) -> int:
        return int(self.config.train_batch_size)

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, save_dir: str, tag: str | None = None) -> str:
        from .checkpoint.engine import save_checkpoint as _save

        if self.config.elasticity.enabled:
            # cross-restart immutability of the elastic schema (reference
            # elasticity.py:208): fingerprint lives next to the checkpoints
            from ..elasticity import assert_elastic_config_consistent

            assert_elastic_config_consistent(self.config.elasticity, save_dir)
        if self.goodput is not None:
            # checkpoint commit is honest badput: time the save window
            # into its own bucket instead of letting it read as idle
            with self.goodput.window("checkpoint"):
                return _save(self, save_dir, tag)
        return _save(self, save_dir, tag)

    def load_checkpoint(self, load_dir: str, tag: str | None = None) -> str:
        from .checkpoint.engine import load_checkpoint as _load

        if self.config.elasticity.enabled:
            from ..elasticity import assert_elastic_config_consistent

            assert_elastic_config_consistent(self.config.elasticity, load_dir)
        return _load(self, load_dir, tag)

    def wait_for_checkpoint(self) -> None:
        """Block until an async checkpoint save has committed to disk."""
        from .checkpoint.engine import wait_for_checkpoint as _wait

        _wait(self)

    # ------------------------------------------------------------- profiling
    def start_profile_trace(self, logdir: str) -> None:
        """Begin an XLA profiler trace (the NVTX/nsys analog —
        SURVEY §5 tracing: xplane → tensorboard/perfetto). Wrap some
        train_batch calls and view with `tensorboard --logdir`."""
        jax.profiler.start_trace(logdir)
        log_dist(f"profiler trace started → {logdir}", ranks=[0])

    def stop_profile_trace(self) -> None:
        # drain outstanding async-dispatched steps first, or the trace
        # closes mid-step and drops the device activity being profiled
        jax.block_until_ready(self.compute_params if self.offload
                              else self.state)
        jax.profiler.stop_trace()
        log_dist("profiler trace stopped", ranks=[0])


def initialize(config: Config | dict | str | None = None, model=None,
               mesh: Optional[Mesh] = None, seed: Optional[int] = None,
               **kwargs) -> Engine:
    """Public entry point (reference ``deepspeed.initialize``,
    ``deepspeed/__init__.py:64``). Returns the engine; the optimizer and LR
    scheduler live inside it, built from the config."""
    assert model is not None, "initialize() requires a model"
    return Engine(config, model, mesh=mesh, seed=seed, **kwargs)
