"""Autotuner: measured search over mesh shape × ZeRO stage × offload ×
micro-batch × remat (GAS follows: global = micro × gas × dp(mesh)).

Analog of the reference autotuner (``autotuning/autotuner.py:404``), which
profiles the model, generates a grid of experiments (ZeRO stage,
micro-batch-per-GPU, selected subsystem knobs), launches each as a short real
run, and applies model-based early stopping before writing the best config.

TPU-native differences:
- experiments run **in-process**: an engine is just a jitted function +
  sharded arrays, so "launch an experiment" is build → time a few steps →
  drop the references (no process pool / scheduler / hostfile bookkeeping —
  the reference needed those because a torch engine can't be cleanly
  destroyed in-process).
- OOM is a catchable XLA ``RESOURCE_EXHAUSTED`` error, so the tuner walks
  micro-batch sizes upward until the first failure instead of guessing from
  an activation-memory model (the reference's ``max_train_micro_batch_size``
  estimate exists because CUDA OOM often poisons the process).
- early stop: within each (stage, remat) sweep, stop growing the micro-batch
  once throughput turns over (the reference's model-based early stopping,
  reduced to the one signal that matters under a compiled step: measured
  samples/s). Stages always run — on TPU a whole-stage sweep is a handful of
  compiles, not a cluster job per cell like the reference's scheduler.
"""

from __future__ import annotations

import copy
import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import numpy as np

from ..utils.logging import log_dist


@dataclass
class Experiment:
    zero_stage: int
    micro_batch: int
    remat: bool
    mesh: dict = field(default_factory=dict)
    offload: Optional[str] = None
    samples_per_sec: float = 0.0
    ok: bool = False
    error: str = ""
    est_bytes: int = 0          # feasibility-model estimate (0 = not run)

    def label(self) -> str:
        mesh = "x".join(f"{k}{v}" for k, v in sorted(self.mesh.items())) or "dp"
        return (f"{mesh}_z{self.zero_stage}_mbs{self.micro_batch}"
                f"{'_remat' if self.remat else ''}"
                f"{'_off-' + self.offload if self.offload else ''}")


#: fp32 optimizer-moment tensors per parameter, by optimizer type. Lion and
#: momentum-SGD carry one; plain SGD none; Adam-family two. Used by the
#: feasibility model so a 1B-Lion config is not pruned for Adam-sized state.
OPTIMIZER_MOMENTS = {
    "adam": 2, "adamw": 2, "fusedadam": 2, "lamb": 2, "fusedlamb": 2,
    "onebitadam": 2, "onebitlamb": 2, "zerooneadam": 2, "adagrad": 1,
    "lion": 1, "fusedlion": 1, "momentum": 1, "sgd": 0,
}


def optimizer_moment_count(config: Optional[dict]) -> int:
    """Moments/param implied by a ds_config's optimizer block (default 2)."""
    try:
        name = str(config["optimizer"]["type"]).lower().replace("_", "")
    except (TypeError, KeyError):
        return 2
    return OPTIMIZER_MOMENTS.get(name, 2)


def estimate_experiment_bytes(model_cfg, exp: Experiment, dp: int,
                              compute_bytes: int = 2,
                              seq: Optional[int] = None,
                              opt_moments: int = 2) -> dict:
    """Per-device memory estimate for one experiment — the reference
    autotuner's model-info pass (``autotuning/autotuner.py:404`` params +
    optimizer-state arithmetic, ``:663`` activation estimate), rebuilt for
    the sharding-based stages: compute params shard over model/pipe (and
    dp at stage 3), fp32 master+moments shard over dp from stage 1,
    gradients from stage 2. The activation term is deliberately
    CONSERVATIVE (counts the fp32 logits slice and per-layer attention
    probs for the no-remat case): over-pruning costs one missed candidate,
    under-pruning costs an OOM'd child and its compile time."""
    n = model_cfg.param_count()
    mp = int(np.prod([v for k, v in exp.mesh.items()
                      if k in ("model", "pipe")])) or 1
    params = n * compute_bytes // (mp * (dp if exp.zero_stage >= 3 else 1))
    states = (0 if exp.offload else
              (1 + opt_moments) * 4 * n
              // (mp * (dp if exp.zero_stage >= 1 else 1)))
    grads = 4 * n // (mp * (dp if exp.zero_stage >= 2 else 1))
    S = seq or getattr(model_cfg, "max_seq", 1024)
    d = model_cfg.d_model
    L = model_cfg.n_layer
    # T5Config spells the FFN width d_ff and has no ffn_dim property
    f = (getattr(model_cfg, "ffn_dim", None)
         or getattr(model_cfg, "d_ff", None) or 4 * d)
    h = model_cfg.n_head
    tokens = exp.micro_batch * S
    if exp.remat:
        # saved carries + ~one live layer of intermediates
        act = L * tokens * d * compute_bytes * 2
    else:
        per_tok = (12 * d + 2 * f) * compute_bytes  # qkv/o/mlp intermediates
        probs = h * S * compute_bytes               # attention probs row
        act = L * tokens * (per_tok + probs)
    logits = tokens * model_cfg.vocab_size * 4      # fp32 loss slice
    total = params + states + grads + act + logits
    return {"params": params, "opt_states": states, "grads": grads,
            "activations": act, "logits": logits, "total": total}


class Autotuner:
    """Grid-search tuner over short real runs.

    ``model_builder`` is a zero-arg callable returning a fresh model (fresh
    params each experiment — engines donate/mutate state).  ``make_batch``
    maps a global batch size to a host batch dict."""

    def __init__(self, base_config: dict, model_builder: Callable[[], Any],
                 make_batch: Callable[[int], dict], *,
                 stages: Sequence[int] = (3, 2, 1, 0),
                 micro_batches: Optional[Sequence[int]] = None,
                 remat_options: Sequence[bool] = (False,),
                 mesh_options: "Optional[Sequence[dict]] | str" = None,
                 offload_options: Sequence[Optional[str]] = (None,),
                 steps: int = 3, warmup: int = 1,
                 early_stop_margin: float = 0.05,
                 results_path: Optional[str] = None,
                 model_spec: Optional[dict] = None,
                 isolate: Optional[bool] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 child_timeout_s: float = 900.0):
        self.base_config = base_config
        self.model_builder = model_builder
        self.make_batch = make_batch
        self.stages = list(stages)
        self.micro_batches = list(micro_batches) if micro_batches else None
        self.remat_options = list(remat_options)
        # mesh candidates: None = pure DP only; "auto" = factor the device
        # count into model/seq splits (on TPU the mesh shape is THE knob —
        # reference tunes only stage+mbs, autotuner.py:404)
        self.mesh_options = mesh_options
        self.offload_options = list(offload_options)
        self.steps = steps
        self.warmup = warmup
        self.early_stop_margin = early_stop_margin
        self.results_path = results_path
        # model_spec ({"family", "size", "overrides"}) enables BOTH
        # hardening layers the in-process tuner lacked (round-3 review):
        # the feasibility model (prune before touching the device) and
        # child isolation (each surviving experiment in its own
        # interpreter — a native CHECK-crash or OOM kills the child, not
        # the tune). ``model_builder`` remains for in-process use with
        # arbitrary models.
        self.model_spec = model_spec
        self.isolate = isolate if isolate is not None else model_spec is not None
        if self.isolate and model_spec is None:
            raise ValueError("isolate=True needs model_spec: engines and "
                             "closures do not cross process boundaries")
        self.hbm_budget_bytes = hbm_budget_bytes
        self.child_timeout_s = child_timeout_s
        self._model_cfg = None
        self._probe_seq = None
        if model_spec is not None:
            from .worker import build_model_from_spec

            _, self._model_cfg = build_model_from_spec(model_spec)
            # the seq both the estimate AND the worker run at (they must
            # judge the same workload)
            self._probe_seq = min(getattr(self._model_cfg, "max_seq", 128),
                                  512)
        self.experiments: list[Experiment] = []

    # ------------------------------------------------------------------ grid
    @staticmethod
    def _auto_mesh_options(n_dev: int) -> list[dict]:
        """Candidate (model, seq) splits of the device count; ``data``
        absorbs the remainder. Bounded: at most ~6 candidates."""
        out: list[dict] = [{}]
        for m in (2, 4):
            if n_dev % m == 0 and n_dev > m:
                out.append({"model": m})
        if n_dev % 2 == 0 and n_dev > 2:
            out.append({"seq": 2})
        if n_dev % 4 == 0 and n_dev > 4:
            out.append({"model": 2, "seq": 2})
        return out

    def _mesh_candidates(self, n_dev: int) -> list[dict]:
        if self.mesh_options is None:
            return [{}]
        if self.mesh_options == "auto":
            return self._auto_mesh_options(n_dev)
        return [dict(m) for m in self.mesh_options]

    @staticmethod
    def _dp_for_mesh(mesh: dict, n_dev: int) -> int:
        non_dp = int(np.prod([v for k, v in mesh.items()
                              if k not in ("data", "zero", "expert")])) or 1
        return max(1, n_dev // non_dp)

    def _candidate_micro_batches(self, dp: int) -> list[int]:
        if self.micro_batches is not None:
            return self.micro_batches
        global_bs = int(self.base_config.get("train_batch_size", dp))
        per_dev = max(1, global_bs // dp)
        out, m = [], 1
        while m <= per_dev:
            out.append(m)
            m *= 2
        return out

    def _experiment_config(self, exp: Experiment, dp: int) -> dict:
        cfg = copy.deepcopy(self.base_config)
        zo = cfg.setdefault("zero_optimization", {})
        zo["stage"] = exp.zero_stage
        if exp.mesh:
            cfg["mesh"] = dict(exp.mesh)   # data axis auto-absorbs the rest
        if exp.offload:
            zo["offload_optimizer"] = {"device": exp.offload}
        cfg["train_micro_batch_size_per_gpu"] = exp.micro_batch
        global_bs = int(cfg.get("train_batch_size", dp * exp.micro_batch))
        cfg["gradient_accumulation_steps"] = max(
            1, global_bs // (exp.micro_batch * dp))
        # keep global batch consistent: global = micro * gas * dp
        cfg["train_batch_size"] = (exp.micro_batch
                                   * cfg["gradient_accumulation_steps"] * dp)
        if exp.remat:
            cfg["remat"] = {"enabled": True, "policy": "dots_saveable"}
        else:
            # remat=False must really measure remat-off even when the base
            # config enables it, or the grid dimension compares identical runs
            cfg.pop("remat", None)
        cfg.setdefault("steps_per_print", 10 ** 9)
        return cfg

    # ----------------------------------------------------------- feasibility
    def _probe_device(self) -> dict:
        """(n_devices, bytes_limit) WITHOUT initializing jax in this
        process when isolating: a parent that claims the TPU would starve
        every worker child of the very device isolation exists to protect
        (review r4). Cached; probed from a throwaway subprocess."""
        if getattr(self, "_device_info", None) is not None:
            return self._device_info
        if not self.isolate:
            try:
                dev = jax.local_devices()[0]
                stats = dev.memory_stats() or {}
                self._device_info = {"n_dev": jax.device_count(),
                                     "limit": stats.get("bytes_limit")}
            except Exception:
                self._device_info = {"n_dev": 1, "limit": None}
            return self._device_info
        import subprocess
        import sys as _sys

        code = ("import json, jax; d = jax.local_devices()[0]; "
                "print(json.dumps({'n_dev': jax.device_count(), "
                "'limit': (d.memory_stats() or {}).get('bytes_limit')}))")
        try:
            p = subprocess.run([_sys.executable, "-c", code], timeout=300,
                               capture_output=True, text=True)
            line = next(ln for ln in reversed(p.stdout.strip().splitlines())
                        if ln.startswith("{"))
            self._device_info = json.loads(line)
        except Exception as e:
            log_dist(f"autotune: device probe child failed ({e!r}); no HBM "
                     "budget, nothing is pruned", level="WARNING")
            self._device_info = {"n_dev": 1, "limit": None}
        return self._device_info

    def _budget_bytes(self) -> Optional[int]:
        if self.hbm_budget_bytes is not None:
            return self.hbm_budget_bytes
        limit = self._probe_device().get("limit")
        return int(limit * 0.92) if limit else None

    def _prune_infeasible(self, exp: Experiment, dp: int) -> bool:
        """True = pruned (recorded as a failed experiment, never run)."""
        if self._model_cfg is None:
            return False
        budget = self._budget_bytes()
        if budget is None:
            return False
        est = estimate_experiment_bytes(
            self._model_cfg, exp, dp, seq=self._probe_seq,
            opt_moments=optimizer_moment_count(self.base_config))
        exp.est_bytes = int(est["total"])
        if est["total"] <= budget:
            return False
        exp.ok = False
        exp.error = (f"pruned: estimated {est['total'] / 2**30:.2f} GiB "
                     f"> budget {budget / 2**30:.2f} GiB "
                     f"(params {est['params'] / 2**30:.2f}, states "
                     f"{est['opt_states'] / 2**30:.2f}, act "
                     f"{est['activations'] / 2**30:.2f})")
        self.experiments.append(exp)
        log_dist(f"autotune: {exp.label()} {exp.error}", ranks=[0])
        return True

    # --------------------------------------------------------------- measure
    def _run_isolated(self, exp: Experiment, dp: int) -> Experiment:
        """One experiment in a fresh child interpreter (reference
        scheduler-job isolation): a crash, an OOM or a hang costs the
        child."""
        import os
        import subprocess
        import sys as _sys

        payload = json.dumps({"config": self._experiment_config(exp, dp),
                              "model_spec": self.model_spec,
                              "seq": self._probe_seq,
                              "steps": self.steps, "warmup": self.warmup})
        try:
            p = subprocess.run(
                [_sys.executable, "-m", "deepspeed_tpu.autotuning.worker",
                 payload],
                capture_output=True, text=True, env=dict(os.environ),
                timeout=self.child_timeout_s)
        except subprocess.TimeoutExpired:
            exp.error = f"child timeout after {self.child_timeout_s:.0f}s"
            return exp
        # guarded parse: a child killed
        # mid-flush can leave a truncated '{'-line — that is a failed
        # experiment, never a crashed tune
        result = None
        for ln in reversed((p.stdout or "").strip().splitlines()):
            if ln.startswith("{"):
                try:
                    result = json.loads(ln)
                    break
                except json.JSONDecodeError:
                    continue
        if result is None:
            exp.error = (f"child rc={p.returncode}, no result line: "
                         f"{(p.stderr or '')[-200:]!r}")
            return exp
        exp.ok = bool(result.get("ok"))
        exp.samples_per_sec = float(result.get("samples_per_sec", 0.0))
        exp.error = result.get("error", "")
        return exp

    def _run_one(self, exp: Experiment, dp: int) -> Experiment:
        if self.isolate:
            return self._run_isolated(exp, dp)
        import deepspeed_tpu as ds

        cfg = self._experiment_config(exp, dp)
        try:
            engine = ds.initialize(cfg, self.model_builder())
            batch = self.make_batch(engine.train_batch_size)
            for _ in range(self.warmup):
                engine.train_batch(batch)
            jax.block_until_ready(jax.tree.leaves(
                engine.state.master_params if not engine.offload
                else engine.compute_params)[0])
            t0 = time.perf_counter()
            for _ in range(self.steps):
                engine.train_batch(batch)
            jax.block_until_ready(jax.tree.leaves(
                engine.state.master_params if not engine.offload
                else engine.compute_params)[0])
            dt = (time.perf_counter() - t0) / self.steps
            exp.samples_per_sec = engine.train_batch_size / dt
            exp.ok = True
        except Exception as e:  # RESOURCE_EXHAUSTED, config errors, ...
            exp.error = f"{type(e).__name__}: {e}"[:300]
            exp.ok = False
        finally:
            # drop engine references so the next experiment's arrays can
            # reuse the HBM; donation already released most of it
            engine = None
            jax.clear_caches()
        return exp

    # ------------------------------------------------------------------ tune
    def tune(self) -> dict:
        """Run the grid; return the fastest config (base config if nothing
        succeeded). Results land in ``self.experiments`` +
        ``results_path`` JSON."""
        n_dev = max(1, int(self._probe_device().get("n_dev") or 1))
        best: Optional[Experiment] = None
        for mesh in self._mesh_candidates(n_dev):
            dp = self._dp_for_mesh(mesh, n_dev)
            for offload in self.offload_options:
                for stage in self.stages:
                    if offload and stage < 1:
                        continue   # host optimizer needs a sharded master
                    for remat in self.remat_options:
                        # turnover baseline is per sweep: remat=True starts
                        # slower at small mbs and only wins at larger ones, so
                        # it must not be early-stopped against another sweep
                        sweep_best: Optional[Experiment] = None
                        for mbs in self._candidate_micro_batches(dp):
                            exp = Experiment(stage, mbs, remat, mesh=mesh,
                                             offload=offload)
                            if self._prune_infeasible(exp, dp):
                                break  # larger micro-batches estimate bigger
                            log_dist(f"autotune: running {exp.label()}",
                                     ranks=[0])
                            exp = self._run_one(exp, dp)
                            self.experiments.append(exp)
                            log_dist(
                                f"autotune: {exp.label()} → "
                                f"{exp.samples_per_sec:.1f} samples/s"
                                f"{'' if exp.ok else ' (FAILED: ' + exp.error + ')'}",
                                ranks=[0])
                            if not exp.ok:
                                break  # larger micro-batches will also OOM
                            if sweep_best and exp.samples_per_sec < \
                                    sweep_best.samples_per_sec * (1 - self.early_stop_margin):
                                break  # throughput turned over
                            if not sweep_best or exp.samples_per_sec > \
                                    sweep_best.samples_per_sec:
                                sweep_best = exp
                        if sweep_best and (not best or sweep_best.samples_per_sec
                                           > best.samples_per_sec):
                            best = sweep_best
        # isolate mode never touches jax in-process (the children own the
        # device); the parent is then necessarily single-process
        if self.results_path and (self.isolate or jax.process_index() == 0):
            with open(self.results_path, "w") as f:
                json.dump([e.__dict__ for e in self.experiments], f, indent=2)
        if best is None:
            log_dist("autotune: every experiment failed; keeping base config",
                     ranks=[0])
            return copy.deepcopy(self.base_config)
        log_dist(f"autotune: best = {best.label()} "
                 f"({best.samples_per_sec:.1f} samples/s)", ranks=[0])
        return self._experiment_config(
            best, self._dp_for_mesh(best.mesh, n_dev))


def autotune(base_config: dict, model_builder, make_batch, **kw) -> dict:
    """One-call convenience wrapper."""
    return Autotuner(base_config, model_builder, make_batch, **kw).tune()
