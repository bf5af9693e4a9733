"""Operator tool: decompose the main-bench train step's time on the TPU.

Times, for the bench.py flagship config (GPT-2-350M, micro 16, seq 512,
ZeRO-1, dots_saveable remat):
  trunk_fwd — forward hidden states only (no lm-head matmul, no xent)
  fwd       — full forward loss
  grad      — loss + backward (no optimizer)
  step      — full train_batch (fwd+bwd+optimizer+clip)
Deltas localize the budget: lm-head+xent fwd = fwd - trunk_fwd;
backward = grad - fwd; optimizer+clip+cast = step - grad.

Not part of the test suite; exits non-zero without a TPU.
"""

import json
import time

import jax
import jax.numpy as jnp


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))     # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    import bench_common as bc

    bc.require_tpu("decompose")
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset
    from deepspeed_tpu.runtime.engine import _remat_policy

    micro, seq = 16, 512
    cfg = {
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
        "remat": {"enabled": True, "policy": "dots_saveable"},
    }
    # engine row: the flagship auto config (fused xent auto-on for TPU);
    # the explicit fwd/grad rows below pin fused_xent both ways so the
    # naive baseline is actually naive
    model_cfg = gpt2("350m", max_seq=seq)
    model = build_model(gpt2("350m", max_seq=seq, fused_xent=False))
    engine = ds.initialize(cfg, build_model(model_cfg))
    policy = _remat_policy(engine.config)
    data = random_token_dataset(micro * 2, seq_len=seq,
                                vocab_size=model_cfg.vocab_size)
    batch = DataLoader(data, local_batch_size=micro,
                       shuffle=False).collate_fn(data[:micro])

    res = {}
    res["step_ms"] = timed(lambda b: engine.train_batch(b)["loss"], batch) * 1e3

    with jax.set_mesh(engine.mesh):
        cp = jax.jit(engine._cast_compute)(engine.state.master_params)
        cp = jax.tree.map(lambda x: x.copy(), cp)   # detach from donated state

        loss_j = jax.jit(lambda p, b: model.loss(p, b, remat_policy=policy))
        res["fwd_ms"] = timed(loss_j, cp, batch) * 1e3

        grad_j = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss(p, b, remat_policy=policy)))
        res["grad_ms"] = timed(lambda p, b: grad_j(p, b)[0], cp, batch) * 1e3

        feat_cfg = gpt2("350m", max_seq=seq, objective="feature")
        feat = build_model(feat_cfg)
        fp = jax.jit(feat.init)(jax.random.PRNGKey(0))
        fp = jax.tree.map(lambda x: x.astype(jnp.bfloat16), fp)
        trunk_j = jax.jit(lambda p, ids: feat.apply(p, ids, remat_policy=policy))
        res["trunk_fwd_ms"] = timed(trunk_j, fp, batch["input_ids"]) * 1e3

        # fused Pallas xent vs the XLA loss path, fwd and fwd+bwd
        fused_model = build_model(gpt2("350m", max_seq=seq, fused_xent=True))
        floss_j = jax.jit(lambda p, b: fused_model.loss(p, b,
                                                        remat_policy=policy))
        res["fwd_fused_ms"] = timed(floss_j, cp, batch) * 1e3
        fgrad_j = jax.jit(jax.value_and_grad(
            lambda p, b: fused_model.loss(p, b, remat_policy=policy)))
        res["grad_fused_ms"] = timed(lambda p, b: fgrad_j(p, b)[0],
                                     cp, batch) * 1e3

    res = {k: round(v, 1) for k, v in res.items()}
    res["head_xent_fwd_ms"] = round(res["fwd_ms"] - res["trunk_fwd_ms"], 1)
    res["bwd_ms"] = round(res["grad_ms"] - res["fwd_ms"], 1)
    res["opt_ms"] = round(res["step_ms"] - res["grad_ms"], 1)
    res.update(commscope_columns(engine, batch))
    print(json.dumps(res))


def commscope_columns(engine, batch, n_steps=3):
    """Exposed/overlap collective columns + per-kind achieved GB/s from
    a short profiler window over the engine's own train step
    (observability/commscope.py — the T3 decomposition the plain wall
    deltas above cannot see). Nulls, never a crash, when the backend's
    profiler yields no device op timeline."""
    import tempfile

    from deepspeed_tpu.comm.hlo_analysis import collective_summary
    from deepspeed_tpu.observability.commscope import (CommScope,
                                                       CommScopeConfig)

    out = {"exposed_comm_frac": None, "overlap_frac": None}
    try:
        tdir = tempfile.mkdtemp(prefix="decompose_commscope_")
        jax.profiler.start_trace(tdir)
        try:
            for _ in range(n_steps):
                engine.train_batch(batch)
            jax.block_until_ready(engine.state.step)
        finally:
            # a failed traced step must not leave the process-wide
            # profiler session open (the next start_trace would raise)
            jax.profiler.stop_trace()
        cs = CommScope(CommScopeConfig(enabled=True),
                       n_devices=len(jax.devices()))
        cs.set_collective_bytes(
            collective_summary(engine._compiled_step(batch)))
        rep = cs.analyze(tdir, n_steps=n_steps)
        an = rep["anatomy"]
        out["exposed_comm_frac"] = an["exposed_comm_frac"]
        out["overlap_frac"] = an["overlap_frac"]
        for kind, row in rep["ledger"]["by_kind"].items():
            if row["busbw_gbps"] is not None:
                out[f"comm_{kind}_busbw_gbps"] = round(
                    row["busbw_gbps"], 1)
    except Exception as e:     # diagnostics must not cost the artifact
        out["commscope_error"] = f"{type(e).__name__}: {str(e)[:160]}"
    return out


if __name__ == "__main__":
    main()
