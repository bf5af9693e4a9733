"""Decoder-LM MFU at >=1B params on one chip (VERDICT r4 #2).

The reference's own headline decoder row is GPT-2 1.5B training speed
(``docs/_pages/training.md:49``) and BASELINE.md's north star is >=45% MFU
on decoder LMs. GPT-2 350M measured 0.33 MFU in round 3 with the head
slice (18 ms) and trunk bwd (166 ms of 246 ms) identified as where the
points live; this bench runs the largest decoder that FITS a single v5e
(16 GiB HBM), with the two levers that target those costs:

- fused Pallas softmax-xent (no (B, S, V) fp32 logits cube), and
- Lion optimizer for the 1B row (one fp32 moment: master+moment+compute+
  grads = 14 bytes/param vs AdamW's 18 — the difference between 1.0B
  fitting and not; GPT-2-XL width at 30 layers = 1.00B params).

The headline row (``_CANDIDATES[0]``) and every attached row run one after
another in this process, each engine freed before the next. Each row records
a step decomposition (fwd / fwd+bwd / full step) so the artifact shows where
the milliseconds go, and a 350M no-remat row measures the remat dimension
where activations fit. A row that fails raises; without a TPU the script
exits non-zero.

Writes ``GPT_LARGE_BENCH.json``.
"""

import json
import math
import os
import time

import bench_common as bc

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "GPT_LARGE_BENCH.json")

# Row spec (JSON-serializable dict); _CANDIDATES[0] is the headline, the
# rest document what was measured against it on 2026-08-01. policy None = remat off;
# flash routes attention through the Pallas kernel; gas = gradient
# accumulation steps; grad_dtype "bfloat16" halves the grad buffer
# (data_types.grad_accum_dtype). Memory arithmetic on the 15.75 GiB v5e:
# 1B lion = 14.1 GiB params+state (fp32 master+moment, bf16 compute,
# fp32 grads at 1.004 B params), so only save_names-class remat fits it
# (dots_saveable compiles to 18.31 GiB at mbs8 — measured OOM dump).
_CANDIDATES = [
    # Round-5 measured at this 1B shape (latest run wins): with the
    # block-512 flash default (bf16 operands, wide MXU tiles) the flash
    # step measures 305.5 ms vs 410.5 for the best all-XLA combo — flash
    # leads. (History: at block 128 flash LOST to XLA 421.5 vs 410.5;
    # the tile width was the whole story.) The bf16-grad / gas / mlp_h
    # 1B variants all compile 0.5-2 GiB over the line (OOM dumps in
    # PROGRESS notes) - buffer assignment, not arithmetic, owns that
    # margin.
    dict(tag="1b_lion_mbs4_flash512_savenames",
         kw=dict(size="1.5b", n_layer=30), opt="lion", micro=4, seq=1024,
         policy="save_names", fused=None, flash=True, gas=1,
         grad_dtype=None),
    dict(tag="1b_lion_mbs4_xla_savenames", kw=dict(size="1.5b", n_layer=30),
         opt="lion", micro=4, seq=1024, policy="save_names", fused=False,
         flash=False, gas=1, grad_dtype=None),
    dict(tag="774m_lion_mbs16_flash_savenames", kw=dict(size="774m"),
         opt="lion", micro=16, seq=1024, policy="save_names", fused=None,
         flash=True, gas=1, grad_dtype=None),
    dict(tag="350m_lion_mbs16_flash", kw=dict(size="350m"), opt="lion",
         micro=16, seq=512, policy="dots_saveable", fused=None, flash=True,
         gas=1, grad_dtype=None),
    dict(tag="350m_adamw_mbs16", kw=dict(size="350m"), opt="adamw",
         micro=16, seq=512, policy="dots_saveable", fused=False, flash=False,
         gas=1, grad_dtype=None),
]

# Extra measured row (attached as "mlph_774m"): save_names_mlp keeps the
# pre-GELU MLP intermediate so the backward never recomputes w_in — only
# fits below 1B; bf16 grads buy back the saved-activation head-room.
_MLPH_EXTRA = dict(tag="774m_lion_mbs8_mlph_bf16g", kw=dict(size="774m"),
                   opt="lion", micro=8, seq=1024, policy="save_names_mlp",
                   fused=None, flash=True, gas=1, grad_dtype="bfloat16")

# A/B twins run AFTER the headline lands, each TOGGLING one lever on the
# winner's exact config (VERDICT r5 priorities (a)/(b)): fused-vs-XLA
# xent and flash-vs-XLA attention, whichever direction the winner isn't;
# plus the remat dimension on the 350M shape where activations fit.
# mbs4: the mbs8 no-remat step compiled to 16.36 GiB (round-5 OOM dump)
_REMAT_OFF_TWIN = dict(tag="350m_lion_noremat", kw=dict(size="350m"),
                       opt="lion", micro=4, seq=512, policy=None, fused=None,
                       flash=False, gas=1, grad_dtype=None)


def _twin_spec(spec, key: str):
    """Derive an A/B twin from a winning spec by flipping one lever.
    fused: None (auto → Pallas-fused on TPU) <-> False (XLA loss path)."""
    s = dict(spec, kw=dict(spec["kw"]))
    if key == "xent":
        to_xla = s["fused"] is None or s["fused"] is True
        s["fused"] = False if to_xla else None
        s["tag"] += "_xlaxent" if to_xla else "_fusedxent"
    elif key == "attn":
        s["flash"] = not s["flash"]
        s["tag"] = (s["tag"].replace("_flash", "") + "_xlaattn"
                    if not s["flash"] else s["tag"] + "_flashattn")
    return s


def _run_candidate(spec: dict, devices) -> dict:
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset
    from deepspeed_tpu.utils.timer import peak_flops_for

    tag, kw, opt, micro, seq = (spec["tag"], spec["kw"], spec["opt"],
                                spec["micro"], spec["seq"])
    remat_policy, fused, flash = spec["policy"], spec["fused"], spec["flash"]
    gas, grad_dtype = spec.get("gas", 1), spec.get("grad_dtype")
    remat = remat_policy is not None
    kw = dict(kw)
    size = kw.pop("size")
    model_cfg = gpt2(size, max_seq=seq, fused_xent=fused, **kw)
    attn = None
    if flash:
        from deepspeed_tpu.ops.flash_attention import make_flash_attention

        attn = make_flash_attention()
    model = build_model(model_cfg, attention_fn=attn)
    engine = ds.initialize({
        "train_batch_size": micro * gas * len(devices),
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": opt, "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
        "remat": {"enabled": remat,
                  "policy": remat_policy or "dots_saveable"},
        "data_types": {"grad_accum_dtype": grad_dtype},
        "steps_per_print": 10 ** 9,
    }, model)
    data = random_token_dataset(engine.train_batch_size, seq_len=seq,
                                vocab_size=model_cfg.vocab_size)
    batch = DataLoader(data, local_batch_size=engine.train_batch_size,
                       shuffle=False).collate_fn(data[:engine.train_batch_size])

    jax.block_until_ready(engine.train_batch(dict(batch))["loss"])  # compile
    n_steps = 10
    t0 = time.perf_counter()
    for _ in range(n_steps):
        m = engine.train_batch(dict(batch))
    final_loss = float(jax.block_until_ready(m["loss"]))
    dt = (time.perf_counter() - t0) / n_steps
    if not math.isfinite(final_loss):
        raise RuntimeError(f"non-finite loss {final_loss}")

    # step decomposition: fwd-only and fwd+bwd over the same micro-batch
    import jax.numpy as jnp

    cast = jax.jit(engine._cast_compute)
    with engine.mesh:
        cp = cast(engine.state.master_params)
        mb = {k: jnp.asarray(np.asarray(v)[:micro]) for k, v in batch.items()}
        fwd = jax.jit(lambda p, b: engine.model.loss(
            p, b, remat_policy=engine.remat_policy))
        bwd = jax.jit(lambda p, b: jax.grad(
            lambda pp: engine.model.loss(
                pp, b, remat_policy=engine.remat_policy).astype(
                    jnp.float32))(p))

        def timed(fn, reps=6):
            jax.block_until_ready(fn(cp, mb))             # compile
            t = time.perf_counter()
            for _ in range(reps):
                out = fn(cp, mb)
            jax.block_until_ready(out)
            return (time.perf_counter() - t) / reps

        t_fwd = timed(fwd)
        t_bwd = timed(bwd)

    tokens_per_sec = engine.train_batch_size * seq / dt
    mfu = (tokens_per_sec * model_cfg.flops_per_token()
           / (peak_flops_for(devices[0]) * len(devices)))
    n_params = model_cfg.param_count()
    n_params_str = (f"{n_params / 1e9:.2f}B" if n_params >= 10 ** 9
                    else f"{n_params / 1e6:.0f}M")
    result = {
        "metric": f"gpt2_{size}{'' if size != '1.5b' else '_30L'}_"
                  f"{opt}_mfu",
        "value": round(mfu, 4),
        # BASELINE.md north star: >=45% MFU on decoder LMs
        "vs_baseline": round(mfu / 0.45, 4),
        "unit": (f"MFU ({n_params_str} params, tokens/s="
                 f"{tokens_per_sec:.0f}, step={dt * 1000:.1f}ms, seq={seq}, "
                 f"mbs={micro}, gas={gas}, opt={opt}, "
                 f"grads={grad_dtype or 'fp32'}, "
                 f"remat={remat_policy if remat else 'off'}, "
                 f"attn={'flash' if flash else 'xla'}, "
                 f"xent={bc.xent_label(fused)}, "
                 f"platform={devices[0].platform}, "
                 f"device_kind={devices[0].device_kind})"),
        "decompose_ms": {
            "fwd_micro": round(t_fwd * 1000, 1),
            "fwd_bwd_micro": round(t_bwd * 1000, 1),
            "bwd_only_micro": round((t_bwd - t_fwd) * 1000, 1),
            "full_step_global": round(dt * 1000, 1),
        },
        "candidate": tag,
    }
    return result


def main():
    import gc

    import jax

    devices = bc.require_tpu("gptl-bench")

    def row(spec):
        gc.collect()
        jax.clear_caches()
        return _run_candidate(dict(spec), devices)

    headline = _CANDIDATES[0]
    best = row(headline)
    # secondary rows attached to the artifact (not replacing the headline):
    # A/B twins toggling the xent and attention levers on the headline's
    # exact config + the 774M saved-MLP row + the 350M no-remat row
    # measuring the remat dimension where activations fit outright.
    for key in ("xent", "attn"):
        best[f"{key}_flip"] = row(_twin_spec(headline, key))
    for key, spec in (("mlph_774m", _MLPH_EXTRA),
                      ("remat_off_350m", _REMAT_OFF_TWIN)):
        best[key] = row(spec)
    with open(_OUT, "w") as f:
        json.dump(best, f, indent=2)
    print(json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
