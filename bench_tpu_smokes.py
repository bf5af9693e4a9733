"""On-TPU composition smokes that need no perf claim — just proof of
compile+execute on the real backend (VERDICT r4 weak #6/#7).

Rows (each one compiled AND executed step, tiny shapes, loss must be
finite):

- ``bf16_pipeline`` — a bf16 PipelinedTransformerLM train step. On CPU the
  engine upcasts pipeline collectives to fp32 (models/pipeline.py CPU
  workaround), so every green pipeline test so far proved fp32 numerics
  only; this smoke is the first bf16 pipe program a real TPU backend
  lowers end to end. Single chip still exercises the bf16 collective
  lowering path (pipe=1 degenerates the permutes; the dtype path is what
  is under test) — on a real pod the same program shards pipe>1.
- ``fp16_offload`` — the round-5 fp16 loss-scaling host-optimizer step.

Writes ``TPU_SMOKES.json`` (one JSON object; per-row ok/error). Runs in
this process; exits non-zero without a TPU (the rows prove the TPU
lowering, nothing else) or when a row fails.
"""

import json
import os
import time

import bench_common as bc

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "TPU_SMOKES.json")


def _smoke_bf16_pipeline():
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import PipelinedTransformerLM, tiny_test
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    model = PipelinedTransformerLM(
        tiny_test(n_layer=4, max_seq=64, dtype=jnp.bfloat16),
        n_stages=1, num_micro=2, schedule="1f1b")
    eng = ds.initialize({
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
    }, model)
    data = random_token_dataset(4, seq_len=64, vocab_size=256)
    batch = DataLoader(data, local_batch_size=4,
                       shuffle=False).collate_fn(data)
    import jax

    loss = float(jax.block_until_ready(eng.train_batch(batch)["loss"]))
    assert np.isfinite(loss), loss
    return {"loss": round(loss, 4)}


def _smoke_fp16_offload():
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test
    from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

    eng = ds.initialize({
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2,
                              "offload_optimizer": {"device": "cpu"}},
        "fp16": {"enabled": True, "initial_scale_power": 8},
    }, build_model(tiny_test(max_seq=64, dtype=jnp.float16)))
    data = random_token_dataset(4, seq_len=64, vocab_size=256)
    batch = DataLoader(data, local_batch_size=4,
                       shuffle=False).collate_fn(data)
    m = eng.train_batch(batch)
    assert np.isfinite(m["loss"]), m
    return {"loss": round(float(m["loss"]), 4),
            "loss_scale": m["loss_scale"], "skipped": m["skipped"]}


def _smoke_spec_decode():
    """Self-speculative serving lane (PR-16): greedy spec-on must
    reproduce spec-off bit-exactly while committing >= 1 token per
    slot-step, and the fixed-shape verify must not mint compile shapes
    per acceptance count — a second traffic batch with different
    accept/reject patterns compiles NOTHING new. CPU-runnable (tier-1
    wiring lives in tests/unit/test_speculation.py); on TPU it proves
    the T=k+1 verify program lowers on the real backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(n_layer=2, d_model=64, d_ff=128, n_head=4,
                    max_seq=128, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # speculation refuses an engine whose plain step uses the Pallas decode
    # kernel (the T > 1 verify forward is dense), and on a TPU flash_decode
    # resolves on: it has to be turned off by hand (first seen on the v5e,
    # PR 22)
    eng = ds.init_inference(model, params, {"dtype": "float32",
                                            "flash_decode": False})

    def traffic(seed):
        rng = np.random.default_rng(seed)
        return [np.tile(rng.integers(0, 64, (4,)).astype(np.int32), 5)
                for _ in range(5)]

    base = {"slots": 3, "max_len": 128, "prefill_chunk": 16,
            "greedy": True, "page_size": 16}
    spec = {**base, "speculation": {"ngram": 3, "max_draft": 4}}
    prompts, max_new = traffic(7), [24] * 5
    srv = ds.ServingEngine(eng, base)
    want = srv.serve_batch(prompts, max_new)
    srv.close()
    srv = ds.ServingEngine(eng, spec)
    got = srv.serve_batch(prompts, max_new)
    assert all(np.array_equal(a, b) for a, b in zip(want, got)), \
        "greedy spec-on diverged from spec-off"
    snap = srv.spec_snapshot()
    assert snap["verify_steps"] > 0, snap
    assert snap["accepted_tokens_per_step"] >= 1.0, snap
    warm = srv.compiles
    srv.serve_batch(traffic(8), max_new)   # new acceptance patterns
    assert srv.compiles == warm, \
        f"{srv.compiles - warm} new compiles after warmup — verify " \
        "shape must not depend on acceptance counts"
    snap = srv.spec_snapshot()
    srv.close()
    return {"parity_requests": len(prompts),
            "verify_steps": snap["verify_steps"],
            "accepted_tokens_per_step":
                round(snap["accepted_tokens_per_step"], 3),
            "new_compiles_after_warmup": 0}


_SMOKES = {"bf16_pipeline": _smoke_bf16_pipeline,
           "fp16_offload": _smoke_fp16_offload,
           "spec_decode": _smoke_spec_decode}


def main():
    import jax

    platform = bc.require_tpu("smokes")[0].platform
    rows = {}
    for name, fn in _SMOKES.items():
        t0 = time.time()
        try:
            detail = fn()
            rows[name] = {"ok": True, "seconds": round(time.time() - t0, 1),
                          **detail}
        except Exception as e:      # every row is reported, then exit 1
            rows[name] = {"ok": False, "seconds": round(time.time() - t0, 1),
                          "error": f"{type(e).__name__}: {str(e)[:300]}"}
        bc.log(f"{name}: {rows[name]}", "smokes")
        jax.clear_caches()
    green = all(r["ok"] for r in rows.values())
    out = {"metric": "tpu_compile_execute_smokes",
           "value": sum(1 for r in rows.values() if r["ok"]),
           "vs_baseline": 1.0 if green else 0.0,
           "unit": f"of {len(rows)} smokes green (platform={platform})",
           "rows": rows, "platform": platform,
           "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(_OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out), flush=True)
    if not green:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
