"""End-to-end engine tests on the virtual 8-device mesh.

Correctness oracles follow the reference test strategy (SURVEY.md §4):
loss decreases, and ZeRO stages are loss-equivalent to the unsharded
baseline (the analog of ZeRO-vs-vanilla-Adam equivalence in
tests/unit/runtime/zero/test_zero.py).
"""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset


def make_config(stage=0, **over):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "steps_per_print": 100,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 5}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": stage, "param_persistence_threshold": 0},
    }
    cfg.update(over)
    return cfg


def run_steps(engine, n_steps=8, seed=0):
    data = random_token_dataset(256, seq_len=32, vocab_size=256, seed=seed,
                                learnable=True)
    loader = DataLoader(data, local_batch_size=engine.train_batch_size,
                        shuffle=True, seed=seed)
    losses = []
    for i, batch in enumerate(loader):
        if i >= n_steps:
            break
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
    return losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stage_trains(devices, stage):
    model = build_model(tiny_test())
    engine = ds.initialize(make_config(stage=stage), model)
    losses = run_steps(engine, n_steps=8)
    assert losses[-1] < losses[0], f"stage {stage}: loss did not decrease: {losses}"


def test_zero_stages_loss_equivalent(devices):
    """All ZeRO stages compute the same optimization trajectory."""
    ref_losses = None
    for stage in [0, 1, 2, 3]:
        model = build_model(tiny_test())
        engine = ds.initialize(make_config(stage=stage), model)
        losses = run_steps(engine, n_steps=4)
        if ref_losses is None:
            ref_losses = losses
        else:
            np.testing.assert_allclose(losses, ref_losses, rtol=2e-2,
                                       err_msg=f"stage {stage} diverged from stage 0")


def test_gas_matches_large_batch(devices):
    """GAS x micro == one big batch (same global batch, same trajectory)."""
    model = build_model(tiny_test())
    e1 = ds.initialize(make_config(stage=1, train_batch_size=32,
                                   gradient_accumulation_steps=4,
                                   train_micro_batch_size_per_gpu="auto"), model)
    e2 = ds.initialize(make_config(stage=1, train_batch_size=32,
                                   gradient_accumulation_steps=1,
                                   train_micro_batch_size_per_gpu="auto"), model)
    l1 = run_steps(e1, n_steps=3)
    l2 = run_steps(e2, n_steps=3)
    np.testing.assert_allclose(l1, l2, rtol=2e-2)


def test_bf16_grad_accum_matches_fp32(devices):
    """data_types.grad_accum_dtype=bfloat16 (reference config-json.md)
    halves the grad buffer; trajectory must track the fp32 accumulator
    within bf16 rounding, across a real GAS scan."""
    l_fp32 = run_steps(ds.initialize(make_config(stage=1),
                                     build_model(tiny_test())), n_steps=4)
    l_bf16 = run_steps(ds.initialize(
        make_config(stage=1, data_types={"grad_accum_dtype": "bfloat16"}),
        build_model(tiny_test())), n_steps=4)
    np.testing.assert_allclose(l_bf16, l_fp32, rtol=3e-2)
    # alias spelling accepted
    eng = ds.initialize(make_config(
        stage=1, data_types={"grad_accum_dtype": "bf16"}),
        build_model(tiny_test()))
    assert np.isfinite(run_steps(eng, n_steps=1)[0])


def _attention(kind):
    if kind == "dense":
        return None
    from deepspeed_tpu.ops.flash_attention import make_flash_attention

    return make_flash_attention(block=16)     # interpreted on the CPU


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("policy", ["save_names", "save_names_mlp"])
def test_save_names_remat_policies_match_dense(devices, policy, attention):
    """save_names / save_names_mlp change WHAT is stored, never the math:
    trajectory must match the no-remat baseline tightly — with the dense
    attention (the projected attn_out is kept) and with the flash kernel
    (its own flash_o / flash_lse are, under a shard_map over the mesh)."""
    base = run_steps(ds.initialize(
        make_config(stage=1),
        build_model(tiny_test(), attention_fn=_attention(attention))),
        n_steps=3)
    got = run_steps(ds.initialize(
        make_config(stage=1, remat={"enabled": True, "policy": policy}),
        build_model(tiny_test(), attention_fn=_attention(attention))),
        n_steps=3)
    np.testing.assert_allclose(got, base, rtol=1e-4)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_save_names_keeps_what_the_attention_names(attention):
    """What one remat'd trunk layer saves under save_names. An attention
    function that names its residuals (the flash kernel) has its own o,
    lane-dense (B, S, H*hd), and lse, one (B, H, S) row, kept, and the
    projected attn_out goes untagged: the backward redoes one wo product,
    not the kernel's forward. Any other attention keeps attn_out."""
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals

    from deepspeed_tpu.config import Config
    from deepspeed_tpu.runtime.engine import _remat_policy

    cfg = tiny_test(dtype=jnp.float32)
    model = build_model(cfg, attention_fn=_attention(attention))
    B, S, D, H = 2, 32, cfg.d_model, cfg.n_head
    layer = jax.tree.map(lambda a: a[0],
                         model.init(jax.random.PRNGKey(0))["layers"])
    policy = _remat_policy(Config.from_any(make_config(
        remat={"enabled": True, "policy": "save_names"})))
    body = jax.checkpoint(
        lambda x, p: model._layer(x, p, model._positions(B, S), None)[0],
        policy=policy, prevent_cse=False)
    saved = [(tuple(aval.shape), why) for aval, why in saved_residuals(
        body, jnp.ones((B, S, D), jnp.float32), layer)
        if "from the argument" not in why]
    shapes = sorted(shape for shape, _ in saved)
    in_kernel = [shape for shape, why in saved if "flash_attention" in why]
    if attention == "flash":
        assert shapes == [(B, H, S), (B, S, D), (B, S, D)], saved
        assert sorted(in_kernel) == [(B, H, S), (B, S, D)], saved
        assert any("flash_lse" in why for _, why in saved), saved
    else:
        assert shapes == [(B, S, D), (B, S, D)] and not in_kernel, saved


def test_tensor_parallel_trains(devices):
    model = build_model(tiny_test())
    cfg = make_config(stage=1, train_micro_batch_size_per_gpu="auto")
    cfg["mesh"] = {"data": 2, "model": 4}
    engine = ds.initialize(cfg, model)
    assert engine.dp_world == 2
    losses = run_steps(engine, n_steps=6)
    assert losses[-1] < losses[0]


def test_ulysses_sequence_parallel_trains(devices):
    """seq axis shards the sequence dim; attention reshards via all-to-all
    (the GSPMD realization of reference sequence/layer.py)."""
    model = build_model(tiny_test())
    cfg = make_config(stage=1, train_micro_batch_size_per_gpu="auto")
    cfg["mesh"] = {"data": 2, "seq": 4}
    engine = ds.initialize(cfg, model)
    losses = run_steps(engine, n_steps=6)
    assert losses[-1] < losses[0]


def test_fp16_dynamic_loss_scale(devices):
    model = build_model(tiny_test())
    cfg = make_config(stage=2)
    cfg["bf16"] = {"enabled": False}
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    engine = ds.initialize(cfg, model)
    losses = run_steps(engine, n_steps=6)
    assert losses[-1] < losses[0]
    assert float(engine.state.loss_scale.scale) > 0


def test_eval_batch(devices):
    model = build_model(tiny_test())
    engine = ds.initialize(make_config(stage=1), model)
    data = random_token_dataset(16, 32, 256)
    batch = DataLoader(data, local_batch_size=16, shuffle=False).collate_fn(data)
    loss = engine.eval_batch(batch)
    assert np.isfinite(loss) and loss > 0


def test_device_lion_with_sharded_zero_state():
    """Single-moment optimizers (Lion: nu is a (0,) placeholder) must
    initialize under ZeRO-sharded state shardings — the rank-2 master spec
    must not be applied to the empty moment (found by the 1B Lion bench
    candidate; the old post-init fixup ran too late to save the init)."""
    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "lion", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    }, build_model(tiny_test(n_layer=2)))
    data = random_token_dataset(16, 32, 256, learnable=True)
    batch = DataLoader(data, local_batch_size=8,
                       shuffle=False).collate_fn(data[:8])
    losses = [float(engine.train_batch(dict(batch))["loss"])
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
