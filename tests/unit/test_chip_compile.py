"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed wherever jax[tpu] is; it compiles for a chip
that is described and not attached, and refuses what the chip's compiler
would refuse (block shapes off the (8, 128) tiling, too much scoped VMEM).
Interpret-mode parity tests cannot see those. Nothing runs here: a compile
that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture, in the test's
own process, and only in this file: one process at a time may hold the TPU
library, so a second file (or a child process) would skip in silence.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference.decode import (GenCarry, cache_layout,
                                            decode_step)
from deepspeed_tpu.inference.sampling import sample_logits
from deepspeed_tpu.models import build_model, gpt2, llama2
from deepspeed_tpu.ops.decode_attention import decode_attention
from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.woq_matmul import woq_matmul, woq_matmul_t
from deepspeed_tpu.ops.xent import fused_token_nll
from deepspeed_tpu.serving.slots import (init_slots, insert_request,
                                         retire_slots)

# (name, d_model, heads, kv_heads, head_dim, d_ff, vocab) at published width
GPT2_774M = ("gpt2-774m", 1280, 20, 20, 64, 5120, 50257)
LLAMA2_7B = ("llama2-7b", 4096, 32, 32, 128, 11008, 32000)
WIDTHS = [pytest.param(GPT2_774M, id="gpt2-774m"),
          pytest.param(LLAMA2_7B, id="llama2-7b")]
SEQ = 1024
GROUP = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel absent from the compiled text"
    return text


def _flash_kernels(text):
    """The flash kernels among the compiled text's Mosaic calls, by the
    name their ``pallas_call`` gave them, sorted: one entry an instruction."""
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    return sorted(m.group(1) for m in (
        re.search(r"%\w*?(flash_attention_(?:fwd|bwd_dq|bwd_dkv))[_.\d]* = ",
                  ln) for ln in calls) if m)


GPT2_1_5B = ("gpt2-1.5b", 1600, 25, 25, 64, 6400, 50257)
# the micro-batch of 16 a chip of both train cells; the kernels lower for
# real (no interpreter) at (16, 20 | 25, 1024, 64)
FLASH_SHAPES = [pytest.param(GPT2_774M, 16, id="gpt2-774m-cell"),
                pytest.param(GPT2_1_5B, 16, id="gpt2-1.5b-cell"),
                pytest.param(LLAMA2_7B, 2, id="llama2-7b")]


@pytest.mark.parametrize("width,batch", FLASH_SHAPES)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention(one_chip, width, batch, grad):
    _, _, H, KV, hd, _, _ = width
    qkv = ((batch, SEQ, H, hd), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = _compile(fn, one_chip, qkv, qkv, qkv)
    assert _flash_kernels(text) == (
        ["flash_attention_bwd_dkv", "flash_attention_bwd_dq"] if grad
        else []) + ["flash_attention_fwd"]


def _trunk_grad(model, policy):
    """Gradient of a scanned trunk (x and the stacked layers) with the
    layer body remat'd under ``policy``; None for no remat."""
    def loss(x, layers):
        B, S, _ = x.shape
        y, _ = model._scan_layers(x, layers, model._positions(B, S), None,
                                  policy)
        return y.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1))


def _save_names():
    from deepspeed_tpu.config import Config
    from deepspeed_tpu.runtime.engine import _remat_policy

    return _remat_policy(Config.from_any({
        "train_batch_size": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "remat": {"enabled": True, "policy": "save_names"}}))


_TILED = re.compile(r"\b(bf16|f32)\[([\d,]+)\]\{([\d,]+):T\(8,128\)")


def _stacked(text, L, B):
    """{(dtype, dims): bytes as stored} of the compiled text's buffers
    shaped (L, B, ...): what a scan over L layers keeps of a micro-batch of
    B for its backward. The tile pads the two minor-most dims in memory
    order, to 128 and to 8 rows of 32 bits (16 of bf16)."""
    out = {}
    for dtype, dims, order in _TILED.findall(text):
        dims = [int(d) for d in dims.split(",")]
        order = [int(d) for d in order.split(",")]
        if len(dims) < 3 or dims[:2] != [L, B] or len(order) != len(dims):
            continue
        item = 2 if dtype == "bf16" else 4
        stored = list(dims)
        stored[order[0]] += -stored[order[0]] % 128
        stored[order[1]] += -stored[order[1]] % (32 // item)
        n = item
        for d in stored:
            n *= d
        out[dtype, tuple(dims)] = max(n, out.get((dtype, tuple(dims)), 0))
    return out


@pytest.mark.parametrize("size", ["774m", "1.5b"])
@pytest.mark.parametrize("chips", [1, 4], ids=["one-chip", "2x2-data4"])
def test_save_names_keeps_the_flash_kernels_own_residuals(topo, chips, size):
    """The gradient of a scanned trunk at the two train cells' widths (GPT-2
    774M: 20 heads; 1.5B: 25 heads, a width off the lanes), seq 1024, under
    save_names, the micro-batch of 16 a chip of the train cells: exactly the
    three flash kernels, ONE instruction each, in the compiled module (two
    ``flash_attention_fwd`` before the kernel named its residuals: the remat
    ran it again for o and lse). At 774M what the scan stacks for the
    backward is the layer's input, the kernel's o as (B, S, H*hd) with no
    lane of padding, and lse as one (B, H, S) row — not the kernel's
    (B, H, S, 64) padded to 128 lanes, nor its eight equal sublanes of lse
    (at 1600 the layouts are the compiler's: PERF.md "PR 41"). Eight
    layers: a scan compiles one body whatever its length, and eight rows
    fill a tile whichever way the compiler lays the stack. On the 2x2 host
    the batch is split over ``data`` and the kernel runs in a
    ``shard_map``: the names inside it are kept all the same."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.flash_attention import make_flash_attention
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    L, B = 8, 16
    cfg = gpt2(size, n_layer=L, max_seq=SEQ, dtype=jnp.bfloat16)
    D, H = cfg.d_model, cfg.n_head
    model = build_model(cfg, attention_fn=make_flash_attention(
        interpret=False))
    mesh = build_mesh(MeshSpec(data=chips), devices=topo.devices[:chips])

    def arg(a, spec):
        return jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                    sharding=NamedSharding(mesh, spec))

    layers = jax.tree.map(
        lambda a: arg(a, P()),
        jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"])
    x = arg(jax.ShapeDtypeStruct((B * chips, SEQ, D), jnp.bfloat16),
            P("data"))
    with mesh:
        text = jax.jit(_trunk_grad(model, _save_names())).lower(
            x, layers).compile().as_text()
    kernels = _flash_kernels(text)
    assert kernels == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                       "flash_attention_fwd"], kernels
    if size == "774m":
        assert _stacked(text, L, B) == {
            ("bf16", (L, B, SEQ, D)): L * B * SEQ * D * 2,   # layer_in, flash_o
            ("f32", (L, B, H, SEQ)): L * B * H * SEQ * 4,    # flash_lse
        }


def test_save_names_gradients_equal_no_remat_under_flash():
    """The interpret-mode twin of the compile above: the same gradient at
    unit-test width, against no remat at all."""
    from deepspeed_tpu.models import tiny_test
    from deepspeed_tpu.ops.flash_attention import make_flash_attention

    cfg = tiny_test(n_layer=3, dtype=jnp.float32)
    model = build_model(cfg, attention_fn=make_flash_attention(block=16))
    layers = model.init(jax.random.PRNGKey(0))["layers"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    want = _trunk_grad(model, None)(x, layers)
    got = _trunk_grad(model, _save_names())(x, layers)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("width", WIDTHS)
def test_decode_attention(one_chip, width):
    _, _, H, KV, hd, _, _ = width
    B = 8
    cache = ((B, KV, hd, SEQ), jnp.bfloat16)
    _compile(lambda q, ck, cv, n: decode_attention(q, ck, cv, n,
                                                   interpret=False),
             one_chip, ((B, 1, H, hd), jnp.bfloat16), cache, cache,
             ((B,), jnp.int32))


@pytest.mark.parametrize("B,H,KV,hd,L,alibi", [
    pytest.param(48, 20, 20, 64, 36, False, id="gpt2-774m-cell"),
    pytest.param(48, 5, 5, 64, 36, False, id="tp4-shard-5heads"),
    pytest.param(8, 32, 8, 128, 4, False, id="gqa-group4-hd128"),
    pytest.param(8, 16, 4, 128, 4, True, id="alibi-gqa"),
    pytest.param(4, 64, 64, 128, 2, False, id="64heads-two-programs"),
])
def test_decode_attention_over_the_whole_cache(one_chip, B, H, KV, hd, L,
                                               alibi):
    """The kernel as the slot step calls it: the whole ``(L, B, KV, hd,
    max_len)`` cache, a traced ``layer``, (B,) lengths; at the serving
    cells' real shape, and where the tile rule gives another tile."""
    cache = ((L, B, KV, hd, SEQ), jnp.bfloat16)
    slopes = (((H,), jnp.float32),) if alibi else ()
    _compile(lambda q, ck, cv, n, layer, *s: decode_attention(
        q, ck, cv, n, layer=layer, alibi_slopes=s[0] if s else None,
        interpret=False),
        one_chip, ((B, 1, H, hd), jnp.bfloat16), cache, cache,
        ((B,), jnp.int32), ((), jnp.int32), *slopes)


def _appending(q, ck, cv, n, layer, k, v):
    return decode_attention(q, ck, cv, n, k=k, v=v, layer=layer,
                            interpret=False)


@pytest.mark.parametrize("width", WIDTHS)
def test_decode_attention_appending(one_chip, width):
    """The call the slot step makes: the step's new K/V as operands, the
    caches handed back where they were (aliased: no temporary of their
    size) by the one kernel."""
    _, _, H, KV, hd, _, _ = width
    B, L = 48, 4
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, 1, H, hd), jnp.bfloat16), ((L, B, KV, hd, SEQ), jnp.bfloat16),
        ((L, B, KV, hd, SEQ), jnp.bfloat16), ((B,), jnp.int32),
        ((), jnp.int32), ((B, 1, KV, hd), jnp.bfloat16),
        ((B, 1, KV, hd), jnp.bfloat16))]
    compiled = jax.jit(_appending, donate_argnums=(1, 2)).lower(
        *args).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "decode_attention" in calls[0], calls
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * L * B * KV * hd * SEQ * 2
    assert mem.temp_size_in_bytes < 2 ** 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("B,H,KV,hd,vd,L,S,W,name", [
    pytest.param(48, 8, 2, 128, 128, 20, 4096, 8, "cca_decode_attention",
                 id="zaya-cell"),
    pytest.param(32, 64, 4, 192, 128, 2, 32768, 2, "full_decode_attention",
                 id="mimo-full-layers-cell"),
])
def test_decode_attention_appending_with_wide_turns(one_chip, B, H, KV, hd,
                                                    vd, L, S, W, name):
    """Where the heads are few a loop turn takes several live blocks
    (``blocks_per_turn``): 8 for ZAYA's 2 KV heads of 128, 2 for MiMo's
    full layers' 4 with keys of 192 over values of 128. The appending call
    at the cells' shapes: buffers ``W`` blocks wide, ``W`` copies a turn,
    the patched block written back from a traced 128-lane range — one
    Mosaic call under the name the benchmark's roofline finds it by, the
    caches in place."""
    from deepspeed_tpu.ops.decode_attention import blocks_per_turn

    assert blocks_per_turn(KV, hd, vd, S, jnp.bfloat16) == W
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, 1, H, hd), jnp.bfloat16), ((L, B, KV, hd, S), jnp.bfloat16),
        ((L, B, KV, vd, S), jnp.bfloat16), ((B,), jnp.int32),
        ((), jnp.int32), ((B, 1, KV, hd), jnp.bfloat16),
        ((B, 1, KV, vd), jnp.bfloat16))]
    compiled = jax.jit(
        lambda q, ck, cv, n, layer, k, v: decode_attention(
            q, ck, cv, n, k=k, v=v, layer=layer, name=name, interpret=False),
        donate_argnums=(1, 2)).lower(*args).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and f"/{name}/pallas_call" in calls[0], calls
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * B * KV * (hd + vd) * S * 2
    assert mem.temp_size_in_bytes < 2 ** 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("B,H,KV,hd,L,S", [
    pytest.param(48, 20, 20, 64, 36, 1024, id="gpt2-774m-cells"),
    pytest.param(12, 16, 16, 128, 192, 384, id="ouro-2.6b-cell"),
    pytest.param(16, 32, 8, 128, 4, 2048, id="gqa-two-blocks-a-turn"),
])
def test_decode_attention_with_a_tail(one_chip, B, H, KV, hd, L, S):
    """The call the ``Dense`` kind's step makes (``tail=``) at the serving
    cells' shapes: the slot's tile of 16 rows copied in and out at a traced
    slot and head offset, its rows landed at a traced 16-row offset of the
    buffer the identity product turns, the block written back under a
    condition — one Mosaic call under the one name, the planes and the tail
    donated and handed back in place."""
    from deepspeed_tpu.ops.decode_attention import tail_rows

    T = tail_rows(jnp.bfloat16)
    assert T == 16
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, 1, H, hd), jnp.bfloat16), ((L, B, KV, hd, S), jnp.bfloat16),
        ((L, B, KV, hd, S), jnp.bfloat16), ((B,), jnp.int32),
        ((), jnp.int32), ((B, 1, KV, hd), jnp.bfloat16),
        ((B, 1, KV, hd), jnp.bfloat16),
        ((L, B, KV, T, 2 * hd), jnp.bfloat16))]
    compiled = jax.jit(
        lambda q, ck, cv, n, layer, k, v, tail: decode_attention(
            q, ck, cv, n, k=k, v=v, tail=tail, layer=layer, interpret=False),
        donate_argnums=(1, 2, 7)).lower(*args).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "/decode_attention/pallas_call" in calls[0], \
        calls
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * L * B * KV * hd * (S + T) * 2
    assert mem.temp_size_in_bytes < 2 ** 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("tailed", [False, True], ids=["block", "tail"])
def test_decode_attention_appending_under_a_model_axis(topo, tailed):
    """``model`` = 4 over the described host's four chips: the kernel runs
    per shard of 5 KV heads inside a ``shard_map`` with the caches in its
    ``out_specs`` (GSPMD cannot partition a Mosaic kernel); the deferred
    tail's heads shard as the planes'."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    _, _, H, KV, hd, _, _ = GPT2_774M
    B, L = 48, 4
    mesh = build_mesh(MeshSpec(data=1, model=4), devices=topo.devices)
    rows, cache = P(None, None, "model", None), P(None, None, "model")

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = (arg((B, 1, H, hd), jnp.bfloat16, rows),
            arg((L, B, KV, hd, SEQ), jnp.bfloat16, cache),
            arg((L, B, KV, hd, SEQ), jnp.bfloat16, cache),
            arg((B,), jnp.int32, P()), arg((), jnp.int32, P()),
            arg((B, 1, KV, hd), jnp.bfloat16, rows),
            arg((B, 1, KV, hd), jnp.bfloat16, rows))
    fn, donated = _appending, (1, 2)
    if tailed:
        args += (arg((L, B, KV, 16, 2 * hd), jnp.bfloat16, cache),)
        fn, donated = (lambda q, ck, cv, n, layer, k, v, tail:
                       decode_attention(q, ck, cv, n, k=k, v=v, tail=tail,
                                        layer=layer, interpret=False)), \
            (1, 2, 7)
    with mesh:
        compiled = jax.jit(fn, donate_argnums=donated).lower(
            *args).compile()
    text = compiled.as_text()
    assert "decode_attention" in text and "tpu_custom_call" in text
    # a shard's caches: 5 of 20 heads, donated and handed back in place
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * L * B * (KV // 4) * hd * (SEQ + 16 * tailed) * 2


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_fused_token_nll(one_chip, width, grad):
    _, d, _, _, _, _, V = width
    T = 2 * SEQ

    def nll(x, w, t):
        return fused_token_nll(x, w, None, t, 256, 512, False).sum()

    fn = jax.grad(nll, argnums=(0, 1)) if grad else nll
    _compile(fn, one_chip, ((T, d), jnp.bfloat16), ((V, d), jnp.bfloat16),
             ((T,), jnp.int32))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("bits", [8, 4], ids=["int8", "int4"])
def test_woq_matmul(one_chip, width, bits):
    """Up- and down-projection: G = K/128 is 10 and 40 (774M), 32 and 86
    (7B) — both scale blockings (8 rows, whole group dim) are compiled."""
    _, d, _, _, _, ff, _ = width
    for K, N in ((d, ff), (ff, d)):
        _compile(lambda x, q, s: woq_matmul(x, q, s, group_size=GROUP,
                                            bits=bits, interpret=False),
                 one_chip, ((8, K), jnp.bfloat16),
                 ((K // 2 if bits == 4 else K, N), jnp.int8),
                 ((K // GROUP, N), jnp.float32))


@pytest.mark.parametrize("width,bits", [
    pytest.param(GPT2_774M, 8, id="int8-gpt2-774m"),
    pytest.param(LLAMA2_7B, 8, id="int8-llama2-7b"),
    pytest.param(LLAMA2_7B, 4, id="int4-llama2-7b"),
])
def test_woq_matmul_t(one_chip, width, bits):
    """Tied head: a vocab 128 does not divide (50257) quantizes to a single
    group; 32000 has 250 groups of 128 rows. (An odd vocab cannot pack int4
    row pairs: ``woq_matmul_t_eligible`` keeps it on XLA.)"""
    _, d, _, _, _, _, V = width
    gs = GROUP if V % GROUP == 0 else V
    _compile(lambda x, q, s: woq_matmul_t(x, q, s, group_size=gs, bits=bits,
                                          interpret=False),
             one_chip, ((8, d), jnp.bfloat16),
             ((V // 2 if bits == 4 else V, d), jnp.int8),
             ((V // gs, d), jnp.float32))


def _slot_step(one_chip, cfg, slots):
    """The serving engine's donated slot step (``_step_impl``), compiled
    for the described chip at full width; (compiled, cache shape)."""
    model = build_model(cfg)
    shape, _ = cache_layout(cfg, slots, SEQ)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, cfg.dtype if a.dtype == jnp.float32 else a.dtype,
            sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    # the slots' own state: lengths a slot (0 where a row is not running),
    # ``done`` and the tokens left, which the step counts down
    carry = on_chip(jax.eval_shape(lambda: init_slots(cfg, slots, SEQ)))
    assert carry.cache.k.shape == shape and carry.left.shape == (slots,)
    sampler = partial(sample_logits, greedy=False, temperature=0.8,
                      top_k=40, top_p=1.0)
    step = jax.jit(lambda p, c: decode_step(
        model, p, c, sampler=sampler, flash_decode=True, logit_guard=True),
        donate_argnums=(1,))
    return step.lower(params, carry).compile(), shape


@pytest.mark.parametrize("name,slots", [
    pytest.param("gpt2-774m", 32, id="gpt2-774m-32slots"),
    pytest.param("gpt2-774m", 48, id="gpt2-774m-48slots"),
    pytest.param("llama2-7b", 8, id="llama2-7b-4layers"),
])
def test_slot_step_keeps_the_cache_in_place(one_chip, monkeypatch, name,
                                            slots):
    """The built program, pinned: the cache enters donated, only the one
    kernel that appends and attends touches it, and it leaves aliased to the
    output. On the layout
    with ``hd`` last the step's temporaries were a second cache (6.25 GiB
    at 32 slots, and 48 did not fit the chip): a whole-cache ``copy``
    around the layer loop and a slice / transpose / write-back of every
    layer's slab (PERF.md F10)."""
    # the gates ask the backend whether Mosaic compiles this; the program
    # is built for the described chip, so the answer is the chip's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = (gpt2("774m", max_seq=SEQ, dtype=jnp.bfloat16)
           if name == "gpt2-774m" else
           llama2("7b", n_layer=4, max_seq=SEQ, dtype=jnp.bfloat16))
    compiled, (L, B, KV, hd, S) = _slot_step(one_chip, cfg, slots)
    mem = compiled.memory_analysis()
    cache_bytes = 2 * L * B * KV * hd * S * 2
    # 0.009 GiB at 48 slots of GPT-2 774M: the kernels' operands are the
    # cache itself, a query row and a new position a slot
    assert mem.temp_size_in_bytes < 0.02 * 2 ** 30, mem.temp_size_in_bytes
    # donated, in place: the planes, and the tail of 16 positions beside
    # them that the kernel writes in rows
    assert mem.alias_size_in_bytes >= cache_bytes * (S + 16) // S
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any("decode_attention" in ln for ln in calls)
    # the append is the attention kernel's own: no second kernel a layer
    assert not any("cache_append" in ln for ln in calls)
    # a result shaped like the cache, a layer's slab or a slot's (either
    # way round) from anything that moves data
    slab = re.compile(
        rf"= \w+\[(?:{L},|1,)?{B},{KV},(?:{hd},{S}|{S},{hd})\]\S* "
        r"(copy|scatter|dynamic-slice|dynamic-update-slice|transpose|"
        r"fusion)\(")
    moved = [ln.strip()[:160] for ln in text.splitlines() if slab.search(ln)]
    assert not moved, moved


@pytest.mark.parametrize("kind", ["contiguous", "latent"])
def test_seating_and_retiring_keep_the_cache_in_place(one_chip, kind):
    """The two small programs around the step that touch the slot state:
    ``insert_request`` with the tokens left as one more scalar, and
    ``retire_slots`` (a mask of rows to length 0, for a retirement the
    device cannot foresee). Both take the state donated and hand the cache
    back where it was: a retirement that copied it would cost 8.4 GiB."""
    from deepspeed_tpu.inference.decode import init_cache
    from deepspeed_tpu.models import deepseek_v3

    if kind == "latent":
        slots, S = KANANA["slots"], KANANA["S"]
        cfg = deepseek_v3("kanana-2-30b-a3b", n_layer=KANANA["L"],
                          dtype=jnp.bfloat16)
    else:
        slots, S = 48, SEQ
        cfg = gpt2("774m", max_seq=SEQ, dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    state = on_chip(jax.eval_shape(
        lambda: init_slots(cfg, slots, S, jnp.bfloat16)))
    cache_bytes = sum(a.size * 2 for name, a in state.cache._asdict().items()
                      if name != "length")
    one = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, 1, S, jnp.bfloat16)))
    pf = on_chip(GenCarry(
        tok=jax.ShapeDtypeStruct((1,), jnp.int32), cache=one,
        rng=jax.ShapeDtypeStruct((1, 2), jnp.uint32),
        done=jax.ShapeDtypeStruct((1,), jnp.bool_)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    for compiled in (
            jax.jit(insert_request, donate_argnums=(0,)).lower(
                state, i32, pf, i32).compile(),
            jax.jit(retire_slots, donate_argnums=(0,)).lower(
                state, mask).compile()):
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes
        assert mem.temp_size_in_bytes < 2 ** 20, mem.temp_size_in_bytes


# ------------------------------------------------- latent cache, expert rows
KANANA = dict(L=7, slots=48, H=32, rank=512, rope=64, S=8192, E=128, d=2048,
              f=768)


@pytest.mark.parametrize("kernel", ["mla_decode_attention", "latent_append",
                                    "experts_swiglu-step",
                                    "experts_swiglu-chunk"])
def test_latent_and_expert_kernels(one_chip, kernel):
    """The latent decode step's two kernels and the grouped expert product
    at Kanana-2-30B-A3B's published widths: the 576-value contraction, the
    (576, 128) append tile and 16-row expert blocks have to pass Mosaic."""
    from deepspeed_tpu.ops.mla_attention import (latent_append,
                                                 mla_decode_attention)
    from deepspeed_tpu.ops.moe_matmul import experts_swiglu

    k = KANANA
    D, bf, i32 = k["rank"] + k["rope"], jnp.bfloat16, jnp.int32
    cache = ((k["L"], k["slots"], D, k["S"]), bf)
    if kernel == "mla_decode_attention":
        # Kanana's cache, and Ling's one latent layer of 160 slots x 24 576
        for L, slots, S in ((k["L"], k["slots"], k["S"]), (1, 160, 24576)):
            _compile(lambda q, c, n, layer: mla_decode_attention(
                q, c, n, layer=layer[0], rank=k["rank"], scale=0.072,
                interpret=False), one_chip,
                ((slots, k["H"], D), bf), ((L, slots, D, S), bf),
                ((slots,), i32), ((1,), i32))
    elif kernel == "latent_append":
        _compile(lambda c, new, n, layer: latent_append(
            c, new, n, layer=layer[0], interpret=False), one_chip,
            cache, ((k["slots"], D), bf), ((k["slots"],), i32), ((1,), i32))
    else:
        tokens = k["slots"] if kernel.endswith("step") else 512
        rows = -(-(tokens * 6 + k["E"] * 15) // 16) * 16
        bank = ((k["E"], k["d"], k["f"]), bf)
        _compile(lambda xs, wg, wi, wo, be, used: experts_swiglu(
            xs, wg, wi, wo, be, used[0], bm=16, interpret=False), one_chip,
            ((rows, k["d"]), bf), bank, bank, ((k["E"], k["f"], k["d"]), bf),
            ((rows // 16,), i32), ((1,), i32))


def test_latent_slot_step_keeps_the_cache_in_place(one_chip, monkeypatch):
    """The 48-slot step of the seven-layer cut: the latent cache enters
    donated and leaves aliased, the four kernels are in the program, and
    the live set fits the chip (15.75 GiB) with the weights beside it."""
    from deepspeed_tpu.models import deepseek_v3

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    k = KANANA
    cfg = deepseek_v3("kanana-2-30b-a3b", n_layer=k["L"], dtype=jnp.bfloat16)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda key: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(key)),
        jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(
        lambda: init_slots(cfg, k["slots"], k["S"], jnp.bfloat16)))

    def step(params, carry):
        return decode_step(model, params, carry, flash_decode=True,
                           sampler=partial(sample_logits, temperature=1.0),
                           logit_guard=True, moe_stats=True)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(params,
                                                        state).compile()
    mem = compiled.memory_analysis()
    cache_bytes = k["L"] * k["slots"] * 576 * k["S"] * 2
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 15.75 * 2 ** 30, live
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for kernel in ("mla_decode_attention", "mla_cache_append",
                   "moe_experts_up", "moe_experts_down"):
        assert any(kernel in ln for ln in calls), f"{kernel} absent"


# ------------------------------------------------------------ a looped trunk
@pytest.mark.parametrize("program", ["slot step", "final chunk"])
def test_looped_trunk_keeps_the_cache_in_place(one_chip, monkeypatch,
                                               program):
    """Ouro-2.6B whole at the cell's 12 slots x 384: the pass loop and the
    layer loop are loops of the program around ONE kernel call (not 192
    unrolled layers), the 192-plane cache enters donated and leaves aliased,
    temporaries stay under 64 MiB, and the step's live set leaves the chip
    (15.75 GiB) over 1 GiB beside the batch-1 prefill cache."""
    from deepspeed_tpu.inference.decode import (forward_with_cache,
                                                init_cache)
    from deepspeed_tpu.models import ouro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_len, chunk = 12, 384, 128
    cfg = ouro("2.6b", dtype=jnp.bfloat16)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def served(key):
        """The tree as ``InferenceEngine`` serves it: bf16, [wq | wk | wv]
        one weight (with the three apart the compiler merges the products
        itself and concatenates the whole stacks outside the loops: 1.4 GiB
        of temporaries)."""
        p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), model.init(key))
        layers = dict(p["layers"])
        layers["wqkv"] = jnp.concatenate(
            [layers.pop(k) for k in ("wq", "wk", "wv")], axis=-1)
        return {**p, "layers": layers}

    params = on_chip(jax.eval_shape(served, jax.random.PRNGKey(0)))
    planes = cfg.n_layer * cfg.loop_steps
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, slots, max_len, jnp.bfloat16)))
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, exit_pdf=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        batch = slots
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len, jnp.bfloat16)))
        ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start),
                last_token_head=True, last_index=last, with_passes=True),
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        batch = 1
    mem = compiled.memory_analysis()
    cache_bytes = 2 * planes * batch * 16 * 128 * max_len * 2
    assert planes == 192 and cache_bytes == batch * max_len * 1572864
    assert mem.alias_size_in_bytes >= cache_bytes      # donated, in place
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    prefill_cache = max_len * 1572864
    assert live + (prefill_cache if batch > 1 else 0) \
        < (15.75 - 1.0) * 2 ** 30, live
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert len(loops) == 2, len(loops)        # the passes, the layers
    if program == "slot step":
        assert len(calls) == 1 and "decode_attention" in calls[0]
    else:
        assert not calls      # T > 1 attends densely over its plane


# -------------------------------------------------- one mixer a layer
@pytest.mark.parametrize("program", ["slot step", "chunk", "final chunk"])
def test_hybrid_trunk_keeps_the_state_in_place(one_chip, monkeypatch,
                                               program, capsys):
    """Nemotron-3-Super's share (layers MEMEMEM*EME, 128 of 512 experts,
    ``benchmark/configs/nemotron-3-super-l11-e128.json``) at the cell's 64
    slots x 6144, chunks of 512: the cache's four buffers enter donated and
    leave aliased; no copy of the (5, 64, ...) state stands around a layer
    (temporaries under 64 MiB in the step, under 1 GiB in a chunk); every
    new kernel lowers for the chip; the step's live set leaves the chip over
    1 GiB beside the batch-1 prefill cache. The pattern's eleven runs of one
    layer are eleven bodies of the program (no loop to scan: PERF.md), and
    the compile time says what that costs."""
    import json
    import time

    from benchmark.models import nemotron_h as fam
    from deepspeed_tpu.inference.decode import (forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_len, chunk = 64, 6144, 512
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-super-l11-e128.json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)
    keep = set(model.fp32_param_names())

    def served(path, a):
        """As ``InferenceEngine`` serves it: bf16 but for the leaves the
        model keeps in float32 (the routers, the decay's scalars)."""
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype if name in keep else jnp.bfloat16,
            sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.tree_util.tree_map_with_path(
        served, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, slots, max_len, jnp.bfloat16)))
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        batch = slots
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len, jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True)[final ^ 1:],
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        batch = 1
    took = time.perf_counter() - t0
    with capsys.disabled():
        print(f"\n[hybrid {program}: compiled for a described v5e in "
              f"{took:.1f} s]")
    mem = compiled.memory_analysis()
    held = batch * (state_bytes_per_slot(cfg, jnp.bfloat16) + max_len * 1024)
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 21278720
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert mem.temp_size_in_bytes < (64 if batch > 1 else 1024) * 2 ** 20, \
        mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    prefill_cache = state_bytes_per_slot(cfg, jnp.bfloat16) + max_len * 1024
    assert live + (prefill_cache if batch > 1 else 0) \
        < (15.75 - 1.0) * 2 ** 30, live
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "ssm_state_step", "latent_experts_up", "latent_experts_down",
        "decode_attention", "moe_experts_up")}
    # (a chunk with no head drops its last layer, an E: nothing reads it)
    experts = 5 if program != "chunk" else 4
    assert count == {
        "ssm_state_step": 5 if batch > 1 else 0,
        "latent_experts_up": experts, "latent_experts_down": experts,
        "decode_attention": 1 if batch > 1 else 0, "moe_experts_up": 0}, count


# ------------------------------------- window layers beside full ones
@pytest.mark.parametrize("program", ["slot step", "chunk", "final chunk"])
def test_windowed_trunk_keeps_planes_and_rings_in_place(one_chip, monkeypatch,
                                                        program, capsys):
    """MiMo-V2-Flash's share (layers GSSSSGS, 16 of 256 experts,
    ``benchmark/configs/mimo-v2-flash-l7-e16.json``) at the cell's 32 slots
    x 32 768, chunks of 512: the cache's four buffers enter donated and
    leave aliased; the decode kernel lowers for the chip with K blocks of
    192 beside V blocks of 128 under its two new names; a chunk's attention
    stands nowhere as a (64, 512, 32 768) score tensor (4.3 GB: temporaries
    stay under 1.5 GiB); the step's live set leaves the chip room beside the
    batch-1 prefill cache."""
    import json
    import time

    from benchmark.models import mimo_v2_flash as fam
    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_len, chunk = 32, 32768, 512
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "mimo-v2-flash-l7-e16.json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)
    keep = set(model.fp32_param_names())

    def served(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype if name in keep else jnp.bfloat16,
            sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.tree_util.tree_map_with_path(
        served, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, slots, max_len, jnp.bfloat16)))
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        batch = slots
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len, jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True)[final ^ 1:],
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        batch = 1
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[windowed {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB]")
    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 5120
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 6553600
    held = batch * (6553600 + max_len * 5120)
    assert mem.alias_size_in_bytes >= held             # donated, in place
    # (the step's: XLA stages one buffer of rings, 84 MB, through VMEM)
    assert mem.temp_size_in_bytes < (128 if batch > 1 else 1536) * 2 ** 20, \
        mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    prefill_cache = 6553600 + max_len * 5120
    assert live + (prefill_cache if batch > 1 else 0) \
        < (15.75 - 1.0) * 2 ** 30, live
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "full_decode_attention", "window_decode_attention",
        "decode_attention", "moe_experts_up")}
    # one call a run of layers (G | SSSS | G | S), the experts' three runs
    # (a chunk with no head drops its last layer's experts: nothing reads
    # them; its attention stays, for the ring it writes)
    assert count == {
        "full_decode_attention": 2 if batch > 1 else 0,
        "window_decode_attention": 2 if batch > 1 else 0,
        "decode_attention": 0,
        "moe_experts_up": 2 if program == "chunk" else 3}, count
    # no copy of a projection's weights re-laid out for heads of 192
    assert not [ln for ln in compiled.as_text().splitlines()
                if " copy(" in ln and "4096,12288]{1,2,0" in ln]


# ---------------------- compressed convolutional attention, the zaya router
@pytest.mark.parametrize("program", ["slot step", "chunk", "final chunk"])
def test_cca_trunk_keeps_planes_and_tails_in_place(one_chip, monkeypatch,
                                                   program, capsys):
    """ZAYA1-8B's first pipeline stage (20 of 40 layers, every expert, the
    whole vocabulary, ``benchmark/configs/zaya1-8b-l20.json``) at the cell's
    48 slots x 4096, chunks of 512: the planes and the tails enter donated
    and leave aliased; the decode kernel lowers for the chip at 8 : 2 heads of
    128 under its own name, once (one scan over the 20 layers); the step's
    live set leaves the chip room beside the batch-1 prefill cache."""
    import json
    import time

    from benchmark.models import zaya as fam
    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_len, chunk = 48, 4096, 512
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "zaya1-8b-l20.json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    # the serving tree: every leaf in the served type
    # (benchmark/kinds/_serving.py build), q, k and v one matrix
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    layers = dict(params["layers"])
    d = cfg.d_model
    qkv = sum(layers.pop(k).shape[-1] for k in ("wq", "wk", "wv"))
    layers["wqkv"] = jax.ShapeDtypeStruct((cfg.n_layer, d, qkv), jnp.bfloat16,
                                          sharding=one_chip)
    params = {**params, "layers": layers}
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, slots, max_len, jnp.bfloat16)))
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        batch = slots
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len, jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True)[final ^ 1:],
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        batch = 1
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[cca {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB]")
    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 20480
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 107520
    held = batch * (107520 + max_len * 20480)
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert mem.temp_size_in_bytes < (256 if batch > 1 else 1024) * 2 ** 20, \
        mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live + (held // slots if batch > 1 else 0) \
        < (15.75 - 1.0) * 2 ** 30, live
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "cca_decode_attention", "decode_attention", "moe_experts_up",
        "moe_experts_down")}
    assert count == {"cca_decode_attention": int(batch > 1),
                     "decode_attention": 0, "moe_experts_up": 1,
                     "moe_experts_down": 1}, count


# ------------- a Mamba-2 mixer and attention side by side in every layer
def test_ssm_state_step_at_blocks_of_two_mebibytes(one_chip):
    """``ssm_state_step`` at both cells' shapes lowers for the chip at a
    block of 2 MiB of float32 state a program — Falcon-H1's one group of
    (16, 128, 256), Nemotron's four groups of 16 heads as (64, 64, 128) — in
    and out double-buffered 8 MiB of a core's 16 MiB of scoped VMEM, with the
    batch's decays in SMEM beside it."""
    from deepspeed_tpu.ops.ssm_step import (groups_per_program, kernel_fits,
                                            ssm_state_step)

    f32 = jnp.float32
    for L, B, H, G, P, N, gb in ((6, 96, 32, 2, 128, 256, 1),
                                 (5, 64, 128, 8, 64, 128, 4)):
        assert kernel_fits(H, G, P, N)
        assert groups_per_program(H, G, P, N) == gb
        assert gb * (H // G) * P * N * 4 == 2 << 20
        text = _compile(
            lambda S, lay, x, dt, A, Bv, Cv, n: ssm_state_step(
                S, lay, x, dt, A, Bv, Cv, n, interpret=False), one_chip,
            ((L, B, H, P, N), f32), ((), jnp.int32), ((B, H, P), f32),
            ((B, H), f32), ((H,), f32), ((B, G, N), f32), ((B, G, N), f32),
            ((B,), jnp.int32))
        assert "ssm_state_step" in text


@pytest.mark.parametrize("program", ["slot step", "chunk", "final chunk"])
def test_parallel_trunk_keeps_planes_and_state_in_place(one_chip, monkeypatch,
                                                        program, capsys):
    """Falcon-H1-34B's stage (6 of 72 layers, the embedding and the head,
    ``benchmark/configs/falcon-h1-34b-l6.json``) at the cell's 96 slots x
    1536, chunks of 256: the planes, the state and the window enter donated
    and leave aliased; ``ssm_state_step`` and the decode kernel (under its
    own name, at five query heads a KV head) lower for the chip once each
    (one scan over the six layers); the step's live set stands under 15.0
    GiB, which is what keeps the mix at 96 slots and not 80."""
    import json
    import time

    from benchmark.models import falcon_h1 as fam
    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_len, chunk = 96, 1536, 256
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    # every leaf in the served type (benchmark/kinds/_serving.py build)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, slots, max_len, jnp.bfloat16)))
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        batch = slots
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, max_len, jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start),
                last_token_head=final,
                last_index=last if final else None)[final ^ 1:],
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        batch = 1
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[parallel {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB]")
    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 12288
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 25350144
    held = batch * (25350144 + max_len * 12288)
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert mem.temp_size_in_bytes < (256 if batch > 1 else 1024) * 2 ** 20, \
        mem.temp_size_in_bytes
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live + (held // slots if batch > 1 else 0) < 15.0 * 2 ** 30, live
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "ssm_state_step", "gqa_decode_attention", "decode_attention")}
    assert count == {"ssm_state_step": int(batch > 1),
                     "gqa_decode_attention": int(batch > 1),
                     "decode_attention": 0}, count


# ----------------------- latents a position a row: an indexer's selection
GLM = dict(slots=10, max_len=32768, chunk=512, L=7, words=384, K=2048)


def _glm(one_chip):
    """(cfg, model, abstract served params) of GLM-5.2's share
    (``benchmark/configs/glm-5.2-l7-e16.json``: layers F s s s F s s, 16 of
    256 experts) at the published widths."""
    import json

    from benchmark.models import glm_moe_dsa as fam

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-5.2-l7-e16.json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)
    keep = set(model.fp32_param_names())

    def served(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype if name in keep else jnp.bfloat16,
            sharding=one_chip)

    return cfg, model, jax.tree_util.tree_map_with_path(
        served, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("program", ["slot step", "chunk", "final chunk"])
def test_sparse_latent_trunk_fits_and_reads_the_selection_only(
        one_chip, monkeypatch, program, capsys):
    """GLM-5.2's share at the cell's 10 slots x 32 768, chunks of 512: both
    buffers enter donated and leave aliased; every program's live set beside
    what else stands on the chip (the slots' cache beside a chunk, the
    batch-1 prefill cache beside the step) stays under 15.0 GiB — ISSUE 51's
    limit, which 12 slots break; no (64, 512, 32 768) float32 score array
    stands in a chunk, and no program sorts its scores (512 x 32 768 a chunk,
    10 x 32 768 the step); a chunk and a final chunk hold four calls of the
    chunk's attention kernel (one a run of layers) and nothing of the walk
    it replaced; the step holds four calls of the sparse read (one
    a run of layers), each handed the selection's mask beside its indices
    (the dense side is in the program: a slot's live blocks whole under the
    mask, or a descriptor a selected row, by ``reads_dense`` a slot), two of
    the score and of the key append, and NO other operation touches the
    latents' buffer: XLA reads no layer's latents, the kernel's own DMAs
    bring in what it attends."""
    import time

    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g = GLM
    cfg, model, params = _glm(one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 7 * 1536 + 2 * 256
    a_slot = g["max_len"] * 11264
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, g["chunk"]), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(lambda: init_slots(
            cfg, g["slots"], g["max_len"], jnp.bfloat16)))
        assert state.cache.c.shape == (7, 10, 32768, 1, 384) \
            and state.cache.c.dtype == jnp.uint32
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        held, beside = g["slots"] * a_slot, a_slot
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, g["max_len"], jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True)[final ^ 1:],
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        held, beside = a_slot, g["slots"] * a_slot
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\n[sparse latent {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB; with "
              f"what stands beside it {(live + beside) / 2 ** 30:.2f} GiB]")
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert live + beside < 15.0 * 2 ** 30, (live + beside) / 2 ** 30
    # ... which 12 slots would not: two more slots' cache
    assert live + beside + 2 * a_slot > 15.0 * 2 ** 30 or program != \
        "final chunk"
    text = compiled.as_text()
    assert not re.search(r"f32\[(1,)?64,512,32768\]", text)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "sparse_mla_decode_attention", "sparse_mla_chunk_attention",
        "dsa_index_score", "mla_cache_append", "mla_decode_attention",
        "moe_experts_up")}
    step = program == "slot step"
    # a selection is a threshold and counts (PR 52): no sort of a chunk's
    # 512 x 32 768 scores, none of the step's 10 x 32 768
    sorts = [ln for ln in text.splitlines() if re.search(r" sort\(", ln)]
    assert not any(re.search(r"\[(1,)?(512|10),(1,)?32768\]", ln)
                   for ln in sorts), sorts
    # a chunk's attention is the kernel's (PR 54), a call a run of layers
    # (3 + 2 + 1 + 1: seven a chunk): no accumulator of 64 heads x 512
    # queries carried round a loop of XLA's, no block expanded into 64
    # heads' k_nope | v, no (64, 512, 512) scores or probabilities
    walk = [ln for ln in text.splitlines() if re.search(
        r"f32\[1,64,512,256\]|bf16\[1,512,64,448\]|\[(1,)?64,512,512\]",
        ln)]
    assert not walk, walk[:3]
    assert count == {
        "sparse_mla_decode_attention": 4 if step else 0,
        "sparse_mla_chunk_attention": 0 if step else 4,
        "dsa_index_score": 2 if step else 0,
        "mla_cache_append": 2 if step else 0, "mla_decode_attention": 0,
        # (a chunk with no head drops its last layer's experts)
        "moe_experts_up": 3}, count
    if step:
        reads = [ln for ln in calls
                 if "/sparse_mla_decode_attention/pallas_call" in ln]
        assert all("f32[10,1,32768]" in ln for ln in reads), reads[:1]
        latents = "u32[7,10,32768,1,384]"
        passes = ("custom-call(", "parameter(", "get-tuple-element(",
                  " tuple(", "while(", "bitcast(")
        touched = [ln for ln in text.splitlines()
                   if ln.lstrip().startswith(("%", "ROOT")) and " = " in ln
                   and latents in ln and not any(p in ln for p in passes)]
        assert not touched, touched[:3]


def test_held_experts_take_rows_of_6144(one_chip):
    """``experts_swiglu`` at GLM-5.2's shapes: rows 6144 wide into 16 held
    experts of 2048, the step's 80 pairs and a chunk's 4096."""
    from deepspeed_tpu.ops.moe_matmul import experts_swiglu

    bf, i32 = jnp.bfloat16, jnp.int32
    bank = ((16, 6144, 2048), bf)
    for tokens in (GLM["slots"], GLM["chunk"]):
        rows = -(-(tokens * 8 + 16 * 15) // 16) * 16
        _compile(lambda xs, wg, wi, wo, be, used: experts_swiglu(
            xs, wg, wi, wo, be, used[0], bm=16, interpret=False), one_chip,
            ((rows, 6144), bf), bank, bank, ((16, 2048, 6144), bf),
            ((rows // 16,), i32), ((1,), i32))


# sha256 of the lowered text with every kernel's serialized body taken out
# (a body carries its source's path and lines), taken at the parent of PR 51
# (commit 2545243) by this very function. PR 63 changed the step's
# ``mla_decode_attention`` call (one program a slot, the cache in HBM, two
# buffers and a semaphore each): grid, scratch and memory spaces are all
# inside the body this text leaves out, so "slot step" stands to the digit
KANANA_TEXT = {"slot step": "c8fe8b56f27a7bb2", "chunk": "81451999b8e18582"}


@pytest.mark.parametrize("program", ["slot step", "chunk"])
def test_the_latent_kind_s_programs_are_the_parent_s(one_chip, monkeypatch,
                                                     program):
    """Kanana's step and chunk, which share ``mla.project``,
    ``attend_expanded``, ``latent_append`` and the sorted expert rows with
    the new kind, lower to the text they lowered to before it."""
    import hashlib

    from deepspeed_tpu.inference.decode import forward_with_cache, init_cache
    from deepspeed_tpu.models import deepseek_v3

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = deepseek_v3("kanana-2-30b-a3b", n_layer=7, dtype=jnp.bfloat16)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda key: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(key)),
        jax.random.PRNGKey(0)))
    if program == "slot step":
        state = on_chip(jax.eval_shape(
            lambda: init_slots(cfg, 48, 8192, jnp.bfloat16)))
        lowered = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state)
    else:
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, 8192, jnp.bfloat16)))
        lowered = jax.jit(
            lambda p, c, ids, start: forward_with_cache(
                model, p, ids, c._replace(length=start), with_stats=True,
                with_routing=True)[1:], donate_argnums=(1,)).lower(
            params, cache,
            jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", lowered.as_text())
    assert text.count("BODY") == (6 if program == "slot step" else 2)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == KANANA_TEXT[program]


# --- delta-rule mixers beside selected latent attention (GLM-5.3-Flash) --
GLM53 = dict(slots=160, max_len=8192, chunk=512)


def _served_share(one_chip, fam, name):
    """(cfg, model, abstract served params) of the share that
    ``benchmark/configs/<name>.json`` states, at the published widths."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    model = build_model(cfg)
    keep = set(model.fp32_param_names())

    def served(path, a):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype if name in keep else jnp.bfloat16,
            sharding=one_chip)

    return cfg, model, jax.tree_util.tree_map_with_path(
        served, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


@pytest.fixture(scope="module")
def glm53(one_chip):
    """GLM-5.3-Flash's share (layers K A K K K, 36 of 288 experts): built
    once for both programs."""
    from benchmark.models import glm5_next as fam

    return _served_share(one_chip, fam, "glm-5.3-flash-l5-e36")


@pytest.mark.parametrize("program", ["slot step", "final chunk"])
def test_linear_sparse_trunk_fits_and_moves_its_state_in_place(
        one_chip, monkeypatch, glm53, program, capsys):
    """GLM-5.3-Flash's share at the cell's 160 slots x 8192, chunks of 512:
    the five buffers enter donated and leave aliased; every program's live
    set beside what else stands on the chip (the slots' state beside a
    chunk, the batch-1 prefill cache beside the step) stays under 13.5 GiB
    of the chip's 15.75; the step holds two calls of the state step (a run of
    one layer, a scan of three), one each of the pooled keys' append, the
    score and the selected read — handed the selection's mask beside its
    groups of 4, the dense side in the program — and NO other operation
    touches the delta-rule state or the latents' buffer; a final chunk holds one call of the chunk's attention
    kernel and carries no (64, 512, 8192) score array; its KDA layers scan
    in ``kda_chunk_scan``, one call a run of layers (two), and the XLA
    scan's ``(1, 8, 64, 64, 64)`` float32 inverse is gone from its text."""
    import time

    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g = GLM53
    cfg, model, params = glm53

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    # a row is the latent alone (no rope part, no padding), a pooled key a
    # group of 4; the state a slot as ISSUE 55 counts it
    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 1024 + 64
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 17367808 \
        == 4 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2) + 3 * 128 * 2
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert sum(a.size for a in jax.tree.leaves(params)) == 4718150030 \
        and abs(weights / 2 ** 30 - 8.80) < 0.01
    a_slot = g["max_len"] * 1088 + 17367808
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(lambda: init_slots(
            cfg, g["slots"], g["max_len"], jnp.bfloat16)))
        assert state.cache.kda.shape == (4, 160, 64, 128, 128) \
            and state.cache.c.shape == (1, 160, 8192, 1, 256) \
            and state.cache.ik.shape == (1, 160, 128, 2048)
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        held, beside = g["slots"] * a_slot, a_slot
    else:
        ids = jax.ShapeDtypeStruct((1, g["chunk"]), jnp.int32,
                                   sharding=one_chip)
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, g["max_len"], jnp.bfloat16)))
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=True, last_index=last, with_stats=True,
                with_routing=True),
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        held, beside = a_slot, g["slots"] * a_slot
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\n[linear sparse {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB; with "
              f"what stands beside it {(live + beside) / 2 ** 30:.2f} GiB]")
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert live + beside < 13.5 * 2 ** 30, (live + beside) / 2 ** 30
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "kda_state_step", "sparse_mla_decode_attention",
        "sparse_mla_chunk_attention", "dsa_index_score", "mla_cache_append",
        "moe_experts_up", "kda_chunk_scan")}
    step = program == "slot step"
    assert count == {
        "kda_state_step": 2 if step else 0,
        "kda_chunk_scan": 0 if step else 2,
        "sparse_mla_decode_attention": 1 if step else 0,
        "sparse_mla_chunk_attention": 0 if step else 1,
        "dsa_index_score": 1 if step else 0,
        "mla_cache_append": 1 if step else 0, "moe_experts_up": 2}, count
    assert not re.search(r"f32\[(1,)?64,512,8192\]", text)
    assert "f32[1,8,64,64,64]" not in text      # the scan's inverse, in HBM
    if step:
        reads = [ln for ln in calls
                 if "/sparse_mla_decode_attention/pallas_call" in ln]
        assert all("f32[160,1,8192]" in ln for ln in reads), reads[:1]
        passes = ("custom-call(", "parameter(", "get-tuple-element(",
                  " tuple(", "while(", "bitcast(")
        for buf in ("f32[4,160,64,128,128]", "u32[1,160,8192,1,256]"):
            touched = [ln for ln in text.splitlines()
                       if ln.lstrip().startswith(("%", "ROOT"))
                       and " = " in ln and buf in ln
                       and not any(p in ln for p in passes)]
            assert not touched, touched[:3]


@pytest.mark.parametrize("B,S,D,K,run,block", [
    (2, 131072, 576, 2048, 1, 1024), (10, 32768, 576, 2048, 1, 2048),
    (160, 8192, 512, 2052, 4, 512)],
    ids=["two slots of GLM-5.2's 131 072", "GLM-5.2's cell, blocks of 2048",
         "GLM-5.3-Flash's cell, blocks of 512"])
def test_the_selected_read_holds_both_fetches(one_chip, monkeypatch, B, S, D,
                                              K, run, block):
    """``sparse_mla_decode_attention`` with the selection's mask compiles
    for a described v5e — the gathered fetch and the dense walk in one
    program, chosen a slot from the prefetched scalars, so a long cache's
    short slots read dense too — at the published widths (the rope part
    beside the rank in a row of 384 words, or a bare row of 256), the
    selection's indices prefetched or a block a slot, the cache aliased."""
    from deepspeed_tpu.ops import sparse_mla_attention as sparse

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    words = sparse.row_layout(D, jnp.bfloat16)[0]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def read(q, cache, new, idx, length, n, mask, layer):
        return sparse.sparse_mla_decode_attention(
            q, cache, new, idx, length, layer=layer, rank=512,
            scale=D ** -0.5, n=n, run=run, mask=mask, block=block)

    compiled = jax.jit(read, donate_argnums=(1,)).lower(
        sds((B, 64, D), jnp.bfloat16), sds((1, B, S, 1, words), jnp.uint32),
        sds((B, D), jnp.bfloat16), sds((B, K), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, 1, S), jnp.float32), sds((), jnp.int32)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= B * S * words * 4
    text = compiled.as_text()
    assert text.count("/sparse_mla_decode_attention/pallas_call") == 1
    # the mask goes to the kernel as it came: no copy re-tiles it
    assert not re.search(rf" copy\(.*f32\[{B},1,{S}\]", text)


SOLAR = {"slots": 24, "max_len": 65536, "chunk": 512}


@pytest.fixture(scope="module")
def solar(one_chip):
    """Solar-Open2's share (layers G K K K, 40 of 320 experts): built once
    for both programs."""
    from benchmark.models import solar_open2 as fam

    return _served_share(one_chip, fam, "solar-open2-250b-l4-e40")


@pytest.mark.parametrize("program", ["slot step", "final chunk", "chunk"])
def test_delta_gqa_trunk_fits_and_moves_its_state_in_place(
        one_chip, monkeypatch, solar, program, capsys):
    """Solar-Open2's share at the cell's 24 slots x 65 536, chunks of 512:
    the four buffers enter donated and leave aliased; every program's live
    set beside what else stands on the chip (the slots' state beside a
    chunk, the batch-1 prefill cache beside the step) stays under 15.0 GiB
    of the chip's 15.75; the step holds one call of the state step (a scan
    of three KDA layers) and one of ``nope_gqa_decode_attention``, and NO
    other operation touches the delta-rule state or the K/V planes; a
    chunk, final or not, attends in ONE call of ``nope_gqa_chunk_attention``
    (Mosaic takes the kernel at the cell's shape) and carries neither a
    block's float32 scores ``(1, 8, 8, 512, 512)`` nor a (64, 512, 65 536)
    score array, and its three KDA layers scan in ONE call of
    ``kda_chunk_scan`` (a scan of three layers) with the XLA scan's ``(1, 8,
    64, 64, 64)`` float32 inverse gone from its text."""
    import time

    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g = SOLAR
    cfg, model, params = solar

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 4096
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 13025280 \
        == 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert sum(a.size for a in jax.tree.leaves(params)) == 3308353344 \
        and abs(weights / 2 ** 30 - 6.17) < 0.01
    a_slot = g["max_len"] * 4096 + 13025280
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(lambda: init_slots(
            cfg, g["slots"], g["max_len"], jnp.bfloat16)))
        assert state.cache.kda.shape == (3, 24, 64, 128, 128) \
            and state.cache.k.shape == (1, 24, 8, 128, 65536) \
            and state.cache.conv.shape == (3, 24, 3, 24576)
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        held, beside = g["slots"] * a_slot, a_slot
    else:
        ids = jax.ShapeDtypeStruct((1, g["chunk"]), jnp.int32,
                                   sharding=one_chip)
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, g["max_len"], jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True),
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        held, beside = a_slot, g["slots"] * a_slot
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\n[delta gqa {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB; with "
              f"what stands beside it {(live + beside) / 2 ** 30:.2f} GiB]")
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert live + beside < 15.0 * 2 ** 30, (live + beside) / 2 ** 30
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "kda_state_step", "nope_gqa_decode_attention", "moe_experts_up",
        "kda_chunk_scan")}
    step = program == "slot step"
    assert count == {"kda_state_step": 1 if step else 0,
                     "kda_chunk_scan": 0 if step else 1,
                     "nope_gqa_decode_attention": 1 if step else 0,
                     "moe_experts_up": 2}, count
    assert sum("/nope_gqa_chunk_attention/pallas_call" in ln
               for ln in calls) == (0 if step else 1)
    assert not re.search(r"f32\[(1,)?64,512,65536\]", text)
    assert "f32[1,8,8,512,512]" not in text
    assert "f32[1,8,64,64,64]" not in text      # the scan's inverse, in HBM
    if step:
        passes = ("custom-call(", "parameter(", "get-tuple-element(",
                  " tuple(", "while(", "bitcast(")
        for buf in ("f32[3,24,64,128,128]", "bf16[1,24,8,128,65536]"):
            touched = [ln for ln in text.splitlines()
                       if ln.lstrip().startswith(("%", "ROOT"))
                       and " = " in ln and buf in ln
                       and not any(p in ln for p in passes)]
            assert not touched, touched[:3]


LING = {"slots": 160, "max_len": 24576, "chunk": 512}


@pytest.fixture(scope="module")
def ling(one_chip):
    """Ling-3.0-flash's share (layers K K K K K A, group 0's 64 of 512
    experts): built once for the three programs."""
    from benchmark.models import bailing_hybrid as fam

    return _served_share(one_chip, fam, "ling-3.0-flash-l6-e64")


@pytest.mark.parametrize("program", ["slot step", "final chunk", "chunk"])
def test_delta_latent_trunk_fits_and_moves_its_state_in_place(
        one_chip, monkeypatch, ling, program, capsys):
    """Ling-3.0-flash's share at the cell's 160 slots x 24 576, chunks of
    512: the three buffers enter donated and leave aliased; every program's
    live set beside what else stands on the chip (the slots' state beside a
    chunk, the batch-1 prefill cache beside the step) stays under 14.0 GiB
    of the chip's 15.75; the step holds two calls of the state step (a scan
    of four KDA layers and the fifth, whose shared expert is clamped
    otherwise: a run of its own), one each of ``mla_cache_append`` and
    ``mla_decode_attention`` (Kanana's kernels at Kanana's shapes), and NO
    other operation touches the delta-rule state or the latents; a chunk,
    final or not, scans its KDA layers in two calls of ``kda_chunk_scan``
    (Mosaic takes the kernel at 32 heads of 128) and walks the live latents
    in XLA with no (32, 512, 24 576) score array; three runs of expert
    layers, each its own clamps a constant of the program."""
    import time

    from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                                forward_with_cache,
                                                init_cache,
                                                state_bytes_per_slot)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    g = LING
    cfg, model, params = ling

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    assert cache_bytes_per_token(cfg, jnp.bfloat16) == 1152
    assert state_bytes_per_slot(cfg, jnp.bfloat16) == 10854400 \
        == 5 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert sum(a.size for a in jax.tree.leaves(params)) == 2756028448 \
        and abs(weights / 2 ** 30 - 5.15) < 0.01
    a_slot = g["max_len"] * 1152 + 10854400
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    if program == "slot step":
        state = on_chip(jax.eval_shape(lambda: init_slots(
            cfg, g["slots"], g["max_len"], jnp.bfloat16)))
        assert state.cache.kda.shape == (5, 160, 32, 128, 128) \
            and state.cache.c.shape == (1, 160, 576, 24576) \
            and state.cache.conv.shape == (5, 160, 3, 12288)
        compiled = jax.jit(lambda p, c: decode_step(
            model, p, c, flash_decode=True, logit_guard=True, moe_stats=True,
            sampler=partial(sample_logits, temperature=1.0)),
            donate_argnums=(1,)).lower(params, state).compile()
        held, beside = g["slots"] * a_slot, a_slot
    else:
        ids = jax.ShapeDtypeStruct((1, g["chunk"]), jnp.int32,
                                   sharding=one_chip)
        cache = on_chip(jax.eval_shape(
            lambda: init_cache(cfg, 1, g["max_len"], jnp.bfloat16)))
        final = program == "final chunk"
        compiled = jax.jit(
            lambda p, c, ids, start, last: forward_with_cache(
                model, p, ids, c._replace(length=start), flash_decode=True,
                last_token_head=final, last_index=last if final else None,
                with_stats=True, with_routing=True),
            donate_argnums=(1,)).lower(params, cache, ids, i32,
                                       i32).compile()
        held, beside = a_slot, g["slots"] * a_slot
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    with capsys.disabled():
        print(f"\n[delta latent {program}: compiled for a described v5e in "
              f"{took:.1f} s; arguments {mem.argument_size_in_bytes / 1e9:.3f}"
              f" GB, aliased {mem.alias_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB; with "
              f"what stands beside it {(live + beside) / 2 ** 30:.2f} GiB]")
    assert mem.alias_size_in_bytes >= held             # donated, in place
    assert live + beside < 14.0 * 2 ** 30, (live + beside) / 2 ** 30
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    count = {k: sum(f"/{k}/pallas_call" in ln for ln in calls) for k in (
        "kda_state_step", "kda_chunk_scan", "mla_cache_append",
        "mla_decode_attention", "moe_experts_up", "moe_experts_down")}
    step = program == "slot step"
    assert count == {"kda_state_step": 2 if step else 0,
                     "kda_chunk_scan": 0 if step else 2,
                     "mla_cache_append": 1 if step else 0,
                     "mla_decode_attention": 1 if step else 0,
                     "moe_experts_up": 3, "moe_experts_down": 3}, count
    assert not re.search(r"f32\[(1,)?32,512,24576\]", text)
    assert "f32[1,8,32,64,64]" not in text      # the XLA scan's inverse
    if step:
        passes = ("custom-call(", "parameter(", "get-tuple-element(",
                  " tuple(", "while(", "bitcast(")
        for buf in ("f32[5,160,32,128,128]", "bf16[1,160,576,24576]"):
            touched = [ln for ln in text.splitlines()
                       if ln.lstrip().startswith(("%", "ROOT"))
                       and " = " in ln and buf in ln
                       and not any(p in ln for p in passes)]
            assert not touched, touched[:3]
