"""The contract every cache kind keeps (``inference/kinds``; docs/SERVING.md,
"Cache kinds"), over the tuple of kinds with each one's ``tiny`` preset: what
it declares of its buffers is what it allocates and what the byte figures
count; what it refuses is what ``ServingEngine`` refuses, in the kind's
words, and nothing else; the trainer refuses the kinds that say why; and
exactly one kind matches a config."""

import math

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.decode import (cache_bytes_per_token,
                                            cache_layout, init_cache,
                                            state_bytes_per_slot)
from deepspeed_tpu.inference.kinds import (CCA, FEATURES, KINDS, DeltaGQA,
                                           DeltaLatent, Dense, Hybrid, Latent,
                                           LinearSparse, PagedKVCache,
                                           ParallelHybrid, SparseLatent,
                                           Windowed, kind_of)
from deepspeed_tpu.models import (bailing_hybrid, deepseek_v3, falcon_h1,
                                  glm5_next,
                                  glm_moe_dsa, mimo_v2_flash, nemotron_h,
                                  ouro, presets, solar_open2, tiny_test,
                                  why_not_trained, zaya)
from deepspeed_tpu.observability.capacity import kv_cache_bytes
from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
from deepspeed_tpu.serving.pages import init_paged_slots
from deepspeed_tpu.serving.slots import init_slots

F32 = jnp.float32
PAGE, PAGES = 16, 24
# every kind of the tuple with its tiny preset (the plain K/V kind four
# times: a looped trunk is that kind with a plane a pass, a page pool its
# other layout, and where K beside V fill whole lane tiles — one head of 64
# here — it keeps the deferred tail, which the narrower heads' caches leave
# None: a field a cache does not keep is no buffer of it)
CASES = {
    "dense": (Dense, lambda: tiny_test(max_seq=256, dtype=F32)),
    "dense-tail": (Dense, lambda: tiny_test(max_seq=256, dtype=F32,
                                            n_head=1)),
    "looped": (Dense, lambda: ouro("tiny", dtype=F32)),
    "paged": (Dense, lambda: tiny_test(max_seq=256, dtype=F32)),
    "latent": (Latent, lambda: deepseek_v3("tiny", dtype=F32)),
    "hybrid": (Hybrid, lambda: nemotron_h("tiny", dtype=F32)),
    "windowed": (Windowed, lambda: mimo_v2_flash("tiny", dtype=F32)),
    "cca": (CCA, lambda: zaya("tiny", dtype=F32)),
    "parallel": (ParallelHybrid, lambda: falcon_h1("tiny", dtype=F32)),
    "sparse-latent": (SparseLatent, lambda: glm_moe_dsa(
        "tiny", dtype=F32, moe_experts_held=2)),
    "linear-sparse": (LinearSparse, lambda: glm5_next(
        "tiny", dtype=F32, moe_experts_held=2)),
    "delta-gqa": (DeltaGQA, lambda: solar_open2(
        "tiny", dtype=F32, moe_experts_held=2)),
    "delta-latent": (DeltaLatent, lambda: bailing_hybrid(
        "tiny", dtype=F32, moe_experts_held=4)),
}
CONTIGUOUS = [name for name in CASES if name != "paged"]
SLOTS, MAX_LEN = 2, 128


def case(name):
    cls, make = CASES[name]
    cfg = make()
    return cls, cfg, kind_of(cfg)


def test_the_cases_cover_the_tuple_of_kinds():
    assert {cls for cls, _ in CASES.values()} == set(KINDS)


# ------------------------------------------------- (a) what it allocates
@pytest.mark.parametrize("name", CASES)
def test_a_cache_has_the_buffers_its_kind_declares(name):
    cls, cfg, kind = case(name)
    assert type(kind) is cls
    if name == "paged":
        cache = jax.eval_shape(lambda: init_paged_slots(
            cfg, SLOTS, MAX_LEN, PAGE, PAGES, F32)).cache
        declared = kind.buffers(SLOTS, MAX_LEN, F32, PAGE, PAGES)
        assert type(cache) is PagedKVCache
    else:
        cache = jax.eval_shape(
            lambda: kind.empty(SLOTS, MAX_LEN, F32, (SLOTS,)))
        declared = {**kind.buffers(SLOTS, MAX_LEN, F32),
                    **kind.state(SLOTS, F32)}
        assert set(declared) | {"length"} == _kept(cache)
        assert ("tail" in declared) == (name in ("dense-tail", "cca"))
        assert tuple(kind.buffers(SLOTS, MAX_LEN, F32)) == kind.planes
        for make in (lambda: init_cache(cfg, SLOTS, MAX_LEN, F32, (SLOTS,)),
                     lambda: init_slots(cfg, SLOTS, MAX_LEN, F32).cache):
            assert jax.eval_shape(make) == cache
        assert type(cache) is kind.cache
    for field, (shape, dtype) in declared.items():
        buf = getattr(cache, field)
        assert (buf.shape, buf.dtype) == (shape, jnp.dtype(dtype)), field
    assert cache.length.shape == (SLOTS,)


def _kept(cache) -> set:
    return {f for f, b in cache._asdict().items() if b is not None}


# ------------------------------------------------ (b) what the bytes count
def _nbytes(bufs):
    return sum(math.prod(b.shape) * b.dtype.itemsize for b in bufs)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", CONTIGUOUS)
def test_the_byte_figures_are_the_arrays_own(name, dtype):
    _, cfg, kind = case(name)
    cache = jax.eval_shape(lambda: init_cache(cfg, SLOTS, MAX_LEN, dtype))
    planes = [getattr(cache, f) for f in kind.planes]
    state = [getattr(cache, f) for f in kind.state(SLOTS, dtype)]
    assert len(planes) + len(state) + 1 == len(_kept(cache))
    assert cache_bytes_per_token(cfg, dtype) * SLOTS * MAX_LEN \
        == _nbytes(planes)
    assert state_bytes_per_slot(cfg, dtype) * SLOTS == _nbytes(state)
    assert bool(state) == bool(kind.state_bytes_per_slot(dtype))


@pytest.mark.parametrize("name", CONTIGUOUS)
def test_the_capacity_ledger_sums_the_kind_s_buffers(name):
    """``kv_cache_bytes``: every buffer at its own width (a model's values
    may be narrower than its keys) and what a slot holds whatever its
    length."""
    _, cfg, kind = case(name)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, SLOTS, MAX_LEN, jnp.bfloat16))
    kv = kv_cache_bytes(cfg, SLOTS, MAX_LEN, jnp.bfloat16)
    held = _nbytes(b for f, b in cache._asdict().items()
                   if f != "length" and b is not None)
    assert kv["total_bytes"] == held == SLOTS * kv["per_slot_bytes"]
    assert kv["state_bytes"] == SLOTS * kind.state_bytes_per_slot(jnp.bfloat16)
    assert kv["per_token_bytes"] == kind.bytes_per_token(jnp.bfloat16)
    assert kv["shape"] == list(cache[0].shape)


# --------------------------------------------------- (c) what it refuses
def one_device_mesh():
    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


# how each feature is turned on, and the features that come on with it
FEATURE = {
    "paged": ({"page_size": PAGE}, {}, {"paged"}),
    "kv_quant": ({"page_size": PAGE, "kv_quant_bits": 8}, {},
                 {"paged", "kv_quant"}),
    "speculation": ({"greedy": True, "speculation": {"enabled": True}}, {},
                    {"speculation"}),
    "host_kv": ({"page_size": PAGE, "host_pool_bytes": 1 << 20}, {},
                {"paged", "host_kv"}),
    "quantize": ({}, {"quantize": True}, {"quantize"}),
    "mesh": ({}, {}, {"mesh"}),
}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            _, cfg, kind = case(name)
            model = ds.models.build_model(cfg)
            built[name] = (kind_of(cfg), model,
                           model.init(jax.random.PRNGKey(0)))
        return built[name]

    return get


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("name", CONTIGUOUS)
def test_serving_refuses_what_the_kind_refuses_and_nothing_else(
        models, name, feature):
    kind, model, params = models(name)
    serving, conf, on = FEATURE[feature]
    if feature == "mesh" and len(jax.devices()) < 2:
        pytest.skip("one device")
    eng = ds.init_inference(
        model, params, {"dtype": "float32", "flash_decode": False, **conf},
        **({} if feature == "mesh" else {"mesh": one_device_mesh()}))
    serving = {"slots": 2, "max_len": 64, "prefill_chunk": 16, **serving}
    refused = kind.refusal(on)
    if feature in kind.refuses:
        assert kind.what and kind.refuses[feature] in refused
        assert refused.startswith(kind.what + " ")
    if refused is None:
        ds.ServingEngine(eng, serving).close()
        return
    with pytest.raises(ValueError) as err:
        ds.ServingEngine(eng, serving)
    assert str(err.value) == refused


def test_the_sorted_expert_rows_refuse_beside_a_plain_cache_too():
    """A K/V trunk with sigmoid-routed experts: the expert layer's list,
    under the sentence it shares with the latent cache."""
    from deepspeed_tpu.models import moe

    cfg = deepseek_v3("tiny", dtype=F32, attention="mha", kv_lora_rank=0,
                      qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0)
    kind = kind_of(cfg)
    assert type(kind) is Dense and not kind.refuses
    what, sep, refuses = moe.SERVED
    assert kind.refusal({"paged", "mesh"}) == \
        what + " " + refuses["paged"] + sep + refuses["mesh"]
    assert kind.refusal({"host_kv"}) is None
    assert (Latent.what, Latent.sep, Latent.refuses) == moe.SERVED


# ------------------------------------------------ (d) what is not trained
@pytest.mark.parametrize("name", CONTIGUOUS)
def test_the_trainer_refuses_the_kinds_that_say_why(models, name):
    kind, model, _ = models(name)
    conf = {"train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    why = why_not_trained(kind.cfg)
    assert (why is None) == (name in ("dense", "dense-tail", "latent"))
    if why is None:
        if name == "dense":
            # (a trunk of several segments does not pass the trainer's
            # shape plan yet: a TypeError of its own, no refusal)
            ds.initialize(conf, model)
        return
    assert "served, not trained" in why
    with pytest.raises(ValueError) as err:
        ds.initialize(conf, model)
    assert str(err.value) == why


# ------------------------------------------------------ (e) one kind each
PRESETS = [(fn, size) for fn, sizes in (
    (presets.gpt2, ("125m", "774m")), (presets.llama2, ("7b",)),
    (presets.mixtral, ("8x7b",)), (presets.deepseek_v3, ("tiny",
                                                         "kanana-2-30b-a3b")),
    (presets.nemotron_h, ("tiny", "3-super-120b-a12b")),
    (presets.ouro, ("tiny", "2.6b")), (presets.bert, ("base",)),
    (presets.opt, ("125m",)), (presets.bloom, ("560m",)),
    (presets.mimo_v2_flash, ("tiny", "flash")), (presets.zaya, ("tiny",)),
    (presets.falcon_h1, ("tiny", "34b")),
    (presets.glm_moe_dsa, ("tiny", "5.2")),
    (presets.glm5_next, ("tiny", "5.3-flash")),
    (presets.solar_open2, ("tiny", "250b")),
    (presets.bailing_hybrid, ("tiny", "3.0-flash")),
    (presets.tiny_test, (None,))) for size in sizes]


@pytest.mark.parametrize("fn,size", PRESETS,
                         ids=[f"{fn.__name__}-{size}" for fn, size in PRESETS])
def test_exactly_one_kind_matches_a_preset(fn, size):
    cfg = fn() if size is None else fn(size)
    assert sum(kind.matches(cfg) for kind in KINDS) == 1
    kind = kind_of(cfg)
    if type(kind) is Dense and kind.loops == 1:
        assert not kind.contiguous_only
        assert len(cache_layout(cfg, SLOTS, MAX_LEN, page_size=PAGE,
                                pages=PAGES)[0]) == 5
    else:
        assert "contiguous only" in kind.contiguous_only
        with pytest.raises(NotImplementedError, match="contiguous only"):
            cache_layout(cfg, SLOTS, MAX_LEN, page_size=PAGE, pages=PAGES)
