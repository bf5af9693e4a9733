"""The rules that keep a CPU run from passing for a chip run, and the
sharding rules the first four-chip run forced (PR 22)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------ chip_smoke.py
def _chip_smoke(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    return subprocess.run([sys.executable, os.path.join(_ROOT,
                                                        "chip_smoke.py"),
                           *args], env=env, cwd=_ROOT, timeout=timeout,
                          capture_output=True, text=True)


def test_chip_smoke_fails_without_a_tpu():
    """As the driver runs it, off the chip: non-zero, says why, and never
    prints a result."""
    p = _chip_smoke()
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_chip_smoke_four_chip_rehearsal_never_says_ok():
    """The tiny rehearsal drives the four-chip phases (sharded train
    steps against one device, TP generate) on the virtual CPU devices and
    exits 0 — with a last line that is not the contract's."""
    p = _chip_smoke("--rehearse", "--four-chips")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in p.stdout
    assert "four/data2-model2: first-step loss" in p.stdout
    assert "float32 greedy tokens exact" in p.stdout


# ------------------------------------------------------------ compile cache
def test_compile_cache_env_set_means_no_code_override(monkeypatch, tmp_path):
    from deepspeed_tpu.platform import compile_cache as cc

    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(cc.ENV_VAR, placed)
    before = jax.config.jax_compilation_cache_dir
    assert cc.configure_compile_cache() == placed
    assert cc.configure_compile_cache("/elsewhere") == placed
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_is_a_fixed_path_under_the_checkout(monkeypatch):
    from deepspeed_tpu.platform import compile_cache as cc

    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.configure_compile_cache() == os.path.join(_ROOT,
                                                            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            _ROOT, ".jax_cache")
        assert cc.configure_compile_cache() == cc.DEFAULT_DIR   # same again
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -------------------------------------------------------------------- peaks
class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 hyper"),
                                           ("cpu", "cpu"), ("gpu", "H100")])
def test_unknown_device_has_no_peak(platform, kind, monkeypatch):
    from deepspeed_tpu.utils import timer

    for var in ("DSTPU_PEAK_FLOPS", "DSTPU_PEAK_HBM_BW", "DSTPU_PEAK_ICI_BW"):
        monkeypatch.delenv(var, raising=False)
    for fn in (timer.peak_flops_for, timer.peak_hbm_bw_for,
               timer.peak_ici_bw_for):
        with pytest.raises(ValueError, match="refusing to guess"):
            fn(_Dev(platform, kind))


def test_v5e_peaks_are_the_published_ones():
    from deepspeed_tpu.utils import timer

    v5e = _Dev("tpu", "TPU v5 lite")        # as the chip reports itself
    assert timer.peak_flops_for(v5e) == 197e12
    assert timer.peak_hbm_bw_for(v5e) == 819e9
    assert timer.peak_ici_bw_for(v5e) == 200e9
    assert all("default" not in t for tab in (
        timer.PEAK_FLOPS_BY_PLATFORM, timer.PEAK_HBM_BW_BY_PLATFORM,
        timer.PEAK_ICI_BW_BY_PLATFORM) for t in tab.values())


# ----------------------------------------------------------------- sharding
def test_fit_spec_replicates_what_the_mesh_does_not_divide(devices):
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh, fit_spec

    mesh = build_mesh(MeshSpec(data=2, model=4))
    assert fit_spec(P("model", None), (50257, 1280), mesh) == P(None, None)
    assert fit_spec(P("model", None), (50304, 1280), mesh) == P("model", None)
    assert fit_spec(P(None, "model", None), (36, 10, 1280), mesh) \
        == P(None, None, None)
    assert fit_spec(P(("data", "model"), None), (16, 3), mesh) \
        == P(("data", "model"), None)
    assert fit_spec(P(("data", "model"), None), (12, 3), mesh) == P(None, None)
    assert fit_spec(None, (3,), mesh) == P()


def test_build_mesh_raises_on_a_mesh_the_devices_cannot_fill(devices):
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    with pytest.raises(ValueError, match="requires 6 devices"):
        build_mesh(MeshSpec(data=3, model=2))


@pytest.mark.parametrize("quantize", [False, True], ids=["dense", "int8"])
def test_tp_inference_with_a_vocab_and_groups_tp_does_not_divide(devices,
                                                                 quantize):
    """GPT-2's shape problem in small: a vocabulary of 509 and (int8) 10
    scale groups per row-sharded weight under tensor_parallel=4. Both used
    to fail at engine init with an uneven NamedSharding; greedy tokens now
    equal the one-device engine's."""
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, gpt2

    cfg = gpt2("125m", n_layer=2, d_model=1280, n_head=20, vocab_size=509,
               max_seq=64, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = {"dtype": "float32"}
    if quantize:
        kw.update(quantize=True, quant_bits=8)
    prompt = np.arange(1, 13, dtype=np.int32)[None]
    want = np.asarray(ds.init_inference(model, params, kw).generate(
        prompt, 6, greedy=True))
    eng = ds.init_inference(model, params, {**kw, "tensor_parallel": 4})
    got = np.asarray(eng.generate(prompt, 6, greedy=True))
    np.testing.assert_array_equal(got, want)
