"""The shipped examples must actually run (doc-rot tripwire) — smoke mode,
each in a clean subprocess on the virtual CPU mesh."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.slow
@pytest.mark.parametrize("example", ["pretrain_gpt2", "finetune_hf_import",
                                     "moe_pipeline_elastic", "rlhf_hybrid",
                                     "serve_inference", "longseq_sp",
                                     "evoformer_science",
                                     "billion_param_single_chip"])
def test_example_runs(example, tmp_path):
    if example == "finetune_hf_import":
        pytest.importorskip("torch")
        pytest.importorskip("transformers")
    env = dict(os.environ)
    env.update({
        "DSTPU_EXAMPLE_SMOKE": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": _ROOT,
    })
    p = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "examples", f"{example}.py")],
        env=env, cwd=str(tmp_path),   # ckpts/ and out/ land in tmp
        capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, (p.stdout[-1500:], p.stderr[-1500:])
