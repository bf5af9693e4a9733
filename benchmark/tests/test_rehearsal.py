"""The command itself, at tiny size on the CPU: it runs to its last line, and
that line is marked as a rehearsal, never ``correct`` as a chip result. And
without ``--rehearse`` a machine with no TPU gets exit 2 and no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(workload, *more, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "3000000001", "--seconds", "4", *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [(w["name"], w["chips"]) for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell, chips", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_to_its_last_line(cell, chips, trace):
    p = run(cell, "--trace", trace, "--rehearse", devices=chips)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal"]["passed"] is True, line["notes"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace == "0":     # a CPU capture has no device plane to reduce
        assert line["rehearsal"]["not_device_metrics"]


def test_no_tpu_no_result():
    p = run(CELLS[0][0], "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "Nothing was measured" in p.stderr


def test_the_rebuilt_sampler_reproduces_the_engine_s_draws():
    """``decision_margin`` excuses a served/solo difference only as a near-tie
    of logit + Gumbel noise; its rebuild of the key chain has to land on the
    token the engine really drew."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from benchmark.kinds._serving import decision_margin
    from benchmark.models import gpt2 as fam

    pub = {"n_layer": 2, "n_embd": 64, "n_head": 4, "vocab_size": 211,
           "n_positions": 64, "activation_function": "gelu_new",
           "layer_norm_epsilon": 1e-5, "tie_word_embeddings": True}
    cfg, model = fam.build(pub, "float32", flash_attention=False)
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32"})
    prompt = np.arange(7, dtype=np.int32)
    toks = np.asarray(eng.generate(prompt[None], 6, request_seeds=[1234]))[0]
    for pos in (0, 3, 5):
        assert decision_margin(eng, prompt, toks, pos, toks, 1234) == 0.0
