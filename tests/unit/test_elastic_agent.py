"""Elastic agent e2e: membership change + checkpoint resume.

Reference: ``elasticity/elastic_agent.py:28`` (DSElasticAgent restarts worker
groups on membership change) + ``bin/ds_elastic``. Round-2 verdict item 6:
"train 2-proc → kill → relaunch 1-proc → loss continues".

The script trains under an elastic schema (engine derives micro/gas from the
live world size), checkpoints every step, and on the FIRST incarnation rank 1
kills itself after step 3 — after shrinking the advertised world to one
process. The agent must detect the failure, re-probe the world, relaunch at
world=1, and the job must resume from step 3 and finish. Assertions: agent
rc 0, both incarnations logged, the resumed incarnation starts past step 3,
and its first loss continues the dying incarnation's trajectory.
"""

import os
import re
import subprocess
import sys

import pytest

_SCRIPT = """
import os, pathlib, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset

ds.init_distributed()
restart = int(os.environ.get("DSTPU_ELASTIC_RESTART", "0"))
CKPT, NPROC_FILE = sys.argv[1], sys.argv[2]

engine = ds.initialize({
    "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
    "zero_optimization": {"stage": 1},
    "elasticity": {"enabled": True, "max_train_batch_size": 8,
                   "micro_batch_sizes": [1, 2, 4], "max_devices": 8},
    "seed": 7,
}, build_model(tiny_test()))
if (pathlib.Path(CKPT) / "latest").exists():
    engine.load_checkpoint(CKPT)

data = random_token_dataset(16, 16, 256, learnable=True)
local_bs = engine.train_batch_size // jax.process_count()
dl = DataLoader(data, local_batch_size=local_bs, shuffle=False)
batch = next(iter(dl))

TOTAL = 6
while engine.global_steps < TOTAL:
    m = engine.train_batch(dict(batch))
    engine.save_checkpoint(CKPT)
    print(f"ELASTIC restart={restart} step={engine.global_steps} "
          f"world={jax.process_count()} devices={len(jax.devices())} "
          f"loss={float(m['loss']):.4f}", flush=True)
    if restart == 0 and engine.global_steps == 3:
        if jax.process_index() == 0:
            with open(NPROC_FILE, "w") as f:
                f.write("1")     # membership change: next world is 1 process
        if jax.process_index() == 1:
            sys.exit(17)         # simulated worker death
print(f"ELASTIC_DONE restart={restart} steps={engine.global_steps}", flush=True)
"""


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_elastic_restart_resumes_at_new_world(tmp_path):
    script = tmp_path / "elastic_train.py"
    script.write_text(_SCRIPT)
    nproc_file = tmp_path / "nproc"
    nproc_file.write_text("2")
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
    })
    p = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.elasticity.agent",
         "--nproc_file", str(nproc_file), "--max_restarts", "3",
         "--restart_delay", "0.5", "--master_port", str(_free_port()),
         "--max_train_batch_size", "8", "--micro_batch_sizes", "1,2,4",
         str(script), str(ckpt), str(nproc_file)],
        # 900s: two full incarnations (compile x2) on a possibly-contended
        # single-core CI box — 600 flaked when the suite ran alongside
        # other jobs (passes standalone in ~360s)
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])

    # two incarnations, second at the shrunk world
    assert "incarnation 0: world=2" in p.stderr, p.stderr
    assert "incarnation 1: world=1" in p.stderr, p.stderr
    assert "membership change: world 2 -> 1" in p.stderr, p.stderr

    steps = [(int(m.group(1)), int(m.group(2)), int(m.group(3)),
              float(m.group(4)))
             for m in re.finditer(
                 r"ELASTIC restart=(\d+) step=(\d+) world=(\d+) "
                 r"devices=\d+ loss=([\d.]+)", p.stdout)]
    first = [s for s in steps if s[0] == 0]
    second = [s for s in steps if s[0] == 1]
    assert first and second, steps
    # incarnation 0 reached step 3 at world 2 (x2 ranks printing)
    assert max(s[1] for s in first) == 3 and first[0][2] == 2, first
    # incarnation 1 RESUMED (starts at step 4, not 1) at world 1
    assert min(s[1] for s in second) == 4 and second[0][2] == 1, second
    assert max(s[1] for s in second) == 6, second
    # loss continues: resumed first-step loss is below incarnation 0's start
    loss0_start = first[0][3]
    loss1_start = second[0][3]
    assert loss1_start < loss0_start, (loss0_start, loss1_start)
    assert "ELASTIC_DONE restart=1 steps=6" in p.stdout, p.stdout[-2000:]
