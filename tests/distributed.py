"""World-size-parametrized distributed test harness.

Analog of the reference's ``DistributedTest`` + ``@pytest.mark.world_size``
machinery (``tests/unit/common.py:102-233,361-372``): a test body runs at
SEVERAL process counts, each incarnation as real OS processes that
rendezvous through JAX's coordination service over loopback — the
single-node multi-process simulation SURVEY §4 calls the core trick.

Usage::

    from tests.distributed import distributed_test

    @pytest.mark.slow
    @distributed_test(world_sizes=[1, 2])
    def test_allreduce_world(tmp_path):   # pytest sees ONLY tmp_path;
        # the BODY source is shipped to each worker, where the harness
        # injects ``world_size`` and ``rank`` as globals:
        import jax
        total = jax.jit(lambda v: v * len(jax.devices()))(jax.numpy.ones(()))
        assert float(total) == len(jax.devices())

The decorated function's BODY is extracted by source (like the reference
pickling the test fn into forkserver workers) and executed in each worker
process after ``ds.init_distributed()``. Any worker assertion fails the
whole incarnation (the launcher's group-kill semantics); each world size is
a separate sub-run, and the wrapper returns {world_size: stdout} so callers
can assert cross-world properties.
"""

from __future__ import annotations

import ast
import inspect
import os
import socket
import subprocess
import sys
import textwrap
from functools import wraps

_DEVICES_PER_PROC = 2

_PRELUDE = """
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu as ds
ds.init_distributed()
world_size = jax.process_count()
rank = jax.process_index()
assert world_size == {world}, (world_size, {world})
"""

_EPILOGUE = """
print(f"DIST_BODY_OK rank={rank}", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _body_source(fn) -> str:
    """The function's body, dedented (drops the def/signature, however many
    lines it spans, and decorators) — via ast so multi-line signatures
    can't leak fragments into the worker script."""
    src = textwrap.dedent(inspect.getsource(fn))
    fdef = ast.parse(src).body[0]
    lines = src.splitlines()
    start = fdef.body[0].lineno - 1
    return textwrap.dedent("\n".join(lines[start:]))


def run_at_world_size(body_src: str, world: int, tmp_dir: str,
                      timeout: float = 420) -> str:
    """One incarnation: launch ``world`` processes over loopback, each with
    its own virtual CPU devices, all executing the body. Returns stdout."""
    script = os.path.join(tmp_dir, f"dist_body_w{world}.py")
    with open(script, "w") as f:
        f.write(_PRELUDE.format(world=world) + body_src + _EPILOGUE)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={_DEVICES_PER_PROC}",
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    })
    p = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--nproc", str(world), "--master_port", str(_free_port()), script],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, (
        f"world_size={world} failed rc={p.returncode}\n"
        f"stdout: {p.stdout[-2000:]}\nstderr: {p.stderr[-2000:]}")
    assert p.stdout.count("DIST_BODY_OK") == world, (world, p.stdout)
    return p.stdout


def distributed_test(world_sizes=(1, 2)):
    """Decorator: run the body at every world size (reference
    ``@pytest.mark.world_size`` + DistributedTest pool)."""
    def deco(fn):
        body = _body_source(fn)

        @wraps(fn)
        def wrapper(tmp_path):
            return {world: run_at_world_size(body, world, str(tmp_path))
                    for world in world_sizes}

        return wrapper

    return deco
