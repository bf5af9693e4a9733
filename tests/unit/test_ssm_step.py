"""``ops/ssm_step.py`` by itself: the Pallas one-token step of the Mamba-2
recurrence (interpreted here) against the plain form, ``models/ssm.py``
``state_step``, at shapes that put 1, 2 and 4 groups of heads in a program
and with slots that are not running anywhere in the batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.ssm import state_step
from deepspeed_tpu.ops.ssm_step import (_BLOCK_BYTES, groups_per_program,
                                        kernel_fits, ssm_state_step)

F32 = jnp.float32
B = 5
# (H, G, P, N) -> the groups a program takes (a group of the first four
# shapes: 512, 256, 64 and 16 KiB of float32 state)
SHAPES = {
    "one group a program": ((16, 1, 64, 128), 1),
    "two": ((16, 2, 64, 128), 2),
    "four": ((32, 4, 16, 128), 4),
    "an odd count of groups": ((12, 3, 8, 128), 3),
    "a group over the block": ((8, 2, 128, 1024), 1),
}
LENGTHS = {
    "all running": (3, 1, 7, 2, 9),
    "the first idle": (0, 1, 7, 2, 9),
    "the last idle": (3, 1, 7, 2, 0),
    "two neighbours idle": (3, 0, 0, 2, 9),
    "all idle": (0, 0, 0, 0, 0),
}


@pytest.mark.parametrize("lengths", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("shape,gb", list(SHAPES.values())[:4],
                         ids=list(SHAPES)[:4])
def test_the_kernel_equals_the_plain_step(shape, gb, lengths):
    H, G, P, N = shape
    assert kernel_fits(H, G, P, N) and groups_per_program(H, G, P, N) == gb
    keys = jax.random.split(jax.random.PRNGKey(H + G), 6)
    S = jax.random.normal(keys[0], (2, B, H, P, N), F32)    # layer 1 of two
    x = jax.random.normal(keys[1], (B, H, P), F32)
    dt = jax.nn.softplus(jax.random.normal(keys[2], (B, H), F32))
    A = -jnp.exp(jax.random.normal(keys[3], (H,), F32))
    Bv = jax.random.normal(keys[4], (B, G, N), F32)
    Cv = jax.random.normal(keys[5], (B, G, N), F32)
    n = jnp.asarray(lengths, jnp.int32)
    want_y, want_S = state_step(S[1], x, dt, A, Bv, Cv, n > 0)
    y, got = ssm_state_step(S, jnp.int32(1), x, dt, A, Bv, Cv, n,
                            interpret=True)
    live = np.asarray(n) > 0
    np.testing.assert_array_equal(got[0], S[0])             # the other layer
    np.testing.assert_array_equal(got[1][~live], S[1][~live])
    assert not np.asarray(y)[~live].any()
    np.testing.assert_allclose(got[1][live], want_S[live], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape,gb", [
    pytest.param((128, 8, 64, 128), 4, id="nemotron-3-super"),
    pytest.param((32, 2, 128, 256), 1, id="falcon-h1-34b"),
    # 1 MiB a group: two would fit and do not divide 3, three do not fit
    pytest.param((24, 3, 128, 256), 1, id="three groups, two would fit"),
    # 512 KiB a group: four would fit and do not divide 6
    pytest.param((96, 6, 64, 128), 3, id="six groups, four would fit"),
    *(pytest.param(*v, id=k) for k, v in SHAPES.items())])
def test_groups_per_program_from_the_shapes(shape, gb):
    """The largest divisor of G whose state fits the block; one group where
    one fills it (or passes it: the kernel does not split a group)."""
    H, G, P, N = shape
    assert groups_per_program(H, G, P, N) == gb
    one = (H // G) * P * N * 4
    assert G % gb == 0 and (gb == 1 or gb * one <= _BLOCK_BYTES)
    assert all(G % d or d * one > _BLOCK_BYTES for d in range(gb + 1, G + 1))
