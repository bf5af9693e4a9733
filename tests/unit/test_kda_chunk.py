"""``kda_chunk_scan`` (``ops/kda_chunk.py``), interpreted here, against its
two witnesses: the chunkwise scan in plain ``jnp`` it replaces
(``kda.scan_chunked``) and the recurrence token by token
(``kda.state_step``) — a gate with a floor and with none (a channel that
forgets within a token, a gate that forgets nothing), beta up to 2, a state
handed in and handed on, a padded tail, every bucket the cells run; the
shape rule that decides who takes it and the counter of who does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.decode import forward_with_cache, init_cache
from deepspeed_tpu.inference.kinds import kind_of
from deepspeed_tpu.models import build_model, kda, solar_open2
from deepspeed_tpu.ops import kda_chunk

F32 = jnp.float32
# the recurrence token by token is the truth; the scan in plain ``jnp`` stands
# within 2e-5 of it (tests/unit/test_delta_gqa.py: its decays are exponentials
# of differences of running sums) and the kernel, whose decays are
# exponentials of sums, within a quarter of that: so the two chunkwise forms
# may differ by the scan's own distance (3e-5 where a channel runs to e^-5120 a
# block: an ulp of that running sum is 5e-4)
TOL, TOL_SCAN = 5e-6, 5e-5


def _inputs(B, T, H, D, gate, seed=0):
    """``gate``: "bounded" (GLM-5.3's: g in (-5, 0)), "unbounded" (no
    floor: one channel in four down to -60 a token, channel 0 at -80
    always: it forgets within a token), "none" (every g 0: nothing decays).
    beta in (0, 2); a state handed in."""
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, kk = (unit(jax.random.normal(next(k), (B, T, H, D))) for _ in "qk")
    v = jax.random.normal(next(k), (B, T, H, D))
    z = jax.random.normal(next(k), (B, T, H, D))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(next(k), (B, T, H)))
    if gate == "bounded":
        g = -5.0 * jax.nn.sigmoid(3.0 * z)
    elif gate == "none":
        g = jnp.zeros_like(z)
    else:
        g = -jnp.where(jnp.arange(D) % 4 == 3, 60.0 * jax.nn.sigmoid(3.0 * z),
                       jax.nn.softplus(3.0 * z - 4.0))
        g = g.at[..., 0].set(-80.0)
    S0 = jax.random.normal(next(k), (B, H, D, D))
    return q, kk, v, g, beta, S0


def _recurrence(q, k, v, g, beta, S0):
    live = jnp.ones((q.shape[0],), bool)

    def token(St, t):
        o, St = kda.state_step(St, *t, live)
        return St, o

    St, o = jax.lax.scan(token, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), St


def _padded(real, T, g, beta):
    """What ``mix_chunk`` hands the scan behind ``valid``: beta 0, g 0."""
    at = jnp.arange(T)[None, :, None] < real
    return jnp.where(at[..., None], g, 0.0), jnp.where(at, beta, 0.0)


# (what, B, T, real tokens, H, D, gate, heads a program)
CASES = [
    ("a block, bounded gate", 1, 64, 64, 2, 16, "bounded", 8),
    ("a block, no floor", 1, 64, 64, 3, 16, "unbounded", 8),
    ("a block, nothing decays", 1, 64, 64, 2, 16, "none", 8),
    ("four blocks, no floor, two rows", 2, 256, 256, 3, 16, "unbounded", 8),
    ("four blocks, bounded, a head a program", 2, 256, 256, 4, 16, "bounded",
     1),
    ("a chunk of 512, no floor", 1, 512, 512, 2, 16, "unbounded", 2),
    ("a padded tail: 510 of 512", 1, 512, 510, 2, 16, "unbounded", 8),
    ("a padded tail: 37 of 64, two rows", 2, 64, 37, 2, 16, "bounded", 8),
    ("two blocks, 70 real, bounded", 2, 128, 70, 3, 16, "bounded", 8),
    ("the published head, no floor", 1, 128, 128, 2, 128, "unbounded", 2),
    ("the published head, bounded, two rows", 2, 64, 64, 8, 128, "bounded",
     4),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernel_scans_what_the_scan_and_the_recurrence_do(case):
    """o and ``S_T`` against ``state_step`` token by token over the real
    tokens to 5e-6 — four times closer than the 2e-5 the scan in plain
    ``jnp`` is held to — and against ``scan_chunked`` to what that scan
    itself differs from the recurrence by; finite everywhere: decays down to
    e^-80 a token (e^-5120 a block), beta up to 2, a state handed in."""
    _, B, T, real, H, D, gate, heads = case
    q, k, v, g, beta, S0 = _inputs(B, T, H, D, gate)
    if gate == "unbounded":
        assert float(g.min()) <= -80 and float(beta.max()) > 1.9
    g_p, beta_p = _padded(real, T, g, beta)
    o, St = kda_chunk.kda_chunk_scan(q, k, v, g_p, beta_p, S0, heads=heads)
    with jax.default_matmul_precision("highest"):
        o_scan, S_scan = kda.scan_chunked(q, k, v, g_p, beta_p, S0)
        o_want, S_want = _recurrence(*(a[:, :real] for a in (q, k, v, g,
                                                             beta)), S0)
    assert o.shape == q.shape and St.shape == S0.shape
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(St).all())
    np.testing.assert_allclose(o, o_scan, atol=TOL_SCAN)
    np.testing.assert_allclose(St, S_scan, atol=TOL_SCAN)
    np.testing.assert_allclose(o[:, :real], o_want, atol=TOL)
    np.testing.assert_allclose(St, S_want, atol=TOL)


@pytest.mark.parametrize("gate", ["bounded", "unbounded"])
def test_two_calls_handing_the_state_over_are_one_call(gate):
    q, k, v, g, beta, S0 = _inputs(2, 256, 3, 16, gate, seed=1)
    whole, S_whole = kda_chunk.kda_chunk_scan(q, k, v, g, beta, S0)
    first, S = kda_chunk.kda_chunk_scan(
        *(a[:, :64] for a in (q, k, v, g, beta)), S0)
    rest, S = kda_chunk.kda_chunk_scan(
        *(a[:, 64:] for a in (q, k, v, g, beta)), S)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                               atol=TOL)
    np.testing.assert_allclose(S, S_whole, atol=TOL)


def test_every_decay_is_the_exponential_of_a_sum_that_is_not_positive():
    """``decay_sums``: the running sum, ``G_last - G`` and the six levels,
    each <= 0 for ``g <= 0`` and each the SUM it names to float32 rounding
    where the running sum has grown to thousands (a difference of running
    sums would be off by an ulp of 5000); a pair ``j < i`` stands under
    exactly one level's mask, where its row's and its column's entries add
    up to ``G_i - G_j``."""
    C = kda_chunk.CHUNK
    g = -np.random.default_rng(0).random((C, 8)).astype(np.float32)
    g[:, 0] = -80.0                                 # e^-5120 a block
    g[5, 1] = -3000.0
    G, Gend, levels = (np.asarray(x, np.float64) if not isinstance(x, list)
                       else [np.asarray(e, np.float64) for e in x]
                       for x in kda_chunk.decay_sums(jnp.asarray(g)))
    want = np.cumsum(g.astype(np.float64), axis=0)
    assert len(levels) == len(kda_chunk.LEVELS) == 6
    assert max(x.max() for x in [G, Gend] + levels) <= 0.0
    np.testing.assert_allclose(G, want, rtol=3e-7)
    np.testing.assert_allclose(Gend, want[-1] - want, rtol=3e-7, atol=1e-30)
    t = np.arange(C)
    seen = np.zeros((C, C), int)
    for s, E in zip(kda_chunk.LEVELS, levels):
        i, j = np.nonzero((t[:, None] > t[None, :])
                          & ((t[:, None] ^ t[None, :]) // s == 1))
        np.testing.assert_allclose(E[i] + E[j], want[i] - want[j], rtol=3e-7,
                                   atol=1e-30)
        seen[i, j] += 1
    assert np.array_equal(seen, np.tril(np.ones((C, C), int), -1))


def test_the_rule_reads_shapes_alone(monkeypatch):
    """Chunks of whole blocks of 64 where the kind's kernels run; a bucket
    of 8 to 32 keeps the scan (it is the faster there) and is no fallback;
    on the chip only channels of whole lane tiles."""
    cfg = solar_open2("tiny", dtype=F32)
    assert cfg.kda_head_dim == 16
    for T, took in ((64, True), (128, True), (256, True), (512, True),
                    (8, False), (16, False), (32, False), (96, False),
                    (1, False)):
        assert kda.chunk_kernel_ok(cfg, True, T) is took, T
        assert kda.chunk_kernel_ok(cfg, False, T) is False
        assert kda.chunk_scan_falls_back(cfg, False, T) is took
        assert kda.chunk_scan_falls_back(cfg, True, T) is False
    assert kda_chunk.kernel_fits(512, 128) and kda_chunk.kernel_fits(64, 16)
    assert not kda_chunk.kernel_fits(64, 12)
    with pytest.raises(ValueError, match="whole number of blocks"):
        kda_chunk.kda_chunk_scan(*_inputs(1, 32, 2, 16, "bounded"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda_chunk.kernel_fits(512, 128) and kda_chunk.kernel_fits(64, 256)
    assert not kda_chunk.kernel_fits(64, 16)
    assert not kda_chunk.kernel_fits(64, 192)
    assert kda.chunk_kernel_ok(cfg, True, 64) is False
    assert kda_chunk.heads_per_program(64) == 8
    assert kda_chunk.heads_per_program(6) == 6
    assert kda_chunk.heads_per_program(14) == 7
    assert kda_chunk.heads_per_program(64, 1) == 1


@pytest.mark.parametrize("what,T,max_len,flash,scans,walks", [
    ("the kernels", 64, 128, True, 0, 0),
    ("a bucket of 32: the scan by the rule, no fallback", 32, 128, True, 0,
     0),
    ("a bucket of 8", 8, 128, True, 0, 0),
    ("a cache the attention's kernel refuses", 64, 160, True, 1, 1),
    ("queries nothing tiles: the walk, the scan by the rule", 12, 128, True,
     0, 1),
    ("the kernels off", 64, 128, False, 0, 0),
])
def test_a_chunk_traced_onto_the_scan_is_counted(what, T, max_len, flash,
                                                 scans, walks):
    """``Serve/chunk_scan_fallback_builds``: one for every chunk program of
    whole blocks of 64 traced onto ``kda.scan_chunked`` while the kernels
    are on, beside the attention's own counter; the program's text and the
    kind's answer for the ``prefill_chunk`` span agree."""
    from deepspeed_tpu.observability.metrics import get_registry
    from deepspeed_tpu.serving.scheduler import ChunkPlan

    cfg = solar_open2("tiny", dtype=F32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scan = get_registry().counter("Serve/chunk_scan_fallback_builds")
    walk = get_registry().counter("Serve/chunk_attention_fallback_builds")
    before = scan.value, walk.value
    ids = jnp.zeros((1, T), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, ids, cache: forward_with_cache(
        model, p, ids, cache, flash_decode=flash))(
            params, ids, init_cache(cfg, 1, max_len, F32)))
    assert (scan.value - before[0], walk.value - before[1]) == (scans, walks)
    took = flash and not walks and T % 64 == 0
    assert ("kda_chunk_scan" in text) == took
    kind = kind_of(cfg, 1, F32)
    kind.flash, kind.max_len = flash, max_len
    meta = kind.chunk_meta(ChunkPlan(start=0, ids=np.zeros(T, np.int32)))
    assert meta["scan_kernel"] is took
