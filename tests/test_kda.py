"""`ops.kda.kda_chunked` against the recurrence it computes, written here
twice: a loop over positions in float64 numpy (values) and a `lax.scan` over
positions (values and gradients); small sizes, float32, matmul precision
"highest", decays down to the lower bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops import kda

HEADS, K, V = 3, 8, 6
BOUND = -5.0


def _inputs(seq, seed=0, batch=2, low=BOUND, dtype=jnp.float32):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(key[0], (batch, seq, HEADS, K)))
    k = unit(jax.random.normal(key[1], (batch, seq, HEADS, K)))
    v = jax.random.normal(key[2], (batch, seq, HEADS, V))
    # log-decays from the bound to 0: a chunk of 16 at the bound decays by
    # e^-80; a quarter of the channels sit on the bound itself
    g = jax.random.uniform(key[3], (batch, seq, HEADS, K), minval=low,
                           maxval=0.0)
    g = g.at[..., ::4].set(low)
    beta = jax.random.uniform(key[4], (batch, seq, HEADS))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _loop(q, k, v, g, beta):
    """The recurrence, position by position, float64."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    batch, seq, heads, width = q.shape
    out = np.zeros(v.shape)
    for b in range(batch):
        for h in range(heads):
            state = np.zeros((width, v.shape[-1]))
            for t in range(seq):
                kt = k[b, t, h]
                decayed = np.exp(g[b, t, h])[:, None] * state
                state = (decayed - beta[b, t, h] * np.outer(kt, kt @ decayed)
                         + beta[b, t, h] * np.outer(kt, v[b, t, h]))
                out[b, t, h] = state.T @ q[b, t, h] / np.sqrt(width)
    return out


def _scan(q, k, v, g, beta):
    """The same as a `lax.scan` over positions, differentiable."""

    def step(state, t):
        qt, kt, vt, gt, bt = t
        state = jnp.exp(gt)[..., None] * state
        delta = vt - jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] * delta[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state) / np.sqrt(K)

    zeros = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    _, out = jax.lax.scan(step, zeros, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _close(a, b, rel=2e-5):
    """Equal to ``rel`` of the reference's largest entry: both sides are
    float32 at matmul precision "highest", so only the order of the sums
    differs (the factorised decays are products of two exponentials, 1e-6
    relative each; the triangular solve adds a few ulps a row); bf16
    operands move the result by 3e-3 of its size and more, a hundred times
    the limit (`test_bf16_operands_fail_the_float32_limit`)."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=rel * (np.abs(b).max() + 1e-12))


@pytest.mark.parametrize("seq,chunk", [(16, 8), (32, 8), (32, 16),
                                       (48, 16)])
def test_values_equal_the_recurrence(seq, chunk):
    args = _inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(kda.kda_chunked, static_argnums=5)(*args, chunk)
        scanned = jax.jit(_scan)(*args)
    want = _loop(*args)
    _close(scanned, want)
    _close(got, want)
    assert got.shape == (2, seq, HEADS, V) and got.dtype == jnp.float32


@pytest.mark.parametrize("chunk", [8, 16])
def test_gradients_of_every_input_equal_the_recurrences(chunk):
    args = _inputs(32, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, 32, HEADS, V))

    def loss(f):
        return lambda *a: jnp.sum(f(*a) * cot)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(loss(lambda *a: kda.kda_chunked(*a, chunk)),
                               argnums=range(5)))(*args)
        want = jax.jit(jax.grad(loss(_scan), argnums=range(5)))(*args)
    for name, a, b in zip(("q", "k", "v", "log_alpha", "beta"), got, want):
        assert np.abs(np.asarray(b)).max() > 0, name
        _close(a, b)


@pytest.mark.parametrize("heads_at_once", [1, 3])
def test_heads_a_group_at_a_time_are_the_heads_at_once(heads_at_once):
    args = _inputs(32, seed=4)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: kda.kda_chunked(
            *a, 16, heads_at_once=heads_at_once))(*args)
    _close(got, _loop(*args))
    with pytest.raises(ValueError, match="do not divide"):
        kda.kda_chunked(*args, 16, heads_at_once=2)


def test_long_chunks_at_the_bound_stay_finite():
    """Every channel at the bound for a whole chunk of 64: the decay across
    the chunk is e^-320, far below float32's smallest, and no factor
    overflows."""
    q, k, v, g, beta = _inputs(128, seed=2)
    g = jnp.full_like(g, BOUND)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(kda.kda_chunked, static_argnums=5)(q, k, v, g, beta, 64)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, 64)),
                                 argnums=range(5)))(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    for x in grads:
        assert np.isfinite(np.asarray(x)).all()
    _close(got, _loop(q, k, v, g, beta))


def test_a_gate_below_the_bound_is_refused():
    q, k, v, g, beta = _inputs(16)
    with jax.default_matmul_precision("highest"):
        ok = kda.kda_chunked(q, k, v, g, beta, 8)
        bad = kda.kda_chunked(q, k, v, g.at[1, 3, 0, 2].set(BOUND - 0.01),
                              beta, 8)
    assert np.isfinite(np.asarray(ok)).all()
    assert np.isnan(np.asarray(bad)).all()


def test_the_sub_chunk_keeps_every_exponent_within_float32():
    assert kda.sub_chunk(64, -5.0) == 16
    assert kda.sub_chunk(8, -5.0) == 8
    assert kda.sub_chunk(64, -10.0) == 8
    assert kda.MAX_EXPONENT / 2 < np.log(np.finfo(np.float32).tiny) / -2
    with pytest.raises(ValueError, match="no decay"):
        kda.sub_chunk(64, 0.0)


def test_bf16_operands_fail_the_float32_limit():
    """The same op with bf16 q, k, v stays near the recurrence (3e-2 of its
    size) but outside `_close`'s limit: the tests above tell the
    precisions apart."""
    args = _inputs(32, seed=3)
    low = _inputs(32, seed=3, dtype=jnp.bfloat16)
    got = np.asarray(kda.kda_chunked(*low, 16), np.float32)
    want = _loop(*args)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=3e-2 * np.abs(want).max())
    with pytest.raises(AssertionError):
        _close(got, want)


def test_the_pairwise_decays_never_exist_whole():
    """No array of the op's forward or backward program holds a chunk's
    positions squared times the key width: the factorisation's largest is
    the sub-chunks' columns, ``[C / sub, C, K]`` a chunk."""
    seq, chunk = 64, 32
    args = _inputs(seq)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, chunk)),
                            argnums=range(5))).lower(*args).as_text()
    pairwise = f"{chunk}x{chunk}x{K}x"
    assert pairwise not in text
    assert f"{chunk // 16}x{chunk}x{K}x" in text


@pytest.mark.parametrize("bad", ["seq", "shape"])
def test_refuses_what_it_cannot_chunk(bad):
    q, k, v, g, beta = _inputs(24)
    if bad == "seq":
        with pytest.raises(ValueError, match="multiple of the chunk"):
            kda.kda_chunked(q, k, v, g, beta, 16)
    else:
        with pytest.raises(ValueError, match="differ"):
            kda.kda_chunked(q, k[..., :4], v, g, beta, 8)
