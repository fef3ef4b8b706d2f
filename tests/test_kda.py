"""`ops.kda.kda_chunked` against the recurrence it computes, written here
twice: a loop over positions in float64 numpy (values) and a `lax.scan` over
positions (values and gradients); small sizes, float32, matmul precision
"highest", decays down to the lower bound. Its Pallas kernels (Pallas'
interpreter off the TPU) against the same loop and against XLA's chunked
form, at the kernels' 128-lane widths."""

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


# -- the Pallas kernels (interpret mode here) --------------------------------

WIDE = 128          # the kernels take whole 128-lane tiles


def _wide(seq, dtype=jnp.float32, seed=0, heads=2, low=BOUND):
    """`_inputs` at the kernels' widths: K = V = 128, one sequence."""
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (1, seq, heads, WIDE)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (unit(jax.random.normal(kk, shape)) for kk in key[:2])
    v = jax.random.normal(key[2], shape)
    g = jax.random.uniform(key[3], shape, minval=low, maxval=0.0)
    g = g.at[..., ::4].set(low)
    beta = jax.random.uniform(key[4], shape[:3])
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


@pytest.fixture
def kernels(monkeypatch):
    """`kda_chunked` on the kernels (Pallas' interpreter off the TPU)."""
    monkeypatch.setattr(kda, "kernel_applies", lambda *a, **kw: True)


def _scan64(*args):
    """`kda_chunked` at chunk 64, traced afresh (jit's cache is keyed by the
    function, not by which path `kernel_applies` chose)."""
    return jax.jit(lambda *a: kda.kda_chunked(*a, 64))(*args)


def _xla(*args):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "kernel_applies", lambda *a, **kw: False)
        return _scan64(*args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_equal_the_recurrence_and_the_xla_form(kernels, dtype):
    """f32: within `_close` of the float64 recurrence and of XLA's chunked
    form. bf16 operands: both forms round W, P, U and the states to bf16 at
    the same points, but a rounding whose f32 input differs in its last
    bits (another order of sums) can land one bf16 step (4e-3) the other
    way, so they agree to 3e-2 of the output's size, the band bf16 keeps to
    the recurrence (`test_bf16_operands_fail_the_float32_limit`), and the
    float32 limit fails them."""
    args = _wide(256, getattr(jnp, dtype))
    want = _loop(*(a.astype(jnp.float32) for a in args))
    with jax.default_matmul_precision("highest"):
        got = _scan64(*args)
        xla = _xla(*args)
    assert not np.array_equal(np.asarray(got), np.asarray(xla))
    assert got.dtype == args[2].dtype and got.shape == (1, 256, 2, WIDE)
    got, xla = np.asarray(got, np.float32), np.asarray(xla, np.float32)
    if dtype == "float32":
        _close(got, want)
        _close(got, xla)
        return
    for ref in (want, xla):
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=3e-2 * np.abs(ref).max())
    with pytest.raises(AssertionError):
        _close(got, want)


def test_the_kernels_gradients_equal_the_xla_forms(kernels):
    """All five input gradients through the kernels' `custom_vjp` against
    autodiff of XLA's chunked form, f32, within `_close`."""
    args = _wide(256, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(9), (1, 256, 2, WIDE))

    def loss(*a):
        return jnp.sum(kda.kda_chunked(*a, 64) * cot)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(kda, "kernel_applies", lambda *a, **kw: False)
            want = jax.jit(jax.grad(loss, argnums=range(5)))(*args)
    for name, a, b in zip(("q", "k", "v", "log_alpha", "beta"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.abs(np.asarray(b)).max() > 0, name
        _close(a, b)


def test_the_kernels_at_the_bound_stay_finite(kernels):
    """Every channel at the bound for whole chunks of 64 (the decay across
    a chunk e^-320): values and gradients finite, values on the
    recurrence."""
    q, k, v, g, beta = _wide(128, seed=2)
    g = jnp.full_like(g, BOUND)
    with jax.default_matmul_precision("highest"):
        got = _scan64(q, k, v, g, beta)
        grads = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, 64)),
                                 argnums=range(5)))(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    for x in grads:
        assert np.isfinite(np.asarray(x)).all()
    _close(got, _loop(q, k, v, g, beta))


def test_the_kernels_refuse_a_gate_below_the_bound(kernels):
    q, k, v, g, beta = _wide(64, seed=3)
    ok = kda.kda_chunked(q, k, v, g, beta, 64)
    bad = kda.kda_chunked(q, k, v, g.at[0, 3, 1, 2].set(BOUND - 0.01), beta,
                          64)
    assert np.isfinite(np.asarray(ok)).all()
    assert np.isnan(np.asarray(bad)).all()


@pytest.mark.parametrize("shape,chunk,dtype,want", [
    ((1, 8192, 32, 128), 64, jnp.bfloat16, True),     # the cell's layer
    ((1, 8192, 32, 128), 64, jnp.float32, True),      # its f32 check
    ((1, 8192, 32, 64), 64, jnp.bfloat16, False),     # half a lane tile
    ((1, 8192, 32, 128), 16, jnp.bfloat16, False),    # another chunk
    ((1, 8200, 32, 128), 64, jnp.bfloat16, False),    # no whole chunks
    ((1, 8192, 32, 128), 64, jnp.float16, False),
    ((1, 262144, 32, 128), 64, jnp.bfloat16, False),  # VMEM
])
def test_kernel_applies_by_backend_shape_and_dtype(monkeypatch, shape,
                                                   chunk, dtype, want):
    """Off the TPU never; on one (described here) for whole lane tiles,
    whole chunks of 64 and bf16 or f32."""
    assert not kda.kernel_applies(shape, chunk, dtype)
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    assert kda.kernel_applies(shape, chunk, dtype) == want
