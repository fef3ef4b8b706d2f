"""`ops.ssd.ssd_chunked_scan` against the recurrence it computes, written
here three times: a loop over positions in float64 numpy (values), a
`lax.scan` over positions (gradients), and the one-equation form ``y_t =
sum_{s<=t} exp(sum_{r=s+1..t} dt_r A) (C_t . B_s) dt_s x_s + D x_t`` as one
``[S, S]`` matrix a head (values and gradients); small sizes, float32, matmul
precision "highest"."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops.ssd import ssd_chunked_scan

HEADS, WIDTH, GROUPS, STATE = 4, 6, 2, 5
NAMES = ("x", "dt", "A", "B", "C", "D")


def _inputs(seq, seed=0, batch=2, heads=HEADS, groups=GROUPS):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (batch, seq, heads, WIDTH)),
            # steps from 0.01 to 1.5: decays a chunk from 0.99 to e^-100
            jnp.exp(jax.random.uniform(k[1], (batch, seq, heads),
                                       minval=np.log(0.01),
                                       maxval=np.log(1.5))),
            -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0,
                                        maxval=np.log(16.0))),
            jax.random.normal(k[3], (batch, seq, groups, STATE)),
            jax.random.normal(k[4], (batch, seq, groups, STATE)),
            jax.random.normal(k[5], (heads,)))


def _loop(x, dt, A, B, C, D):
    """The recurrence, position by position, float64."""
    x, dt, A, B, C, D = (np.asarray(t, np.float64)
                         for t in (x, dt, A, B, C, D))
    batch, seq, heads, width = x.shape
    per = heads // B.shape[2]
    y = np.zeros_like(x)
    for b in range(batch):
        for h in range(heads):
            state = np.zeros((width, B.shape[3]))
            for t in range(seq):
                state = (np.exp(dt[b, t, h] * A[h]) * state
                         + dt[b, t, h] * np.outer(x[b, t, h],
                                                  B[b, t, h // per]))
                y[b, t, h] = state @ C[b, t, h // per] + D[h] * x[b, t, h]
    return y


def _scan(x, dt, A, B, C, D):
    """The same as a `lax.scan` over positions, differentiable."""
    per = x.shape[2] // B.shape[2]
    Bh, Ch = jnp.repeat(B, per, axis=2), jnp.repeat(C, per, axis=2)

    def step(state, t):
        xt, dtt, bt, ct = t
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + D[:, None] * xt

    zeros = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[3:])
    _, y = jax.lax.scan(step, zeros, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def _one_equation(x, dt, A, B, C, D):
    """``y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r A) (C_t . B_s) dt_s x_s +
    D x_t``: every head's whole ``[S, S]`` matrix at once, no chunks, no
    carried state."""
    per, seq = x.shape[2] // B.shape[2], x.shape[1]
    Bh, Ch = jnp.repeat(B, per, axis=2), jnp.repeat(C, per, axis=2)
    total = jnp.cumsum(dt * A, axis=1)                       # [b, S, h]
    past = (jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :])[..., None]
    decay = jnp.exp(jnp.where(
        past, total[:, :, None] - total[:, None, :], -jnp.inf))  # [b,t,s,h]
    weights = decay * jnp.einsum("bthn,bshn->btsh", Ch, Bh)
    return (jnp.einsum("btsh,bshp->bthp", weights, dt[..., None] * x)
            + D[:, None] * x)


def _close(a, b, rel=2e-5):
    """Equal to ``rel`` of the reference's largest entry: both sides are
    float32 at matmul precision "highest", so only the order of the sums
    differs (a chunk's decays are exponentials of differences of running
    sums, 1e-6 relative each); bf16 operands move ``y`` by 4e-3 of its size,
    two hundred times the limit (`test_bf16_operands_within_3e_2`)."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=rel * (np.abs(b).max() + 1e-12))


@pytest.mark.parametrize("seq,chunk", [(8, 8), (32, 1), (32, 4), (32, 8),
                                       (32, 16), (32, 32), (48, 16)])
def test_values_equal_the_recurrence(seq, chunk):
    args = _inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(ssd_chunked_scan, static_argnums=6)(*args, chunk)
        _close(_one_equation(*args), _loop(*args))
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    _close(got, _loop(*args))


def test_every_chunk_size_gives_the_same_values():
    args = _inputs(32, seed=3)
    with jax.default_matmul_precision("highest"):
        ys = [ssd_chunked_scan(*args, chunk) for chunk in (1, 2, 4, 8, 32)]
    for y in ys[1:]:
        _close(y, ys[0])


@pytest.mark.parametrize("reference", [_scan, _one_equation])
@pytest.mark.parametrize("heads,groups", [(4, 1), (4, 2), (4, 4)])
@pytest.mark.parametrize("seq,chunk", [(8, 1), (8, 8), (32, 4), (32, 8)])
def test_gradients_of_every_input_equal_the_references(seq, chunk, heads,
                                                       groups, reference):
    args = _inputs(seq, seed=1, heads=heads, groups=groups)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def scalar(fn):
        return lambda *a: jnp.sum(weight * fn(*a))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(scalar(
            lambda *a: ssd_chunked_scan(*a, chunk)), argnums=range(6)))(*args)
        want = jax.jit(jax.grad(scalar(reference), argnums=range(6)))(*args)
        _close(reference(*args), _loop(*args))
    for name, g, w in zip(NAMES, got, want):
        assert np.asarray(w).any(), name
        try:
            _close(g, w)
        except AssertionError as e:
            raise AssertionError(f"gradient of {name}: {e}") from None


def test_groups_fewer_than_heads_share_b_and_c():
    """Head i reads group i // (heads / groups): with two groups, heads 0
    and 1 read group 0; it is the equal-groups op on B and C repeated."""
    x, dt, A, B, C, D = _inputs(16, seed=2)
    with jax.default_matmul_precision("highest"):
        grouped = ssd_chunked_scan(x, dt, A, B, C, D, 8)
        repeated = ssd_chunked_scan(x, dt, A, jnp.repeat(B, 2, axis=2),
                                    jnp.repeat(C, 2, axis=2), D, 8)
        tiled = ssd_chunked_scan(x, dt, A, jnp.tile(B, (1, 1, 2, 1)),
                                 jnp.tile(C, (1, 1, 2, 1)), D, 8)
    _close(grouped, repeated)
    assert np.abs(np.asarray(grouped - tiled)).max() > 1e-2


@pytest.mark.parametrize("chunk", [64, 256])
def test_long_chunks_of_fast_decay_stay_finite(chunk):
    """256 positions at dt * A = -24 a position: a chunk's running sum
    reaches -6144, exp of it underflows to 0; a factor formed as exp(cum_i)
    / exp(cum_j) would be 0 / 0 and one formed as exp(-cum_j) would
    overflow, the masked difference is exact. Values and gradients are
    finite and the values the recurrence's."""
    x, dt, A, B, C, D = _inputs(256, seed=4, batch=1)
    dt, A = jnp.full_like(dt, 1.5), jnp.full_like(A, -16.0)
    with jax.default_matmul_precision("highest"):
        y, grads = jax.value_and_grad(
            lambda *a: jnp.sum(ssd_chunked_scan(*a, chunk) ** 2),
            argnums=range(6))(x, dt, A, B, C, D)
        _close(ssd_chunked_scan(x, dt, A, B, C, D, chunk),
               _loop(x, dt, A, B, C, D))
    assert np.isfinite(float(y))
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_bf16_operands_within_3e_2():
    """bf16 operands: the result is bf16 within 3e-2 of the largest entry
    (and outside the float32 tolerance: it tells precisions apart); the
    decays and the carried states are still float32 (the jaxpr holds no
    bf16 exponential and no bf16 running sum)."""
    args = _inputs(32, seed=5)
    low = tuple(t.astype(jnp.bfloat16) if t.ndim == 4 else t for t in args)
    got = ssd_chunked_scan(*low, 8)
    assert got.dtype == jnp.bfloat16
    with pytest.raises(AssertionError):
        _close(got.astype(jnp.float32), _loop(*args))
    _close(got.astype(jnp.float32), _loop(*args), rel=3e-2)
    eqns = list(_equations(jax.make_jaxpr(
        lambda *a: ssd_chunked_scan(*a, 8))(*low).jaxpr))
    for name in ("exp", "cumsum"):
        found = [e for e in eqns if e.primitive.name == name]
        assert found and all(e.outvars[0].aval.dtype == jnp.float32
                             for e in found), name
    # all-bf16 arguments too: dt, A and D are lifted to float32 inside
    all_low = tuple(t.astype(jnp.bfloat16) for t in args)
    _close(ssd_chunked_scan(*all_low, 8).astype(jnp.float32), _loop(*args),
           rel=6e-2)


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub)


def test_the_decay_matrices_are_recomputed_in_the_backward_pass():
    """The forward pass of the gradient keeps no [chunk, chunk] matrix: the
    residuals are the op's inputs (`jax.checkpoint` around the core)."""
    args = _inputs(32, seed=6)
    _, vjp = jax.vjp(lambda *a: ssd_chunked_scan(*a, 8), *args)
    kept = [np.shape(r) for r in jax.tree.leaves(vjp)]
    assert kept and all(s[-2:] != (8, 8) for s in kept)
    assert sum(int(np.prod(s)) for s in kept) <= sum(t.size for t in args)


def test_no_decay_is_divided_and_the_mask_comes_before_the_exponential():
    """The jaxpr holds no division at all, and every exponential of a
    ``[chunk, chunk]`` operand reads the mask's `jnp.where` directly."""
    args = _inputs(32, seed=7)
    eqns = list(_equations(jax.make_jaxpr(
        lambda *a: ssd_chunked_scan(*a, 8))(*args).jaxpr))
    assert not [e for e in eqns if e.primitive.name == "div"]
    producers = {v: e for e in eqns for v in e.outvars}
    square = [e for e in eqns if e.primitive.name == "exp"
              and e.invars[0].aval.shape[-2:] == (8, 8)]
    assert square
    for e in square:
        masked = producers[e.invars[0]]     # `jnp.where`: a jitted select_n
        assert (masked.primitive.name == "select_n"
                or masked.params.get("name") == "_where"), masked


@pytest.mark.parametrize("seq,chunk,groups", [(12, 8, 2), (16, 8, 3)])
def test_refuses_what_it_cannot_chunk(seq, chunk, groups):
    args = list(_inputs(seq, heads=HEADS, groups=2))
    if groups == 3:
        args[3] = args[4] = jnp.zeros((2, seq, 3, STATE))
    with pytest.raises(ValueError,
                       match="multiple of the chunk|do not divide"):
        ssd_chunked_scan(*args, chunk)
