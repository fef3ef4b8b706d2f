"""The routed experts' grouped-matmul kernels (`ops/grouped_matmul.py`) in
interpret mode: each of the four bodies and the whole feed-forward, value and
the gradients for ``xs``, ``wi``, ``wo``, against `lax.ragged_dot` with the
``valid`` mask (what `RoutedExperts` runs everywhere else) and against an f32
per-expert einsum, with the rows past the held experts' groups poisoned in
every buffer that goes in."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dear_pytorch_tpu.ops import grouped_matmul as gm
from dear_pytorch_tpu.ops import moe_rows
from dear_pytorch_tpu.parallel import ep

N, H, F, E = 320, 256, 256, 4
#: rows an expert: boundaries inside tiles (32 and 64 rows here), an expert
#: without rows, every row one expert's, no row held, every row held
SIZES = {
    "uneven": (37, 5, 150, 61),
    "empty-group": (70, 0, 100, 33),
    "one-expert": (0, N, 0, 0),
    "none": (0, 0, 0, 0),
    "all": (100, 60, 96, 64),
}
DTYPES = ["float32", "bfloat16"]


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Several row tiles of two heights and two column tiles of everything
    at this file's sizes (the module's own constants give one of each)."""
    monkeypatch.setattr(gm, "_ROWS", 32)
    monkeypatch.setattr(gm, "_ROWS_T", 64)
    monkeypatch.setattr(gm, "_tiles", lambda *a: gm.Tiles(*[128] * 5))


@functools.lru_cache(maxsize=None)
def _case(sizes, dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    sizes = jnp.asarray(SIZES[sizes], jnp.int32)
    count = int(jnp.sum(sizes))

    def rows(k, n):
        return jax.random.normal(k, (N, n)).astype(dtype)

    return dict(
        sizes=sizes, count=count, live=(jnp.arange(N) < count)[:, None],
        onehot=jax.nn.one_hot(jnp.searchsorted(
            jnp.cumsum(sizes), jnp.arange(N), side="right"), E),
        xs=rows(ks[0], H), g_ys=rows(ks[1], H), act=rows(ks[2], F),
        gu=jax.random.normal(ks[3], (2, N, F)).astype(dtype),
        wi=jax.random.normal(ks[4], (E, H, 2 * F)) * H ** -0.5,
        wo=jax.random.normal(ks[5], (E, F, H)) * F ** -0.5)


def _poisoned(c, a):
    """``a`` with its rows past the held experts' groups NaN (sorted rows on
    the axis before the last): what a buffer nothing wrote there may hold."""
    return jnp.where(c["live"], a, jnp.nan)


def _live(c, a):
    """Rows past the count hold anything: compare the others."""
    a = np.asarray(a, np.float32)
    return np.where(np.asarray(c["live"]), a, 0) if a.shape[-2] == N else a


def _close(c, got, want, dtype):
    got, want = _live(c, got), _live(c, want)
    assert not np.isnan(got).any()
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * (np.abs(want).max() + 1e-9))


def _f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


def _per_expert(c, rows, weights):
    """``rows[i] @ weights[expert of i]`` in f32; 0 for a row of no expert."""
    return jnp.einsum("nk,ne,ekm->nm", rows.astype(jnp.float32), c["onehot"],
                      weights.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def _dense(c, xs, wi, wo):
    """The feed-forward as f32 per-expert einsums."""
    gate_up = _per_expert(c, xs, wi)
    return _per_expert(c, jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:], wo)


def _ragged(sizes, dtype, xs, wi, wo):
    """`RoutedExperts`' program off the TPU."""
    valid = jnp.arange(N) < jnp.sum(sizes)
    gate_up = lax.ragged_dot(xs, wi.astype(dtype), sizes)
    gate_up = jnp.where(valid[:, None], gate_up, 0)
    act = jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:]
    return lax.ragged_dot(act, wo.astype(dtype), sizes)


def _walks(c):
    how = dict(rows=32, interpret=True)
    return gm.visits(c["sizes"], N, 32), how, gm.visits(c["sizes"], N, 64), \
        dict(rows=64, interpret=True)


@pytest.mark.parametrize("rows", [32, 64])
@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_visits_cover_each_experts_rows_once_in_order(sizes, rows):
    """Every (row tile, expert) pair that shares a row is one visit, experts
    in order and each one's tiles in order (an output block may be revisited
    only by consecutive visits); an expert without rows gets one visit."""
    sizes = np.asarray(SIZES[sizes])
    walk = gm.visits(jnp.asarray(sizes, jnp.int32), N, rows)
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(walk.offsets, [0, *ends])
    want = []
    for e, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        tiles = range(lo // rows, -(-hi // rows)) if hi > lo else [
            min(lo // rows, N // rows - 1)]
        want += [(e, t) for t in tiles]
    total = int(walk.total[0])
    assert total == len(want) <= walk.group.shape[0]
    got = list(zip(np.asarray(walk.group)[:total].tolist(),
                   np.asarray(walk.tile)[:total].tolist()))
    assert got == want


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_gate_up_kernel(sizes, dtype):
    c = _case(sizes, dtype)
    walk, how, _, _ = _walks(c)
    gu, act = gm._gate_up(_poisoned(c, c["xs"]), c["wi"].astype(dtype), walk,
                          cols=128, **how)
    want = _per_expert(c, c["xs"], c["wi"].astype(dtype))
    _close(c, gu[0], want[:, :F], dtype)
    _close(c, gu[1], want[:, F:], dtype)
    _close(c, act, jax.nn.silu(want[:, :F]) * want[:, F:], dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["act-wo", "d_gate_up-wiT"])
def test_matmul_kernel(sizes, dtype, transposed):
    c = _case(sizes, dtype)
    walk, how, _, _ = _walks(c)
    if transposed:      # d_xs = d_gate wi_gate^T + d_up wi_up^T
        lhs, rhs = c["gu"], c["wi"].astype(dtype)
        want = _per_expert(c, jnp.concatenate(list(lhs), axis=1),
                           rhs.swapaxes(1, 2))
    else:               # ys = act wo
        lhs, rhs = c["act"][None], c["wo"].astype(dtype)
        want = _per_expert(c, lhs[0], rhs)
    got = gm._matmul(_poisoned(c, lhs), rhs, walk, transposed=transposed,
                     cols=128, **how)
    _close(c, got, want, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_act_grad_kernel(sizes, dtype):
    c = _case(sizes, dtype)
    walk, how, _, _ = _walks(c)
    wo = c["wo"].astype(dtype)
    d_gu, act = gm._act_grad(_poisoned(c, c["g_ys"]), wo,
                             _poisoned(c, c["gu"]), walk, cols=128, **how)
    gate, up = _f32(*c["gu"])
    swiglu = lambda g, u: jax.nn.silu(g) * u  # noqa: E731
    want_act, vjp = jax.vjp(swiglu, gate, up)
    d_gate, d_up = vjp(_per_expert(c, c["g_ys"], wo.swapaxes(1, 2)))
    _close(c, act, want_act, dtype)
    _close(c, d_gu[0], d_gate, dtype)
    _close(c, d_gu[1], d_up, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("halves", [1, 2], ids=["d_wo", "d_wi"])
def test_weight_grad_kernel(sizes, dtype, halves):
    c = _case(sizes, dtype)
    _, _, walk, how = _walks(c)
    lhs, rhs = (c["act"], c["g_ys"][None]) if halves == 1 else (c["xs"],
                                                                c["gu"])
    got = gm._weight_grad(_poisoned(c, lhs), _poisoned(c, rhs), walk,
                          cols=128, **how)
    want = jnp.einsum("nk,ne,nm->ekm", *_f32(lhs), c["onehot"],
                      jnp.concatenate(_f32(*rhs), axis=1),
                      precision=lax.Precision.HIGHEST)
    assert got.dtype == jnp.dtype(dtype)
    _close(c, got, want, dtype)
    # an expert without rows reads exactly zero, whatever the buffers hold
    assert not np.asarray(got)[np.asarray(c["sizes"]) == 0].any()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("reference", ["ragged_dot", "einsum"])
def test_feed_forward_and_its_gradients(sizes, dtype, reference):
    """Value and the three gradients; what goes in is poisoned past the
    count (``xs``, and the cotangent of ``ys``), what comes out is compared
    up to the count (``ys``, ``d_xs``) or whole (``d_wi``, ``d_wo``)."""
    c = _case(sizes, dtype)
    ref = (functools.partial(_ragged, c["sizes"], jnp.dtype(dtype))
           if reference == "ragged_dot" else functools.partial(_dense, c))
    want, ref_vjp = jax.vjp(ref, c["xs"], c["wi"], c["wo"])
    want_grads = ref_vjp(jnp.where(c["live"], c["g_ys"], 0).astype(want.dtype))
    got, vjp = jax.vjp(
        lambda xs, wi, wo: gm.feed_forward(
            xs, wi.astype(dtype), wo.astype(dtype), c["sizes"]),
        _poisoned(c, c["xs"]), c["wi"], c["wo"])
    grads = vjp(_poisoned(c, c["g_ys"]))
    assert got.dtype == jnp.dtype(dtype)
    assert [g.dtype for g in grads] == [jnp.dtype(dtype), jnp.float32,
                                        jnp.float32]
    _close(c, got, want, dtype)
    for g, w in zip(grads, want_grads):
        _close(c, g, w, dtype)


def test_the_forward_keeps_gate_and_up_and_no_activation():
    """What lives from the forward to the backward: ``xs``, the weights as
    they came, ``sizes`` and ONE ``[2, N, F]`` array; no ``[N, F]``
    activation."""
    c = _case("uneven", "bfloat16")
    _, res = gm._forward(c["xs"], c["wi"].astype(jnp.bfloat16),
                         c["wo"].astype(jnp.bfloat16), c["sizes"])
    shapes = sorted((a.shape, a.dtype.name) for a in jax.tree.leaves(res))
    assert shapes == sorted([
        ((N, H), "bfloat16"), ((E, H, 2 * F), "bfloat16"),
        ((E, F, H), "bfloat16"), ((2, N, F), "bfloat16"), ((E,), "int32")])


def test_tiles_are_a_function_of_the_widths(monkeypatch):
    monkeypatch.undo()
    for F_ in (1536, 1792):         # both cells: whole lane tiles that divide
        tiles = gm._tiles(2048, F_, 2)
        assert all(t % 128 == 0 for t in tiles)
        assert F_ % tiles.gate_up == 0 and F_ % tiles.d_wi == 0
        assert not any(2048 % t for t in (tiles.out, tiles.back, tiles.d_wo))
    assert gm._columns(1792, 1024) == 896 and gm._columns(1536, 1024) == 768
    assert gm._columns(1792, 100) == 128
    # a width whose narrowest tiles outgrow the budget is not the kernels'
    assert gm._tiles(1 << 17, 1 << 17, 2) is None


def test_the_model_takes_the_grouped_kernels_only_on_a_tpu(monkeypatch):
    monkeypatch.undo()
    cells = [(32768, 2048, 1536), (32768, 2048, 1792)]
    assert not any(gm.applies(*c, jnp.bfloat16) for c in cells)  # the CPU here
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    assert all(gm.applies(*c, jnp.bfloat16) for c in cells)
    # f32 (the reference checks) keeps `lax.ragged_dot`
    assert not any(gm.applies(*c, jnp.float32) for c in cells)
    assert not gm.applies(256, 64, 48, jnp.bfloat16)        # the tiny presets
    assert not gm.applies(32768 + 128, 2048, 1536, jnp.bfloat16)
    assert not gm.applies(32768, 1 << 17, 1 << 17, jnp.bfloat16)


@pytest.mark.parametrize("rows", ["gathers", "row-kernels"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_layer_with_grouped_kernels_is_the_layer_with_ragged_dot(
        monkeypatch, dtype, rows):
    """`RoutedExperts` whole, value and every gradient, on the path the TPU
    takes (here through Pallas' interpreter) against `lax.ragged_dot` and the
    mask, with either row movement around it."""
    T = 64
    layer = ep.RoutedExperts(router_width=16, experts_held=4, expert_offset=4,
                             top_k=4, mlp_dim=128, routed_scaling_factor=1.8,
                             dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(3), (T, 128)).astype(dtype)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    params = {**params, "router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (16,))}

    def loss(p, x):
        y, state = layer.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), state

    monkeypatch.setattr(moe_rows, "applies",
                        lambda *a: rows == "row-kernels")
    (want, state), want_grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(gm, "applies", lambda *a: True)
    (got, got_state), got_grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(params, x)
    jax.tree.map(np.testing.assert_array_equal, got_state, state)
    assert 0 < int(np.sum(jax.tree.leaves(state)[0])) < T * 4

    def same(g, w):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        tol = 2e-5 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * (np.abs(w).max() + 1e-9))

    same(got, want)
    jax.tree.map(same, got_grads, want_grads)
