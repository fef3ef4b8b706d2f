"""The routed experts' row kernels (`ops/moe_rows.py`) in interpret mode
against the gathers they replace on the TPU (`parallel.ep._spread`,
`_unpermute` + mask + weighted sum, which stay as `RoutedExperts`' path
everywhere else): forward and every gradient, at held shares from none to
all, with the rows of absent experts poisoned on the way in and on the way
out."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops import moe_rows
from dear_pytorch_tpu.parallel import ep

HELD = 4    # experts this "chip" holds; the router scores HELD / share
K = 4

#: share of the T*k rows that are live -> router width
SHARES = {"none": None, "eighth": 32, "quarter": 16, "all": HELD}
#: (tokens, hidden): one block and less, several blocks, blocks that do not
#: divide (T = 72: token blocks of 8; T*k = 288 rows: row blocks of 32)
SHAPES = {"one-block": (64, 128), "blocks": (512, 256), "odd": (72, 128)}


@functools.lru_cache(maxsize=None)
def _case(share, shape, dtype="float32", one_expert=False):
    """A seeded layer's worth of routing and rows."""
    (T, H), width = SHAPES[shape], SHARES[share]
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    if width is None:       # every token to absent experts
        group = jnp.full((T, K), HELD, jnp.int32)
    elif one_expert:        # every token's first slot to held expert 2
        group = jnp.full((T, K), HELD, jnp.int32).at[:, 0].set(2)
    else:                   # k distinct uniform choices among `width`
        idx = jnp.argsort(jax.random.uniform(ks[0], (T, width)))[:, :K]
        group = jnp.where(idx < HELD, idx, HELD).astype(jnp.int32)
    order = jnp.argsort(group.reshape(-1), stable=True)
    sizes = jnp.sum(group.reshape(-1, 1) == jnp.arange(HELD), axis=0,
                    dtype=jnp.int32)
    count = int(jnp.sum(sizes))
    live = (jnp.arange(T * K) < count)[:, None]
    rows = lambda k, n: jax.random.normal(k, (n, H)).astype(dtype)  # noqa: E731
    return dict(
        T=T, H=H, count=count, live=live, group=group, held=group < HELD,
        order=order, inverse=jnp.argsort(order), sizes=sizes,
        x=rows(ks[1], T), ys=rows(ks[2], T * K), g_rows=rows(ks[3], T * K),
        g_tokens=rows(ks[4], T),
        w=jax.random.uniform(ks[5], (T, K), jnp.float32, 0.1, 1.0))


def _moved(c):
    return moe_rows.dispatch(c["group"], c["order"], c["inverse"],
                             c["sizes"])


def _poisoned(c, a):
    """``a`` with the rows past the held experts' groups NaN (what a buffer
    nothing wrote may hold)."""
    return jnp.where(c["live"], a, jnp.nan)


def _reference_combine(c, ys, w):
    back = ep._unpermute(ys, c["order"], c["inverse"]).reshape(
        c["T"], K, c["H"])
    back = jnp.where(c["held"][..., None], back.astype(jnp.float32), 0.0)
    return jnp.sum(back * w[..., None], axis=1).astype(ys.dtype)


def _same(got, want, dtype="float32"):
    """Equal up to the order of at most k f32 additions (and one rounding
    to bf16 of a sum that differs so)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = 1e-5 if dtype == "float32" else 1.6e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


CASES = [(share, shape) for share in SHARES for shape in SHAPES]


@pytest.mark.parametrize("share,shape", CASES)
def test_spread_moves_the_live_rows_and_its_gradient_sums_held_slots(
        share, shape):
    c = _case(share, shape)
    d = _moved(c)
    got, vjp = jax.vjp(lambda x: moe_rows.spread(x, d), c["x"])
    want, ref_vjp = jax.vjp(lambda x: ep._spread(
        x, c["order"], c["inverse"], c["live"][:, 0]), c["x"])
    # rows of absent experts hold anything: compare the live prefix, exactly
    np.testing.assert_array_equal(np.asarray(got)[:c["count"]],
                                  np.asarray(want)[:c["count"]])
    (dx,), (ref_dx,) = vjp(_poisoned(c, c["g_rows"])), ref_vjp(c["g_rows"])
    assert np.isfinite(np.asarray(dx)).all()
    _same(dx, ref_dx)


@pytest.mark.parametrize("share,shape", CASES)
def test_combine_sums_held_slots_and_its_gradients_are_the_gathers(
        share, shape):
    c = _case(share, shape)
    d = _moved(c)
    got, vjp = jax.vjp(
        lambda ys, w: moe_rows.combine(ys, w, d, jnp.float32),
        _poisoned(c, c["ys"]), c["w"])
    want, ref_vjp = jax.vjp(lambda ys, w: _reference_combine(c, ys, w),
                            c["ys"], c["w"])
    assert np.isfinite(np.asarray(got)).all()
    _same(got, want)
    (dys, dw), (ref_dys, ref_dw) = vjp(c["g_tokens"]), ref_vjp(c["g_tokens"])
    np.testing.assert_array_equal(np.asarray(dys)[:c["count"]],
                                  np.asarray(ref_dys)[:c["count"]])
    assert np.isfinite(np.asarray(dw)).all()
    _same(dw, ref_dw)
    # an absent slot's weight moves nothing
    assert not np.asarray(dw)[~np.asarray(c["held"])].any()


@pytest.mark.parametrize("op", ["spread", "combine"])
def test_bf16_rows_move_exactly_and_sum_in_f32(op):
    """The cells' dtype: a moved row is the source's bits (through f32 and
    back), a sum is rounded once."""
    c = _case("quarter", "blocks", "bfloat16")
    d = _moved(c)
    if op == "spread":
        got = moe_rows.spread(c["x"], d)
        want = c["x"][c["order"] // K]
        np.testing.assert_array_equal(
            np.asarray(got, np.float32)[:c["count"]],
            np.asarray(want, np.float32)[:c["count"]])
        return
    got, vjp = jax.vjp(
        lambda ys, w: moe_rows.combine(ys, w, d, jnp.bfloat16),
        _poisoned(c, c["ys"]), c["w"])
    want, ref_vjp = jax.vjp(lambda ys, w: _reference_combine(c, ys, w),
                            c["ys"], c["w"])
    assert got.dtype == jnp.bfloat16
    _same(got, want, "bfloat16")
    (dys, dw), (ref_dys, ref_dw) = vjp(c["g_tokens"]), ref_vjp(c["g_tokens"])
    assert dys.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(dys, np.float32)[:c["count"]],
        np.asarray(ref_dys, np.float32)[:c["count"]])
    _same(dw, ref_dw, "bfloat16")


@pytest.mark.parametrize("shape", SHAPES)
def test_all_tokens_to_one_held_expert(shape):
    """The dropless worst case of one group: a single range a token block,
    every other expert's empty."""
    c = _case("quarter", shape, one_expert=True)
    assert c["count"] == c["T"] and int(c["sizes"][2]) == c["T"]
    d = _moved(c)
    np.testing.assert_array_equal(
        np.asarray(moe_rows.spread(c["x"], d))[:c["T"]], np.asarray(c["x"]))
    _same(moe_rows.combine(_poisoned(c, c["ys"]), c["w"], d, jnp.float32),
          c["w"][:, :1] * c["ys"][:c["T"]])


@pytest.mark.parametrize("share", ["none", "eighth", "all"])
def test_rows_past_count_are_not_written(share):
    """Bytes moved follow ``count``: whole blocks past it are never
    visited (the output there is whatever the buffer held: here, with the
    output aliased to nothing, not the source's rows)."""
    c = _case(share, "blocks")
    got = np.asarray(moe_rows.spread_rows(
        c["x"] + 100.0, c["order"] // K, jnp.int32(c["count"])))
    rows = moe_rows._block(c["T"] * K, moe_rows._ROWS)
    visited = -(-c["count"] // rows) * rows
    assert (got[:c["count"]] > 50).all()
    assert not (got[visited:] > 50).any()


@pytest.mark.parametrize("share", ["eighth", "all"])
def test_the_plan_covers_each_blocks_rows_with_aligned_chunks(share):
    """`combine_plan`: a block's (token, slot)s point at the buffer rows
    where its chunk DMAs put their sorted rows, inside its experts' own
    ranges, and the chunks are whole tiles of the sorted buffer."""
    c = _case(share, "blocks")
    plan = moe_rows.combine_plan(c["group"], c["inverse"], c["sizes"])
    B, chunk = moe_rows._block(c["T"], moe_rows._TOKENS), moe_rows._CHUNK
    blocks = c["T"] // B
    source = np.asarray(plan.source).reshape(blocks, -1)
    filled = np.asarray(plan.filled)
    lo, hi = np.split(np.asarray(plan.bounds)[:, 0], 2, axis=1)
    assert (filled <= source.shape[1]).all()
    assert ((source >= 0) & (source < c["T"] * K // chunk)).all()
    pos, group = np.asarray(plan.pos), np.asarray(c["group"])
    assert ((pos >= 0) == (group < HELD)).all()
    inverse = np.asarray(c["inverse"]).reshape(c["T"], K)
    for b in range(blocks):
        mine = slice(b * B, (b + 1) * B)
        held = group[mine] < HELD
        at = pos[mine][held]
        assert (at < filled[b] * chunk).all()
        # buffer row -> the sorted row the chunk DMA put there
        np.testing.assert_array_equal(
            source[b][at // chunk] * chunk + at % chunk, inverse[mine][held])
        for e in range(HELD):
            own = pos[mine][group[mine] == e]
            assert ((own >= lo[b, e]) & (own < hi[b, e])).all()
            assert hi[b, e] - lo[b, e] == len(own)


def test_the_model_takes_the_kernels_only_on_a_tpu(monkeypatch):
    assert not moe_rows.applies(8192, 4, 2048)          # the CPU here
    monkeypatch.setattr(moe_rows, "_interpret", lambda: False)
    assert moe_rows.applies(8192, 4, 2048)              # both cells' widths
    assert not moe_rows.applies(64, 4, 64)              # the tiny presets
    assert not moe_rows.applies(8193, 4, 2048)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_layer_with_kernels_is_the_layer_with_gathers(monkeypatch, dtype):
    """`RoutedExperts` whole, value and every gradient, on the path the TPU
    takes (here through Pallas' interpreter) against the gathers."""
    T, H = 64, 128
    layer = ep.RoutedExperts(router_width=16, experts_held=4, expert_offset=4,
                             top_k=4, mlp_dim=48, routed_scaling_factor=1.8,
                             dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(3), (T, H)).astype(dtype)
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    params = {**params, "router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (16,))}

    def loss(p, x):
        y, state = layer.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), state

    (want, state), want_grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(params, x)
    monkeypatch.setattr(moe_rows, "applies", lambda *a: True)
    (got, got_state), got_grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(params, x)
    # the routing counter is sown on either path
    jax.tree.map(np.testing.assert_array_equal, got_state, state)
    assert 0 < int(np.sum(jax.tree.leaves(state)[0])) < T * 4
    _same(got, want, dtype)
    jax.tree.map(lambda g, w: _same(
        g, w, dtype) if dtype == "float32" else np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=2e-2 * (np.abs(np.asarray(w, np.float32)).max() + 1e-9)),
        got_grads, want_grads)
