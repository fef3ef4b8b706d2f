"""The routed experts' feed-forward as Pallas TPU grouped matmuls.

`parallel.ep.RoutedExperts` runs ``ys = (silu(xs wi_gate[e]) * (xs
wi_up[e])) wo[e]`` over rows sorted by held expert: ``xs`` ``[N, H]`` with
``N = T*k`` whatever the routing (the layer is dropless), of which only
``count = sum(sizes)`` rows, a prefix, belong to an expert this chip holds.
`jax.lax.ragged_dot` (XLA:TPU's grouped-matmul kernel) bounds its own work
by the groups, but the activation between the two matmuls, the mask on the
rows of no group and their backward are XLA fusions of static shape that
read and write all ``N`` rows. Here the whole feed-forward is six kernels
that walk the held experts' rows only and keep the activation in the
matmuls' epilogues:

forward
    `_gate_up`   ``gate, up = xs wi[e]`` side by side in f32 (``wi`` is gate
                 then up: two block views of one array), ``act = silu(gate)
                 * up`` from the accumulators, all three written once in the
                 compute dtype
    `_matmul`    ``ys = act wo[e]``
backward
    `_act_grad`  ``d_act = d_ys wo[e]^T`` with the SwiGLU's derivative in
                 its epilogue: ``d_gate``, ``d_up``, and ``act`` again (from
                 the saved ``gate`` and ``up``: the forward's ``act`` is no
                 residual)
    `_matmul`    ``d_xs = d_gate wi_gate[e]^T + d_up wi_up[e]^T``
    `_weight_grad` twice: ``d_wi[e] = xs[e]^T [d_gate | d_up][e]`` and
                 ``d_wo[e] = act[e]^T d_ys[e]``, accumulated in f32 over an
                 expert's row tiles and written once in the compute dtype,
                 as `lax.ragged_dot`'s transpose writes them (written in f32
                 they cost the step more than they gave: the cells pack
                 gradients in bf16, and the pack then reads twice the
                 bytes); an expert without rows reads zero

Rows. One grid dimension walks the VISITS (`visits`): the (row tile, expert)
pairs that share a row, in sorted order, their number traced. A tile that
straddles two experts is visited once for each under a row mask; a tile past
``count`` is never visited, so rows past ``count`` are neither read nor
written (but for the last tile's tail, which is masked out of every sum and
every store: NaN there reaches nothing). Output rows past ``count`` hold
whatever the buffer held, as `lax.ragged_dot`'s do on the TPU. With every
assignment held the kernels walk all ``N`` rows.

Gate and up travel as ONE ``[2, N, F]`` array (gate, then up): two blocks of
one ``[N, 2F]`` array cannot be one kernel output, and a ``[N, 2, F]`` layout
would put a 2-row dimension on the sublanes.

Tiles are functions of the widths alone (`_tiles`): a visit holds its rows
against the whole contraction (no K grid dimension, no accumulator
revisits), the column tile is the outermost grid dimension so that an
expert's weight block stays put while its row tiles pass, and the widest
column tile that fits the VMEM budget is taken.

Precision: operands in the compute dtype, every accumulation in f32, one
rounding on the way out; the SwiGLU and its derivative are computed in f32
from the accumulators (forward) and from the saved ``gate`` / ``up``
(backward). f32 operands run at ``Precision.HIGHEST`` (the tests; the model
keeps `lax.ragged_dot` for f32, `applies`).

Off the TPU everything runs under ``interpret=True`` (tests); the model takes
the kernels only on a TPU (`applies`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dear_pytorch_tpu.ops.moe_rows import _block

_LANES = 128
#: rows of one tile of the matmul kernels / of the weight-gradient kernel
_ROWS = 128
_ROWS_T = 256
#: what one kernel's blocks (double-buffered) and scratch may take of VMEM
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _columns(n: int, most: int) -> int:
    """The widest tile of whole lane tiles that divides ``n`` columns and is
    at most ``most`` wide (128 if none other is)."""
    lanes = n // _LANES
    return _LANES * max(d for d in range(1, lanes + 1)
                        if lanes % d == 0 and (d == 1 or d * _LANES <= most))


class Tiles(NamedTuple):
    """Column tiles of the six kernels at one ``(H, F, dtype)``."""

    gate_up: int    # of F: `_gate_up` and `_act_grad` (contraction: H)
    out: int        # of H: ``ys = act wo`` (contraction: F)
    back: int       # of H: ``d_xs = d_gate_up wi^T`` (contraction: 2F)
    d_wi: int       # of F: ``d_wi``'s columns a half (its H rows whole)
    d_wo: int       # of H: ``d_wo``'s columns (its F rows whole)


def _tiles(hidden: int, mlp_dim: int, itemsize: int) -> Tiles | None:
    """The widest column tiles whose blocks (double-buffered) and scratch
    fit `_VMEM_BUDGET`; ``None`` if 128 columns do not (widths the kernels
    are not for)."""
    H, F, R, RT = hidden, mlp_dim, _ROWS, _ROWS_T

    def widest(n, cost):
        most = n
        while True:
            tn = _columns(n, most)
            if cost(tn) <= _VMEM_BUDGET:
                return tn
            if tn == _LANES:
                return None
            most = tn - _LANES

    def grad(depth):    # lhs and rhs rows in; f32 accumulator, output out
        return lambda tn: (2 * itemsize * RT * (depth + tn)
                           + (4 + 2 * itemsize) * depth * tn)

    found = Tiles(
        # rows x H and two weight views in; gate, up, act out (the backward
        # body: d_ys and one view in, gate and up in, three out)
        widest(F, lambda tn: 2 * itemsize * (R * H + 2 * H * tn + 5 * R * tn)),
        widest(H, lambda tn: 2 * itemsize * (R * F + F * tn + R * tn)),
        widest(H, lambda tn: 2 * itemsize * (2 * R * F + 2 * F * tn + R * tn)),
        widest(F, grad(H)), widest(H, grad(F)))
    return None if None in found else found


def applies(rows: int, hidden: int, mlp_dim: int, dtype) -> bool:
    """Whether `RoutedExperts` runs its feed-forward on these kernels: on a
    TPU (elsewhere Pallas' interpreter would run), bfloat16 compute (f32,
    the reference checks' dtype, keeps `lax.ragged_dot`), ``H`` and ``F``
    whole 128-lane tiles that fit VMEM a row tile at a time, and rows the
    row tiles divide."""
    return (not _interpret() and jnp.dtype(dtype) == jnp.bfloat16
            and hidden % _LANES == 0 and mlp_dim % _LANES == 0
            and rows % _ROWS_T == 0
            and _tiles(hidden, mlp_dim, 2) is not None)


# ---------------------------------------------------------------------------
# the visits: which (row tile, expert) pairs hold a row
# ---------------------------------------------------------------------------


class Visits(NamedTuple):
    """The row-tile walk of one ``sizes`` at one tile height (`visits`)."""

    offsets: jax.Array  # [E + 1] first sorted row of each expert, then count
    group: jax.Array    # [V] the expert of visit ``v``
    tile: jax.Array     # [V] its row tile
    total: jax.Array    # [1] visits to make (the traced grid bound)


def visits(sizes, rows: int, tile_rows: int) -> Visits:
    """Experts in order, each over the row tiles its rows touch: an expert
    whose rows start inside a tile shares it with the one before. An expert
    WITHOUT rows is visited once, on an empty mask (the weight gradient has
    to write its zeros; the matmuls lose one tile's time to it)."""
    E, tm = sizes.shape[0], tile_rows
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 1)
    stop = jnp.cumsum(tiles)                    # visits up to and with e
    v = jnp.arange(rows // tm + E, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(v[:, None] >= stop[None], axis=1), E - 1)
    tile = first[group] + v - (stop - tiles)[group]
    i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return Visits(i32(jnp.concatenate([starts[:1], ends])), i32(group),
                  i32(jnp.clip(tile, 0, rows // tm - 1)), i32(stop[-1:]))


def _mine(offsets_ref, group_ref, tile_ref, v, rows):
    """``[rows, 1]``: the tile's rows that are visit ``v``'s expert's."""
    g = group_ref[v]
    row = tile_ref[v] * rows + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])


def _dot(a, b, contract=((1,), (0,))):
    # f32 operands (the tests) keep f32 accuracy; bf16 is the MXU's own pass
    return lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if a.dtype == jnp.float32 else None)


def _keep(ref, at, mine, new):
    """Store the expert's rows of ``new``; the tile's other rows keep what
    the block holds (another expert's, or nothing yet)."""
    ref[at] = jnp.where(mine, new.astype(ref.dtype), ref[at])


def _swiglu(gate, up):
    return gate * jax.nn.sigmoid(gate) * up


def _grid_spec(grid, in_specs, out_specs, scratch_shapes=()):
    # offsets, group, tile, total: in SMEM before the grid starts
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=list(scratch_shapes))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 2,
                               vmem_limit_bytes=_VMEM_LIMIT)


# Index maps over (column tile j, visit v | offsets, group, tile, total):
# the visit's row tile against the whole width, against column tile ``j``,
# and the same of a gate / up pair
def _row_tile(j, v, offsets, group, tile, total):
    return tile[v], 0


def _out_tile(j, v, offsets, group, tile, total):
    return tile[v], j


def _pair_tile(j, v, offsets, group, tile, total):
    return 0, tile[v], j


# ... and the visit's expert's weights: column tile ``j`` of ``[E, K, M]``,
# row tile ``j`` of ``[E, M, K]``
def _expert_cols(j, v, offsets, group, tile, total):
    return group[v], 0, j


def _expert_rows(j, v, offsets, group, tile, total):
    return group[v], j, 0


# ---------------------------------------------------------------------------
# forward: gate, up, act = swiglu(xs wi[e])
# ---------------------------------------------------------------------------


def _gate_up_kernel(offsets, group, tile, total, xs_ref, wg_ref, wu_ref,
                    gu_ref, act_ref):
    mine = _mine(offsets, group, tile, pl.program_id(1), xs_ref.shape[0])
    xs = xs_ref[...]
    gate, up = _dot(xs, wg_ref[...]), _dot(xs, wu_ref[...])
    _keep(gu_ref, 0, mine, gate)
    _keep(gu_ref, 1, mine, up)
    _keep(act_ref, ..., mine, _swiglu(gate, up))


@functools.partial(jax.jit, static_argnames=("rows", "cols", "interpret"))
def _gate_up(xs, wi, walk: Visits, *, rows, cols, interpret):
    (N, H), F = xs.shape, wi.shape[2] // 2
    up_at = F // cols           # the up half's first column tile
    weight = lambda at: pl.BlockSpec(  # noqa: E731
        (None, H, cols), lambda j, v, o, g, t, n: (g[v], 0, at + j))
    return pl.pallas_call(
        _gate_up_kernel,
        grid_spec=_grid_spec(
            (F // cols, walk.total[0]),
            [pl.BlockSpec((rows, H), _row_tile),
             weight(0), weight(up_at)],
            [pl.BlockSpec((2, rows, cols), _pair_tile),
             pl.BlockSpec((rows, cols), _out_tile)]),
        out_shape=[jax.ShapeDtypeStruct((2, N, F), xs.dtype),
                   jax.ShapeDtypeStruct((N, F), xs.dtype)],
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_gate_up",
    )(*walk, xs, wi, wi)


# ---------------------------------------------------------------------------
# out[rows of e] = sum_p lhs[p] rhs[e]'s p-th part (or its transpose)
# ---------------------------------------------------------------------------


def _matmul_kernel(offsets, group, tile, total, lhs_ref, rhs_ref, out_ref, *,
                   transposed):
    mine = _mine(offsets, group, tile, pl.program_id(1), out_ref.shape[0])
    parts, _, depth = lhs_ref.shape
    acc = None
    for p in range(parts):
        if transposed:      # rhs [cols, parts * depth]: out = lhs rhs^T
            term = _dot(lhs_ref[p], rhs_ref[:, p * depth:(p + 1) * depth],
                        ((1,), (1,)))
        else:
            term = _dot(lhs_ref[p], rhs_ref[...])
        acc = term if acc is None else acc + term
    _keep(out_ref, ..., mine, acc)


@functools.partial(jax.jit, static_argnames=("transposed", "rows", "cols",
                                             "interpret"))
def _matmul(lhs, rhs, walk: Visits, *, transposed, rows, cols, interpret):
    """``lhs`` ``[P, N, K]``; ``rhs`` ``[E, K, M]`` with ``P = 1``, or
    ``[E, M, P*K]`` ``transposed``; ``[N, M]`` out in ``lhs``'s dtype."""
    P, N, K = lhs.shape
    M = rhs.shape[1] if transposed else rhs.shape[2]
    if transposed:
        rhs_spec = pl.BlockSpec((None, cols, P * K), _expert_rows)
    else:
        rhs_spec = pl.BlockSpec((None, K, cols), _expert_cols)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, transposed=transposed),
        grid_spec=_grid_spec(
            (M // cols, walk.total[0]),
            [pl.BlockSpec((P, rows, K),
                          lambda j, v, o, g, t, n: (0, t[v], 0)), rhs_spec],
            pl.BlockSpec((rows, cols), _out_tile)),
        out_shape=jax.ShapeDtypeStruct((N, M), lhs.dtype),
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_matmul",
    )(*walk, lhs, rhs)


# ---------------------------------------------------------------------------
# backward: d_gate, d_up, act from d_act = d_ys wo[e]^T and the saved gate, up
# ---------------------------------------------------------------------------


def _act_grad_kernel(offsets, group, tile, total, dys_ref, wo_ref, gu_ref,
                     dgu_ref, act_ref):
    mine = _mine(offsets, group, tile, pl.program_id(1), dys_ref.shape[0])
    d_act = _dot(dys_ref[...], wo_ref[...], ((1,), (1,)))
    gate = gu_ref[0].astype(jnp.float32)
    up = gu_ref[1].astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    _keep(dgu_ref, 0, mine, d_act * up * (sig + silu * (1.0 - sig)))
    _keep(dgu_ref, 1, mine, d_act * silu)
    _keep(act_ref, ..., mine, silu * up)


@functools.partial(jax.jit, static_argnames=("rows", "cols", "interpret"))
def _act_grad(d_ys, wo, gu, walk: Visits, *, rows, cols, interpret):
    (N, H), F = d_ys.shape, wo.shape[1]
    pair = pl.BlockSpec((2, rows, cols), _pair_tile)
    return pl.pallas_call(
        _act_grad_kernel,
        grid_spec=_grid_spec(
            (F // cols, walk.total[0]),
            [pl.BlockSpec((rows, H), _row_tile),
             pl.BlockSpec((None, cols, H), _expert_rows), pair],
            [pair,
             pl.BlockSpec((rows, cols), _out_tile)]),
        out_shape=[jax.ShapeDtypeStruct((2, N, F), d_ys.dtype),
                   jax.ShapeDtypeStruct((N, F), d_ys.dtype)],
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_act_grad",
    )(*walk, d_ys, wo, gu)


# ---------------------------------------------------------------------------
# backward: out[e] = lhs[rows of e]^T rhs[rows of e]
# ---------------------------------------------------------------------------


def _weight_grad_kernel(offsets, group, tile, total, lhs_ref, rhs_ref,
                        out_ref, acc):
    v = pl.program_id(1)
    g = group[v]
    mine = _mine(offsets, group, tile, v, lhs_ref.shape[0])

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    # both sides: 0 * NaN of a row that is not the expert's is NaN
    lhs = jnp.where(mine, lhs_ref[...], jnp.zeros_like(lhs_ref))
    rhs = jnp.where(mine, rhs_ref[...], jnp.zeros_like(rhs_ref))
    acc[...] += _dot(lhs, rhs, ((0,), (0,)))

    @pl.when((v == total[0] - 1)
             | (group[jnp.minimum(v + 1, total[0] - 1)] != g))
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "cols", "interpret"))
def _weight_grad(lhs, rhs, walk: Visits, *, rows, cols, interpret):
    """``lhs`` ``[N, K]``, ``rhs`` ``[P, N, M]`` -> ``[E, K, P*M]`` in their
    dtype: per expert ``lhs^T [rhs[0] | rhs[1] ...]``, ``K`` whole."""
    (N, K), (P, _, M) = lhs.shape, rhs.shape
    E, per = walk.offsets.shape[0] - 1, M // cols
    return pl.pallas_call(
        _weight_grad_kernel,
        grid_spec=_grid_spec(
            (P * per, walk.total[0]),
            [pl.BlockSpec((rows, K), _row_tile),
             pl.BlockSpec((None, rows, cols),
                          lambda j, v, o, g, t, n: (j // per, t[v], j % per))],
            pl.BlockSpec((None, K, cols), _expert_cols),
            [pltpu.VMEM((K, cols), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((E, K, P * M), lhs.dtype),
        compiler_params=_PARAMS, interpret=interpret,
        name="grouped_weight_grad",
    )(*walk, lhs, rhs)


# ---------------------------------------------------------------------------
# the differentiable feed-forward `RoutedExperts` calls
# ---------------------------------------------------------------------------


def _plan(xs, wi):
    (N, H), F = xs.shape, wi.shape[2] // 2
    tiles = _tiles(H, F, xs.dtype.itemsize)
    if tiles is None:
        raise ValueError(f"no tiles of H={H}, F={F} fit VMEM")
    return tiles, _block(N, _ROWS), _block(N, _ROWS_T)


@jax.custom_vjp
def feed_forward(xs, wi, wo, sizes):
    """``(silu(xs wi_gate[e]) * (xs wi_up[e])) wo[e]`` by sorted row:
    ``xs`` ``[N, H]``, rows sorted by expert, ``sizes`` ``[E]`` int32 rows an
    expert; ``wi`` ``[E, H, 2F]`` gate then up and ``wo`` ``[E, F, H]``, all
    in the compute dtype. ``[N, H]`` out; rows past ``sum(sizes)`` are NOT
    written and their gradient is not either."""
    return _forward(xs, wi, wo, sizes)[0]


def _forward(xs, wi, wo, sizes):
    tiles, rows, _ = _plan(xs, wi)
    walk = visits(sizes, xs.shape[0], rows)
    how = dict(rows=rows, interpret=_interpret())
    gu, act = _gate_up(xs, wi, walk, cols=tiles.gate_up, **how)
    ys = _matmul(act[None], wo, walk, transposed=False, cols=tiles.out, **how)
    return ys, (xs, wi, wo, gu, sizes)


def _backward(res, d_ys):
    xs, wi, wo, gu, sizes = res
    tiles, rows, rows_t = _plan(xs, wi)
    N = xs.shape[0]
    walk, walk_t = visits(sizes, N, rows), visits(sizes, N, rows_t)
    how = dict(rows=rows, interpret=_interpret())
    how_t = dict(rows=rows_t, interpret=_interpret())
    d_gu, act = _act_grad(d_ys, wo, gu, walk, cols=tiles.gate_up, **how)
    d_xs = _matmul(d_gu, wi, walk, transposed=True, cols=tiles.back, **how)
    d_wi = _weight_grad(xs, d_gu, walk_t, cols=tiles.d_wi, **how_t)
    d_wo = _weight_grad(act, d_ys[None], walk_t, cols=tiles.d_wo, **how_t)
    return d_xs, d_wi, d_wo, None


feed_forward.defvjp(_forward, _backward)
