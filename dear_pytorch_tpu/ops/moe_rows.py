"""Row movement of the dropless routed experts as Pallas TPU kernels.

`parallel.ep.RoutedExperts` moves rows between token order ``[T, H]`` and
expert-sorted order ``[T*k, H]`` four times a layer (forward and backward of
the spread and of the combine). The sorted buffers are ``T*k`` rows whatever
the routing, because the layer is dropless, but only ``count = sum(sizes)``
of them belong to an expert this chip holds (1/8 of them in GLM-4.7-Flash's
cell, 1/4 in LFM2-8B-A1B's). XLA's gather has a static shape and moves them
all; these kernels read ``count`` on the device and stop there.

Two bodies, each the other's gradient:

`spread_rows` (sorted domain, a prefix): ``out[i] = src[row[i]]`` for ``i <
count``; rows ``>= count`` are not written and hold whatever the buffer held.
The grid is the TRACED ``ceil(count / R)``: blocks of absent experts' rows
are never visited. A block brings its ``R`` rows from HBM by one DMA a row,
all in flight together. Mosaic for v5e refuses a one-row slice of a tiled
buffer (``Slice shape along dimension 0 must be aligned to tiling (8), but
is 1``, in HBM as in VMEM, u32 and f32 as well as bf16), so the source is
handed over as ``[T, H/128, 128]`` float32 — a row is then whole ``(8, 128)``
tiles behind an untiled leading index — and the block's ``(R, 128)`` column
chunks are read back out of the landing buffer with a sublane stride and cast
to the output's dtype (exact for bf16: the f32 came from bf16). The source is
in token order, ``T`` rows: turning it into that form costs a pass over
``T`` rows, not ``T*k``. Optionally each row is scaled by a per-row f32
factor before the cast (the combine's gradient for ``ys``: ``w * g``), and
the row-wise dot with another sorted buffer is a second output (the
combine's gradient for the weights).

`combine_rows` (token domain, a masked weighted sum): ``out[t] = sum_j
w[t, j] * src[pos[t, j]]`` over the slots ``j`` whose expert is held, in
f32, written once as ``[T, H]``. Here the source is the ``T*k``-row sorted
buffer in its native layout, which no DMA can address by row. But the sort
is stable, so the rows one expert works on for a BLOCK of tokens are one
contiguous range of the sorted buffer (`combine_plan`): a block of ``B``
tokens brings each held expert's range in 16-row aligned chunks into one
VMEM buffer, and the weighted sum is a matmul of that buffer with a ``[B,
rows]`` selection matrix holding ``w[t, j]`` at column ``pos[t, j]`` (built
in-kernel from iotas). f32 weights against bf16 rows go through the MXU as
three bf16 pieces (8 + 8 + 8 mantissa bits: every product exact, f32
accumulation); f32 rows at ``Precision.HIGHEST``. The at most ``k`` terms
of a token are therefore added in f32 in sorted-row order, not slot order.
Rows of the buffer no (token, slot) of the block points at (alignment slop:
other tokens' rows, or past ``count``: whatever the buffer held) are zeroed
before the matmul, so nothing of them reaches a result.

No ``[T, k, H]`` intermediate exists in either direction, bytes moved are
proportional to ``count`` (plus alignment slop in the combine), and neither
kernel scatters.

Off the TPU everything runs under ``interpret=True`` (tests); the model
takes the kernels only on a TPU (`applies`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: sorted rows a grid step of `spread_rows` brings (one DMA each)
_ROWS = 128
#: tokens a grid step of `combine_rows` sums
_TOKENS = 256
#: rows of one aligned chunk DMA of `combine_rows` (a packed bf16 tile)
_CHUNK = 16
#: contraction depth of one selection matmul
_DEPTH = 128

_VMEM_LIMIT = 64 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def applies(tokens: int, top_k: int, width: int) -> bool:
    """Whether `RoutedExperts` moves its rows with these kernels: on a TPU
    (elsewhere Pallas' interpreter would run), rows of whole 128-lane tiles,
    and token and row counts the blocks divide."""
    return (not _interpret() and width % _LANES == 0
            and tokens % 8 == 0 and (tokens * top_k) % _CHUNK == 0)


def _block(n: int, most: int) -> int:
    """The largest power-of-two block <= ``most`` that divides ``n``."""
    b = most
    while n % b:
        b //= 2
    return b


def _eye(n: int):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


# ---------------------------------------------------------------------------
# spread: out[i] = src[row[i]], i < count
# ---------------------------------------------------------------------------


def _spread_kernel(rows_ref, count_ref, src_ref, *refs, rows, chunks,
                   scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    b = pl.program_id(0)
    live = jnp.clip(count_ref[0] - b * rows, 0, rows)

    def landing(r):
        return buf.at[pl.ds(pl.multiple_of(r * chunks, chunks), chunks)]

    def issue(r, carry):
        pltpu.make_async_copy(src_ref.at[rows_ref[b * rows + r]],
                              landing(r), sem).start()
        return carry

    def wait(r, carry):
        pltpu.make_async_copy(src_ref.at[0], landing(r), sem).wait()
        return carry

    lax.fori_loop(0, live, issue, 0)
    lax.fori_loop(0, live, wait, 0)

    if scaled:      # the block's factors arrive along lanes: make a column
        scale = jnp.sum(jnp.where(_eye(rows), scale_ref[0], 0.0), axis=1,
                        keepdims=True)
    dot = jnp.zeros((rows, 1), jnp.float32)
    for c in range(chunks):
        cols = slice(c * _LANES, (c + 1) * _LANES)
        part = buf[pl.ds(c, rows, stride=chunks), :]            # (R, 128) f32
        if dotted:
            dot += jnp.sum(part * other_ref[:, cols].astype(jnp.float32),
                           axis=1, keepdims=True)
        if scaled:
            part = part * scale
        out_ref[:, cols] = part.astype(out_ref.dtype)
    if dotted:      # and leave along lanes
        dot_ref[0] = jnp.sum(jnp.where(_eye(rows), dot, 0.0), axis=0,
                             keepdims=True)


def spread_rows(src, row, count, *, scale=None, dot_with=None, dtype=None):
    """``out[i] = src[row[i]]`` for ``i < count``: ``src`` ``[T, H]``,
    ``row`` ``[N]`` int32, ``count`` an int32 scalar on the device; ``out``
    is ``[N, H]`` in ``dtype`` (``src``'s) and its rows ``>= count`` are NOT
    written. ``scale`` ``[N]`` f32 multiplies row ``i`` in f32 before the
    cast; with ``dot_with`` ``[N, H]`` the result is ``(out, dot)``, ``dot[i]
    = <src[row[i]], dot_with[i]>`` in f32 (the row before scaling), again
    for ``i < count`` only."""
    return _spread_call(src, row, count, scale, dot_with,
                        dtype=jnp.dtype(dtype or src.dtype),
                        rows=_block(row.shape[0], _ROWS),
                        interpret=_interpret())


# (an inner jit: the expert layers of a step share one trace and one Mosaic
# lowering of each body instead of paying both a call site; the block
# constants and the interpreter are static, so a test or `moe_rows_ab.py`
# that patches them gets a trace of its own)
@functools.partial(jax.jit, static_argnames=("dtype", "rows", "interpret"))
def _spread_call(src, row, count, scale, dot_with, *, dtype, rows, interpret):
    (T, H), N = src.shape, row.shape[0]
    R, C = rows, H // _LANES
    scaled, dotted = scale is not None, dot_with is not None
    blocks = (jnp.asarray(count, jnp.int32) + R - 1) // R
    lanes_in = pl.BlockSpec((1, 1, R), lambda b, *_: (b, 0, 0))
    block = pl.BlockSpec((R, H), lambda b, *_: (b, 0))
    operands = [src.astype(jnp.float32).reshape(T, C, _LANES)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [jax.ShapeDtypeStruct((N, H), dtype)]
    out_specs = [block]
    if scaled:
        operands.append(scale.astype(jnp.float32).reshape(N // R, 1, R))
        in_specs.append(lanes_in)
    if dotted:
        operands.append(dot_with)
        in_specs.append(block)
        out_shape.append(jax.ShapeDtypeStruct((N // R, 1, R), jnp.float32))
        out_specs.append(lanes_in)
    got = pl.pallas_call(
        functools.partial(_spread_kernel, rows=R, chunks=C, scaled=scaled,
                          dotted=dotted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((R * C, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_spread_rows",
    )(row.astype(jnp.int32), jnp.asarray(count, jnp.int32).reshape(1),
      *operands)
    return (got[0], got[1].reshape(N)) if dotted else got[0]


# ---------------------------------------------------------------------------
# combine: out[t] = sum_j w[t, j] * src[pos[t, j]], held slots only
# ---------------------------------------------------------------------------


class CombinePlan(NamedTuple):
    """Where a block of tokens finds its rows (`combine_plan`)."""

    source: jax.Array   # [blocks * S] the sorted buffer's aligned chunk that
    #                     fills chunk ``s`` of the block's VMEM buffer
    filled: jax.Array   # [blocks] chunks the block brings
    bounds: jax.Array   # [blocks, 1, 2E] per expert, the buffer rows that are
    #                     the block's own: [lo_e, hi_e), all lo then all hi
    pos: jax.Array      # [T, k] the buffer row of (t, j); -1: absent expert


def _plan_chunks(tokens: int, top_k: int, experts: int) -> int:
    """Chunks a block's buffer must hold: every assignment of the block
    held, plus each expert's range misaligned at both ends."""
    return tokens * top_k // _CHUNK + 2 * experts


def combine_plan(group, inverse, sizes) -> CombinePlan:
    """The plan of `combine_rows` from the dispatch's own arrays: ``group``
    ``[T, k]`` the held expert of each (token, slot), ``E`` for an absent
    one; ``inverse`` ``[T*k]`` the sorted row of each assignment under the
    STABLE sort by group; ``sizes`` ``[E]``. Stability is what makes it
    work: expert ``e``'s rows for tokens ``[t0, t1)`` are the contiguous
    range that starts at ``offset[e] + #(assignments to e before t0)``."""
    return _plan_call(group, inverse, sizes,
                      tokens=_block(group.shape[0], _TOKENS))


@functools.partial(jax.jit, static_argnames=("tokens",))
def _plan_call(group, inverse, sizes, *, tokens):
    (T, k), E, B = group.shape, sizes.shape[0], tokens
    hot = group.reshape(T // B, B * k, 1) == jnp.arange(E)
    n = jnp.sum(hot, axis=1, dtype=jnp.int32)                   # [blocks, E]
    start = (jnp.cumsum(sizes) - sizes)[None] + jnp.cumsum(n, axis=0) - n
    first = start // _CHUNK                     # in chunks of the sorted rows
    chunks = jnp.where(n > 0, (start + n + _CHUNK - 1) // _CHUNK - first, 0)
    # the experts' chunks lie one after another in the block's buffer
    before = jnp.cumsum(chunks, axis=1) - chunks
    shift = _CHUNK * (before - first)           # sorted row -> buffer row
    slot = jnp.arange(_plan_chunks(B, k, E))[None, :, None]
    owner = jnp.minimum(jnp.sum(slot >= (before + chunks)[:, None], axis=-1),
                        E - 1)                                  # [blocks, S]
    source = slot[..., 0] + jnp.take_along_axis(first - before, owner, axis=1)
    pos = inverse.reshape(T, k) + jnp.take_along_axis(
        jnp.repeat(shift, B, axis=0), jnp.minimum(group, E - 1), axis=1)
    i32 = lambda a: a.astype(jnp.int32)  # noqa: E731
    return CombinePlan(
        i32(jnp.clip(source, 0, T * k // _CHUNK - 1)).reshape(-1),
        i32(jnp.sum(chunks, axis=1)),
        i32(jnp.concatenate([start + shift, start + n + shift],
                            axis=1))[:, None],
        i32(jnp.where(group < E, pos, -1)))


def _combine_kernel(source_ref, filled_ref, src_ref, bounds_ref, pos_ref,
                    *refs, experts, slots, weighted, depth, stride):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    out_ref, buf, acc, sem = refs
    B = out_ref.shape[0]
    filled = filled_ref[pl.program_id(0)]
    at = pl.program_id(0) * stride      # this block's chunks in ``source``

    def chunk_of(ref, i):
        return ref.at[pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK)]

    def issue(c, carry):
        pltpu.make_async_copy(chunk_of(src_ref, source_ref[at + c]),
                              chunk_of(buf, c), sem).start()
        return carry

    def wait(c, carry):
        pltpu.make_async_copy(chunk_of(src_ref, 0), chunk_of(buf, c),
                              sem).wait()
        return carry

    lax.fori_loop(0, filled, issue, 0)
    lax.fori_loop(0, filled, wait, 0)

    acc[...] = jnp.zeros_like(acc)
    pos = pos_ref[...]
    w = w_ref[...] if weighted else None
    # +1 on an expert's ``lo`` lane, -1 on its ``hi`` lane: a buffer row is
    # some expert's own where more ranges have begun than ended
    edge = jnp.where(lax.broadcasted_iota(
        jnp.int32, (1, 2 * experts), 1) < experts, 1, -1)

    def slab(d, carry):
        base = pl.multiple_of(d * depth, depth)
        rows = buf[pl.ds(base, depth), :]
        # rows no (token, slot) of this block points at never reach a sum
        at_row = base + lax.broadcasted_iota(jnp.int32, (depth, 1), 0)
        own = jnp.sum(jnp.where(at_row >= bounds_ref[0], edge, 0), axis=1,
                      keepdims=True) > 0
        rows = jnp.where(own, rows, jnp.zeros_like(rows))
        col = base + lax.broadcasted_iota(jnp.int32, (B, depth), 1)
        pick = jnp.zeros((B, depth), jnp.float32)
        for j in range(slots):
            pick += jnp.where(pos[:, j:j + 1] == col,
                              w[:, j:j + 1] if weighted else 1.0, 0.0)
        if rows.dtype == jnp.float32:
            acc[...] += jnp.dot(pick, rows, precision=lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
            return carry
        # f32 weights against bf16 rows: 8 + 8 + 8 mantissa bits, so each
        # piece's products are exact and the MXU accumulates them in f32
        for _ in range(3 if weighted else 1):
            piece = pick.astype(rows.dtype)
            acc[...] += jnp.dot(piece, rows, precision=lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
            pick = pick - piece.astype(jnp.float32)
        return carry

    lax.fori_loop(0, lax.div(filled * _CHUNK + depth - 1, depth), slab, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def combine_rows(src, plan: CombinePlan, weights: Optional[jax.Array] = None,
                 dtype=None):
    """``out[t] = sum_j weights[t, j] * src[sorted row of (t, j)]`` over the
    slots whose expert is held (``plan.pos >= 0``), in f32, cast to ``dtype``
    (``src``'s): ``src`` ``[N, H]`` sorted rows, ``weights`` ``[T, k]`` f32 or
    ``None`` for ones. A token none of whose experts is held reads 0."""
    return _combine_call(src, plan, weights,
                         dtype=jnp.dtype(dtype or src.dtype),
                         tokens=_block(plan.pos.shape[0], _TOKENS),
                         depth=_DEPTH, interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("dtype", "tokens", "depth", "interpret"))
def _combine_call(src, plan, weights, *, dtype, tokens, depth, interpret):
    (N, H), (T, k) = src.shape, plan.pos.shape
    B, E = tokens, plan.bounds.shape[-1] // 2
    stride = _plan_chunks(B, k, E)
    rows = -(-stride * _CHUNK // depth) * depth     # the buffer: whole slabs
    weighted = weights is not None
    slots_in = pl.BlockSpec((B, k), lambda b, *_: (b, 0))
    operands = [src, plan.bounds, plan.pos]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, 1, 2 * E), lambda b, *_: (b, 0, 0)),
                slots_in]
    if weighted:
        operands.append(weights.astype(jnp.float32))
        in_specs.append(slots_in)
    return pl.pallas_call(
        functools.partial(_combine_kernel, experts=E, slots=k,
                          weighted=weighted, depth=depth, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(T // B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((B, H), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((rows, H), src.dtype),
                            pltpu.VMEM((B, H), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((T, H), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_combine_rows",
    )(plan.source, plan.filled, *operands)


# ---------------------------------------------------------------------------
# the differentiable pair `RoutedExperts` calls
# ---------------------------------------------------------------------------


class Dispatch(NamedTuple):
    """One layer's routing as the kernels read it (`dispatch`)."""

    row: jax.Array      # [T*k] the token of each sorted row
    count: jax.Array    # [] rows some held expert works on
    order: jax.Array    # [T*k] the (token, slot) assignment of a sorted row
    inverse: jax.Array  # [T*k] the sorted row of an assignment
    plan: CombinePlan


def dispatch(group, order, inverse, sizes) -> Dispatch:
    """``group`` ``[T, k]`` (``E``: absent), ``order`` its stable argsort
    (flattened), ``inverse`` that permutation's inverse, ``sizes`` ``[E]``."""
    return Dispatch(order // group.shape[1], jnp.sum(sizes), order, inverse,
                    combine_plan(group, inverse, sizes))


@jax.custom_vjp
def spread(x, d: Dispatch):
    """``x`` ``[T, H]`` in sorted order ``[T*k, H]``, the held experts'
    rows only; the gradient sums a token's held slots (`combine_rows`)."""
    return spread_rows(x, d.row, d.count)


def _spread_fwd(x, d):
    return spread(x, d), d


def _spread_bwd(d, g):
    return combine_rows(g, d.plan), None


spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(ys, weights, d: Dispatch, dtype):
    """``sum_j weights[t, j] * ys[sorted row of (t, j)]`` over held slots,
    f32, as ``dtype`` ``[T, H]``. The gradients are the spread kernel's two
    outputs: ``w * g`` by row for ``ys`` (rows of absent experts not
    written), the row-wise ``<g, ys>`` for the weights."""
    return combine_rows(ys, d.plan, weights, dtype=dtype)


def _combine_fwd(ys, weights, d, dtype):
    return combine(ys, weights, d, dtype), (ys, weights, d)


def _combine_bwd(dtype, res, g):
    ys, weights, d = res
    dys, dot = spread_rows(g, d.row, d.count, dot_with=ys, dtype=ys.dtype,
                           scale=weights.reshape(-1)[d.order])
    dw = jnp.where(d.plan.pos >= 0, dot[d.inverse].reshape(weights.shape), 0)
    return dys, dw.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)
