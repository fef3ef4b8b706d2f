"""Kimi Delta Attention (KDA: Kimi Linear, arXiv:2510.26692): a delta rule
with a decay per key channel, by chunks, forward and backward: XLA's chunked
form, and on a TPU two Pallas kernels.

A head with keys of width ``K`` and values of width ``V`` carries a state
``S [K, V]``; ``g_t`` is the log-decay of each key channel, ``beta_t`` the
head's write strength:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t                                      S_{-1} = 0

With ``u_t = beta_t (v_t - (Diag(e^{g_t}) S_{t-1})^T k_t)`` this is
``S_t = Diag(e^{g_t}) S_{t-1} + k_t u_t^T``. Inside a chunk of ``C``
positions, ``G`` the running sum of ``g`` (inclusive, per channel) and
``S_0`` the state entering the chunk, the ``u`` of the chunk solve one
unit-lower-triangular system (the WY / UT transform):

    (I + StrictTril(diag(beta) A)) [W | U~] = diag(beta) [K e^G | V]
        A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}
    U     = U~ - W S_0
    O     = (Q e^G) S_0 + Tril(P) U,   P_ts = sum_c q_tc k_sc e^{G_tc - G_sc}
    S_C   = e^{G_C} S_0 - (K e^{G_C - G})^T W S_0 + (K e^{G_C - G})^T U~

so the chunks' ``A``, ``P``, ``W``, ``U~`` are batched matmuls and one
triangular solve, all chunks at once, and one `lax.scan` carries the state
over the ``S / C`` chunks.

The decays differ by channel, so ``e^{G_t - G_s}`` cannot leave the
channel sum as a ``[C, C]`` matrix (SSD's trick, `ops.ssd`): it is
factorised, ``e^{G_t - r} e^{r - G_s}``, inside the sum. The pairwise
``[C, C, K]`` decays are never formed. The reference point ``r`` is ``G``
at the middle of the rows' sub-chunk of `sub_chunk` positions, so that a
factor within the sub-chunk is at most ``e^{-lower_bound * sub / 2}`` either
way (``e^{+-40}`` at the bound -5 and 16 positions); a column of an earlier
sub-chunk takes ``e^{<= 0}``, and the columns of later ones are masked to
``-inf`` BEFORE the exponential. Every other factor is the exponential of a
non-positive difference. Why the middle and not the sub-chunk's start: from
there a row's factor goes down to ``e^-80``, and the backward pass's
products of it with small cotangents fall below float32's normal range,
which XLA flushes to zero (a thousandth of the gate's gradient was lost so
at sub-chunks of 16 on the CPU). A gate
below ``lower_bound`` could overflow, so the op refuses it: the result is
NaN throughout (a traced op cannot raise).

Precision: ``G``, every exponential, the triangular solve and the carried
state are float32; matmul operands are in the inputs' dtype (bf16 in the
benchmark's cell), accumulating in float32. The state's own decay is
applied elementwise in float32 in the scan; only the delta rule's
correction goes through a matmul.

The backward pass recomputes: the core sits under `jax.checkpoint`, so a
layer's residuals are the op's inputs alone, as in `ops.ssd` (that module's
docstring has the reasons).

The kernels (`kernel_applies`: a TPU, ``K = V`` whole 128-lane tiles,
chunks of `KERNEL_CHUNK`, bf16 or f32) compute the same algebra a chunk at
a time behind one `jax.custom_vjp`, on ``[B, S, H * K]`` views of the
inputs (no transposes) over a grid of (sequence, a few heads, chunk) whose
chunk axis runs in order. ``kda_fwd`` carries each head's state ``Sᵀ [V,
K]`` in f32 VMEM scratch from chunk to chunk and writes ``O``. ``kda_bwd``
walks a head's chunks twice: forward, keeping in VMEM the state entering
each chunk and the chunk's inverse ``(I + StrictTril(diag(beta) A))^-1``,
then backward, carrying ``dSᵀ`` and writing ``dq``, ``dk``, ``dv``, the
log-decay's gradient (the reverse running sum of ``dG``) and ``beta``'s.
Nothing is saved between them but the op's inputs. Inside a chunk ``G`` is
a log-step running sum, a sub-chunk's reference point a masked sublane
sum, and the sub-chunks' column factors ``[C, K]`` each live only in
VMEM; the inverse is blocked forward substitution by doubling in one-pass
bf16 products refined to f32 by two Newton steps (`_unit_lower_inverse`).
The precision is the XLA form's; f32 operands run at full precision, so
the f32 reference checks run the kernels too.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the most a sub-chunk may decay: its factors then lie within e^{+-40},
#: half of it, far inside float32's (and bfloat16's) normal range e^{+-87}
MAX_EXPONENT = 80.0


def sub_chunk(chunk: int, lower_bound: float) -> int:
    """Positions a reference point of the factorisation serves: the largest
    divisor of ``chunk`` whose worst decay, ``-lower_bound`` a position,
    stays within `MAX_EXPONENT` (16 at the bound -5)."""
    if lower_bound >= 0:
        raise ValueError(f"lower_bound {lower_bound} is no decay")
    return math.gcd(chunk, int(MAX_EXPONENT // -lower_bound))


def kda_chunked(q, k, v, log_alpha, beta, chunk: int, *, scale=None,
                lower_bound: float = -5.0, heads_at_once: int | None = None):
    """``o [B, S, H, V]`` of the recurrence above, in ``v``'s dtype.

    ``q``, ``k`` ``[B, S, H, K]`` (the model's l2-normed ones); ``v`` ``[B,
    S, H, V]``; ``log_alpha`` ``[B, S, H, K]`` the per-channel log-decay, in
    ``[lower_bound, 0]``; ``beta`` ``[B, S, H]``. ``S`` is a multiple of
    ``chunk``; ``scale`` defaults to ``K ** -0.5``. Where `kernel_applies`,
    the Pallas kernels run it; elsewhere XLA's chunked form, in which
    ``heads_at_once`` (a divisor of ``H``; None: all) runs the heads that
    many at a time, one after another (a `lax.map`): the backward pass's
    recomputed chunk matrices are then that share of the whole, the
    recurrence over chunks runs ``H / heads_at_once`` times. Differentiable
    in every array argument."""
    batch, seq, heads, width = q.shape
    if seq % chunk:
        raise ValueError(f"sequence {seq} is not a multiple of the chunk "
                         f"{chunk}")
    if k.shape != q.shape or log_alpha.shape != q.shape:
        raise ValueError(f"q {q.shape}, k {k.shape} and log_alpha "
                         f"{log_alpha.shape} differ")
    if v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]:
        raise ValueError(f"v {v.shape} or beta {beta.shape} do not match "
                         f"q {q.shape}")
    scale = float(width ** -0.5 if scale is None else scale)
    sub = sub_chunk(chunk, lower_bound)
    if (q.dtype == k.dtype == v.dtype
            and kernel_applies(q.shape, chunk, v.dtype,
                               value_width=v.shape[3],
                               lower_bound=lower_bound)):
        o = _scan_kernels(q, k, v, log_alpha.astype(jnp.float32),
                          beta.astype(jnp.float32), sub, scale)
        return jnp.where(jnp.any(log_alpha < lower_bound), jnp.nan,
                         o).astype(v.dtype)
    statics = (chunk, sub, scale, float(lower_bound))
    group = heads_at_once or heads
    if heads % group:
        raise ValueError(f"{group} heads at once do not divide {heads}")
    if group == heads:
        return _core(q, k, v, log_alpha, beta, *statics)

    def by_group(t):        # [B, S, H, ...] -> [H / group, B, S, group, ...]
        t = t.reshape(batch, seq, heads // group, group, *t.shape[3:])
        return jnp.moveaxis(t, 2, 0)

    out = lax.map(lambda a: _core(*a, *statics),
                  tuple(map(by_group, (q, k, v, log_alpha, beta))))
    return jnp.moveaxis(out, 0, 2).reshape(v.shape)


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7, 8))
def _core(q, k, v, log_alpha, beta, chunk, sub, scale, lower_bound):
    batch, seq, heads, width = q.shape
    chunks, blocks, dtype = seq // chunk, chunk // sub, v.dtype
    f32 = jnp.float32

    # [b, h, chunk n, position, ...]: a head's matrices in the minor dims
    def by_chunk(t):
        t = t.reshape(batch, chunks, chunk, heads, *t.shape[3:])
        return jnp.moveaxis(t, 3, 1)

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    Q, K, V = by_chunk(q), by_chunk(k), by_chunk(v)
    g = by_chunk(log_alpha.astype(f32))
    bet = by_chunk(beta.astype(f32))                        # [b, h, n, C]
    refused = jnp.any(g < lower_bound)
    G = jnp.cumsum(g, axis=3)                               # [b, h, n, C, K]
    last = G[:, :, :, -1]                                   # [b, h, n, K]

    # the factorised pairwise decays: a reference point a sub-chunk, G at
    # its middle position
    Gs = G.reshape(*G.shape[:3], blocks, sub, width)
    ref = Gs[:, :, :, :, (sub - 1) // 2]                    # [b,h,n,m,K]
    rows = jnp.exp(Gs - ref[:, :, :, :, None])              # e^{+-sub/2}
    Kr = K.reshape(Gs.shape).astype(f32) * rows
    Qr = Q.reshape(Gs.shape).astype(f32) * rows
    seen = (jnp.arange(chunk)[None, :] // sub
            <= jnp.arange(blocks)[:, None])                 # [m, C]
    cols = jnp.exp(jnp.where(seen[:, :, None],
                             ref[:, :, :, :, None] - G[:, :, :, None],
                             -jnp.inf))                     # e^{<= 5 sub/2}
    Kc = K[:, :, :, None].astype(f32) * cols                # [b,h,n,m,C,K]
    A = dot("bhnmik,bhnmjk->bhnmij", Kr, Kc).reshape(*G.shape[:3], chunk,
                                                       chunk)
    P = dot("bhnmik,bhnmjk->bhnmij", Qr, Kc).reshape(A.shape)
    t, s = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    A = jnp.where(t > s, bet[..., None] * A, 0.0)
    P = jnp.where(t >= s, P, 0.0).astype(dtype)

    # the WY / UT transform, float32
    rhs = bet[..., None] * jnp.concatenate(
        [K.astype(f32) * jnp.exp(G), V.astype(f32)], axis=-1)
    WU = lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                     unit_diagonal=True)
    # (W enters matmuls only: kept in their operand dtype)
    W, U = WU[..., :width].astype(dtype), WU[..., width:]

    # what a chunk hands on: S' = e^{G_C} S - Kend^T W S + Kend^T U~
    Kend = K.astype(f32) * jnp.exp(last[:, :, :, None] - G)
    correct = dot("bhnjk,bhnjl->bhnkl", Kend, W).astype(dtype)
    added = dot("bhnjk,bhnjv->bhnkv", Kend, U)              # [b,h,n,K,V]

    def carry(state, chunk_c):
        decay, correct_c, added_c = chunk_c
        nxt = (jnp.exp(decay)[..., None] * state
               - dot("bhkl,bhlv->bhkv", correct_c, state) + added_c)
        return nxt, state.astype(dtype)

    _, entering = lax.scan(
        carry, jnp.zeros(added.shape[:2] + added.shape[3:], f32),
        tuple(jnp.moveaxis(x, 2, 0) for x in (last, correct, added)))
    entering = jnp.moveaxis(entering, 0, 2)                 # [b,h,n,K,V]

    U = U - dot("bhnjk,bhnkv->bhnjv", W, entering)
    O = (dot("bhnik,bhnkv->bhniv", Q.astype(f32) * jnp.exp(G), entering)
         + dot("bhnij,bhnjv->bhniv", P, U))
    O = jnp.where(refused, jnp.nan, scale * O)
    return jnp.moveaxis(O, 1, 3).reshape(v.shape).astype(dtype)


# ---------------------------------------------------------------------------
# the Pallas TPU kernels
# ---------------------------------------------------------------------------

_LANES = 128
#: the chunk the kernels are written for
KERNEL_CHUNK = 64
#: heads a grid step works on, one after another in its body: 4 read the op
#: 12% faster than 1 forward + backward at the cell's shape (8: 4% more, for
#: twice the VMEM the backward kernel keeps)
_HEADS_PER_STEP = 4
#: what the backward kernel keeps in VMEM between its two passes
#: (`_kept_bytes`)
_KEPT_BUDGET = 48 * 1024 * 1024
#: VMEM a kernel may use besides that
_VMEM_MARGIN = 24 * 1024 * 1024

#: contracting dimensions of `_dot`: ``a b``, ``a bᵀ``, ``aᵀ b``
_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _heads_per_step(heads: int) -> int:
    return math.gcd(heads, _HEADS_PER_STEP)


def _kept_bytes(chunks: int, heads: int, width: int) -> int:
    """f32 bytes the backward kernel keeps of a grid step's heads: for each
    chunk the state entering it and its inverse."""
    return (4 * chunks * _heads_per_step(heads)
            * (width * width + KERNEL_CHUNK * KERNEL_CHUNK))


def kernel_applies(shape, chunk: int, dtype, *, value_width=None,
                   lower_bound: float = -5.0) -> bool:
    """Whether `kda_chunked` runs on the Pallas kernels: on a TPU (elsewhere
    Pallas' interpreter would run), ``q``'s ``shape`` ``[B, S, H, K]`` with
    ``K`` whole 128-lane tiles and the value width (``value_width``,
    default ``K``) equal to it, ``S`` whole chunks of `KERNEL_CHUNK`, bf16
    or f32 operands, sub-chunks of whole f32 sublane tiles, and what the
    backward kernel keeps within `_KEPT_BUDGET`."""
    batch, seq, heads, width = shape
    value = width if value_width is None else value_width
    return (not _interpret() and chunk == KERNEL_CHUNK and seq % chunk == 0
            and width % _LANES == 0 and value == width
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and lower_bound < 0 and sub_chunk(chunk, lower_bound) % 8 == 0
            and _kept_bytes(seq // chunk, heads, width) <= _KEPT_BUDGET)


def _dot(a, b, dims, dtype):
    """``a b``, ``a bᵀ`` or ``aᵀ b`` (`_NN`, `_NT`, `_TN`) with both operands
    in ``dtype``, accumulating in f32; f32 operands at full precision, bf16
    in one pass whatever the caller's default precision."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (((dims[0],), (dims[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
                   else lax.Precision.DEFAULT))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


# The kernels' masks are written with `lax` directly: `jnp.where` and integer
# floor division each trace as a nested jit of several operations, a few
# hundred in the kernels' bodies, which every run traces and lowers again
# during set-up.


def _where(mask, x, y):
    """``jnp.where`` of f32 values, as one select."""
    shape = jnp.broadcast_shapes(mask.shape, jnp.shape(x), jnp.shape(y))

    def full(a):
        return jnp.broadcast_to(jnp.asarray(a, jnp.float32), shape)

    return lax.select(jnp.broadcast_to(mask, shape), full(x), full(y))


def _over(index, size: int):
    """``index // size`` of a non-negative ``index`` and a power of two."""
    return lax.shift_right_logical(index, size.bit_length() - 1)


def _cumsum(x, reverse: bool = False):
    """Inclusive running sums down the rows of ``x`` (up them with
    ``reverse``): log2(rows) shifted adds."""
    size = x.shape[0]
    row = _iota(x.shape, 0)
    shift = 1
    while shift < size:
        if reverse:
            x = x + _where(row < size - shift,
                           pltpu.roll(x, size - shift, 0), 0.0)
        else:
            x = x + _where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _split_dot(a, b, dims):
    """An f32 product to about 16 bits in three one-pass bf16 matmuls: each
    operand as a bf16 head and the bf16 rounding of what is left."""
    bf16 = jnp.bfloat16

    def split(x):
        head = x.astype(bf16)
        return head, (x - head.astype(jnp.float32)).astype(bf16)

    (ah, al), (bh, bl) = split(a), split(b)
    return ((_dot(ah, bl, dims, bf16) + _dot(al, bh, dims, bf16))
            + _dot(ah, bh, dims, bf16))


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of a strictly lower ``[C, C]`` f32 ``n``, to f32.

    Blocked forward substitution by doubling: ``X``, the inverse of the
    diagonal blocks of ``b`` positions, becomes that of blocks of ``2b`` as
    ``X - X L X``, ``L`` the lower-left quarters of the ``2b`` blocks (the
    blocks of 2 are ``I - n`` there). Its entries never outgrow the
    inverse's own, so one-pass bf16 products give it to a few parts in a
    thousand whatever the keys (the same key throughout, ``beta = 1``: 5e-3).
    Two Newton steps, ``X + X (I - (I + n) X)``, square that error twice; the
    second residual is an f32 product, so the result is the f32 inverse
    (2e-8 of its largest entry in the case above)."""
    size = n.shape[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    t, s = _iota(n.shape, 0), _iota(n.shape, 1)
    eye = (t == s).astype(f32)
    x = eye - _where(_over(t, 2) == _over(s, 2), n, 0.0)
    block = 2
    while block < size:
        low = _where((_over(t, 2 * block) == _over(s, 2 * block))
                     & (_over(t, block) != _over(s, block)), n, 0.0)
        x = x - _dot(_dot(x, low, _NN, bf16), x, _NN, bf16)
        block *= 2
    m = eye + n
    x = x + _dot(x, eye - _split_dot(m, x, _NN), _NN, bf16)
    return x + _split_dot(x, eye - _dot(m, x, _NN, f32), _NN)


class _Decays(NamedTuple):
    """One chunk's decays, f32 (the module docstring's names)."""

    G: jax.Array        # [C, K] running sum of the log-decay
    eg: jax.Array       # e^G
    rows: jax.Array     # e^{G - r}, r the reference of the row's sub-chunk
    cols: tuple         # per sub-chunk b: e^{r_b - G}, 0 past sub-chunk b
    last: jax.Array     # [1, K] G at the chunk's end
    tail: jax.Array     # e^{G_C - G}


def _decays(g, sub):
    size, width = g.shape
    blocks = size // sub
    G = _cumsum(g)
    mid = _where(lax.bitwise_and(_iota(g.shape, 0), sub - 1)
                 == (sub - 1) // 2, G, 0.0)
    refs = jnp.sum(mid.reshape(blocks, sub, width), axis=1, keepdims=True)
    ref = jnp.broadcast_to(refs, (blocks, sub, width)).reshape(size, width)
    block = _over(_iota((size, 1), 0), sub)
    cols = tuple(jnp.exp(_where(block <= b, refs[b] - G, -jnp.inf))
                 for b in range(blocks))
    last = pltpu.roll(G, 1, 0)[:1]
    return _Decays(G, jnp.exp(G), jnp.exp(G - ref), cols, last,
                   jnp.exp(last - G))


def _pairs(x, kf, cols, sub, dtype):
    """``[C, C]``: ``sum_c x_tc k_sc e^{G_tc - G_sc}`` for ``x`` already
    times ``rows``, a row sub-chunk against the columns of its reference;
    columns of later sub-chunks read 0."""
    return jnp.concatenate(
        [_dot(x[b * sub:(b + 1) * sub], kf * col, _NT, dtype)
         for b, col in enumerate(cols)], axis=0)


def _solve(a_raw, bet, kf, eg, v):
    """``(I + StrictTril(diag(beta) A))^-1`` and ``[W | U~]``, f32."""
    t, s = _iota(a_raw.shape, 0), _iota(a_raw.shape, 1)
    inv = _unit_lower_inverse(_where(t > s, bet * a_raw, 0.0))
    rhs = bet * jnp.concatenate([kf * eg, v.astype(jnp.float32)], axis=1)
    return inv, _dot(inv, rhs, _NN, jnp.float32)


def _next_state(st, wu, kend, last, dtype):
    """``S' = e^{G_C} S - (Kend^T W) S + Kend^T U~``, transposed: the
    kernels carry ``Sᵀ [V, K]``, so the decay is a row broadcast."""
    width = kend.shape[1]
    correct_t = _dot(wu[:, :width], kend, _TN, dtype).astype(dtype)
    return (st * jnp.exp(last) - _dot(st, correct_t, _NN, dtype)
            + _dot(wu[:, width:], kend, _TN, dtype))


def _head_column(block, head):
    """Column ``head`` of a ``[C, H]`` block as ``[C, 1]``."""
    return jnp.sum(_where(_iota(block.shape, 1) == head, block, 0.0),
                   axis=1, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, *, sub,
                scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype, f32 = v_ref.dtype, jnp.float32
    heads, width = st_ref.shape[0], st_ref.shape[2]
    size = q_ref.shape[0]
    t, s = _iota((size, size), 0), _iota((size, size), 1)
    betas = b_ref[...]
    for j in range(heads):
        lanes = slice(j * width, (j + 1) * width)
        qf, kf = q_ref[:, lanes].astype(f32), k_ref[:, lanes].astype(f32)
        d = _decays(g_ref[:, lanes], sub)
        bet = _head_column(betas, pl.program_id(1) * heads + j)
        _, wu = _solve(_pairs(kf * d.rows, kf, d.cols, sub, dtype), bet, kf,
                       d.eg, v_ref[:, lanes])
        p = _where(t >= s, _pairs(qf * d.rows, kf, d.cols, sub, dtype), 0.0)
        st = st_ref[j]
        u = wu[:, width:] - _dot(wu[:, :width], st, _NT, dtype)
        o = _dot(qf * d.eg, st, _NT, dtype) + _dot(p, u, _NN, dtype)
        o_ref[:, lanes] = (scale * o).astype(o_ref.dtype)
        st_ref[j] = _next_state(st, wu, kf * d.tail, d.last, dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dg_ref, db_ref, states, invs, st_ref, ds_ref, *, sub,
                scale, chunks):
    """Grid steps ``0 … n-1`` carry the heads' states forward over the
    chunks and keep, for each chunk, the state entering it and the inverse;
    steps ``n … 2n-1`` walk the chunks backward, carrying ``dSᵀ``."""
    step = pl.program_id(2)
    dtype, f32 = v_ref.dtype, jnp.float32
    heads, width = st_ref.shape[0], st_ref.shape[2]
    size = q_ref.shape[0]
    t, s = _iota((size, size), 0), _iota((size, size), 1)
    first = pl.program_id(1) * heads        # (read outside the branches)
    betas = b_ref[...]

    def head(j):
        lanes = slice(j * width, (j + 1) * width)
        return lanes, _head_column(betas, first + j)

    @pl.when(step == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    @pl.when(step < chunks)
    def _():
        for j in range(heads):
            lanes, bet = head(j)
            kf = k_ref[:, lanes].astype(f32)
            d = _decays(g_ref[:, lanes], sub)
            inv, wu = _solve(_pairs(kf * d.rows, kf, d.cols, sub, dtype),
                             bet, kf, d.eg, v_ref[:, lanes])
            states[j, step], invs[j, step] = st_ref[j], inv
            st_ref[j] = _next_state(st_ref[j], wu, kf * d.tail, d.last,
                                    dtype)

    @pl.when(step == chunks)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(step >= chunks)
    def _():
        n = 2 * chunks - 1 - step
        for j in range(heads):
            lanes, bet = head(j)
            qf, kf = q_ref[:, lanes].astype(f32), k_ref[:, lanes].astype(f32)
            vf = v_ref[:, lanes].astype(f32)
            d = _decays(g_ref[:, lanes], sub)
            kr, qr = kf * d.rows, qf * d.rows
            a_raw = _pairs(kr, kf, d.cols, sub, dtype)
            p = _where(t >= s, _pairs(qr, kf, d.cols, sub, dtype), 0.0)
            st, inv = states[j, n], invs[j, n]      # S0ᵀ, the inverse
            wu = _dot(inv, bet * jnp.concatenate([kf * d.eg, vf], axis=1),
                      _NN, f32)
            w = wu[:, :width]
            dst = ds_ref[j]                         # dS1ᵀ
            do = scale * do_ref[:, lanes].astype(f32)

            # O = (Q e^G) S0 + P U,  U = U~ - W S0,
            # S1 = e^{G_C} S0 + Kend^T U
            qg, kend = qf * d.eg, kf * d.tail
            u = wu[:, width:] - _dot(w, st, _NT, dtype)
            dqg = _dot(do, st, _NN, dtype)
            dp = _where(t >= s, _dot(do, u, _NT, dtype), 0.0)
            du = _dot(p, do, _TN, dtype) + _dot(kend, dst, _NT, dtype)
            dkend = _dot(u, dst, _NN, dtype)
            decay = jnp.exp(d.last)
            ds_ref[j] = (dst * decay + _dot(do, qg, _TN, dtype)
                         - _dot(du, w, _TN, dtype))
            dlast = (decay * jnp.sum(st * dst, axis=0, keepdims=True)
                     + jnp.sum(dkend * kend, axis=0, keepdims=True))

            # the solve: [W | U~] = T diag(beta) [K e^G | V];  dU~ = dU
            dwu = jnp.concatenate([-_dot(du, st, _NN, dtype), du], axis=1)
            drhs = _dot(inv, dwu, _TN, f32)
            da = _where(t > s, -_dot(drhs, wu, _NT, f32), 0.0)
            drw, dru = drhs[:, :width], drhs[:, width:]
            dbet = (jnp.sum(da * a_raw, axis=1, keepdims=True)
                    + jnp.sum(drw * kf * d.eg, axis=1, keepdims=True)
                    + jnp.sum(dru * vf, axis=1, keepdims=True))
            da = bet * da

            # the factorised pairs, a row sub-chunk b against its columns
            dkr = jnp.concatenate(
                [_dot(da[b * sub:(b + 1) * sub], kf * col, _NN, dtype)
                 for b, col in enumerate(d.cols)], axis=0)
            dqr = jnp.concatenate(
                [_dot(dp[b * sub:(b + 1) * sub], kf * col, _NN, dtype)
                 for b, col in enumerate(d.cols)], axis=0)
            dk = dkr * d.rows + drw * bet * d.eg + dkend * d.tail
            dG = (dkr * kr + dqr * qr + drw * bet * kf * d.eg + dqg * qg
                  - dkend * kend)
            for b, col in enumerate(d.cols):
                rows = slice(b * sub, (b + 1) * sub)
                dkc = (_dot(da[rows], kr[rows], _TN, dtype)
                       + _dot(dp[rows], qr[rows], _TN, dtype))
                dk = dk + dkc * col
                dG = dG - dkc * kf * col

            dq_ref[:, lanes] = (dqr * d.rows + dqg * d.eg).astype(
                dq_ref.dtype)
            dk_ref[:, lanes] = dk.astype(dk_ref.dtype)
            dv_ref[:, lanes] = (bet * dru).astype(dv_ref.dtype)
            dg_ref[:, lanes] = _cumsum(dG, reverse=True) + dlast
            db_ref[j] = jnp.sum(_where(t == s, dbet, 0.0), axis=0,
                                keepdims=True)


def _params(kept: int = 0):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_MARGIN + kept)


def _spec(q, chunk_of):
    """Blocks over ``[B, S, H * K]`` views: a grid step's heads at one
    chunk, ``[C, heads * K]``, 128-lane aligned, with no transposes."""
    lanes = _heads_per_step(q.shape[2]) * q.shape[3]
    return pl.BlockSpec((None, KERNEL_CHUNK, lanes),
                        lambda b, h, i: (b, chunk_of(i), h))


def _beta_spec(q, chunk_of):
    """``beta``'s block: one chunk of every head, ``[C, H]``."""
    return pl.BlockSpec((None, KERNEL_CHUNK, q.shape[2]),
                        lambda b, h, i: (b, chunk_of(i), 0))


def _flat(t):
    return t.reshape(*t.shape[:2], -1)


def _grid(q, passes: int = 1):
    batch, seq, heads, _ = q.shape
    return (batch, heads // _heads_per_step(heads),
            passes * seq // KERNEL_CHUNK)


#: The two calls sit behind jits of their own: a step's six layers share one
#: trace and one lowering of each kernel, where each call site would trace
#: and lower it again in every run (45 s of the cell's cached set-up).
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward_call(q, k, v, g, beta, sub, scale):
    width = q.shape[3]

    def same(i):
        return i

    spec = _spec(q, same)
    o = pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub, scale=scale),
        grid=_grid(q),
        in_specs=[spec, spec, spec, spec, _beta_spec(q, same)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(_flat(v).shape, v.dtype),
        scratch_shapes=[pltpu.VMEM(
            (_heads_per_step(q.shape[2]), width, width), jnp.float32)],
        compiler_params=_params(), interpret=_interpret(), name="kda_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta)
    return o.reshape(v.shape)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _backward_call(q, k, v, g, beta, do, sub, scale):
    batch, seq, heads, width = q.shape
    size, step_heads = KERNEL_CHUNK, _heads_per_step(heads)
    chunks = seq // size

    def forward(i):         # the state pass, then the chunks backward
        return jnp.minimum(i, 2 * chunks - 1 - i)

    def backward(i):        # the first chunk of the backward walk meanwhile
        return jnp.minimum(chunks - 1, 2 * chunks - 1 - i)

    f32 = jnp.float32
    ahead, behind = _spec(q, forward), _spec(q, backward)
    dq, dk, dv, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, scale=scale, chunks=chunks),
        grid=_grid(q, passes=2),
        in_specs=[behind, ahead, ahead, ahead, _beta_spec(q, forward),
                  behind],
        out_specs=[behind, behind, behind, behind,
                   pl.BlockSpec((None, step_heads, None, 1, size),
                                lambda b, h, i: (b, h, backward(i), 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(_flat(q).shape, q.dtype),
                   jax.ShapeDtypeStruct(_flat(k).shape, k.dtype),
                   jax.ShapeDtypeStruct(_flat(v).shape, v.dtype),
                   jax.ShapeDtypeStruct(_flat(g).shape, f32),
                   jax.ShapeDtypeStruct((batch, heads, chunks, 1, size),
                                        f32)],
        scratch_shapes=[
            pltpu.VMEM((step_heads, chunks, width, width), f32),
            pltpu.VMEM((step_heads, chunks, size, size), f32),
            pltpu.VMEM((step_heads, width, width), f32),
            pltpu.VMEM((step_heads, width, width), f32)],
        compiler_params=_params(_kept_bytes(chunks, heads, width)),
        interpret=_interpret(), name="kda_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta, _flat(do))
    db = jnp.moveaxis(db.reshape(batch, heads, seq), 1, 2)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), db)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(q, k, v, g, beta, sub, scale):
    """``O`` of `_core` (without the refusal) on the kernels: ``g`` and
    ``beta`` f32, ``q``, ``k``, ``v`` of one dtype and width."""
    return _forward_call(q, k, v, g, beta, sub, scale)


def _scan_kernels_fwd(q, k, v, g, beta, sub, scale):
    return _forward_call(q, k, v, g, beta, sub, scale), (q, k, v, g, beta)


def _scan_kernels_bwd(sub, scale, residuals, do):
    return _backward_call(*residuals, do, sub, scale)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)
