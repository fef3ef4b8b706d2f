"""Flash attention as Pallas TPU kernels (forward + backward).

The hot op of every transformer in the zoo: the whole
score-softmax-weighted-sum pipeline stays in VMEM per (query-block,
key-block) tile, the S×S score matrix is never materialized in HBM
(memory O(S·D) instead of O(S²)), and the MXU sees back-to-back
[bq,W]×[W,bk] / [bq,bk]×[bk,W] matmuls (Dao et al. 2022, blockwise online
softmax — same math as `parallel.ring_attention`, which distributes ACROSS
chips what this kernel tiles WITHIN one).

STATUS (PR 32): on the default training path of `GptBlock`, `GlmBlock` and
`BertSelfAttention` wherever `models.gpt.flash_core_applies` (a TPU, no
mask or the key-padding mask, S a multiple of 128 from the minimum
`models.gpt.FLASH_MIN_SEQ` gives for (causal, dropout live)); all four
cells of BENCHMARK.json run it (24 / 24 / 12 `tpu_custom_call`s a step in
the GPT and GLM cells, 48 with dropout in BERT-Large's). Measured on a v5e
with `python scripts/flash_ab.py`, bf16, one layer, forward / forward +
backward: `--causal` at (16, 1024, 12, 64) 0.78 / 1.96 ms against XLA's
dense program at 1.95 / 5.86 and the Pallas kernel JAX ships at 1.03 /
5.76 (PR 30); `--kv-mask --dropout 0.1` at BERT-Large's (16, 512, 16, 64)
0.57 / 1.36 ms against the dense program with its threefry masks at 1.82 /
4.34 (PR 32; 0.42 / 1.10 without dropout). PERF.md §6 and docs/KERNELS.md
have the tables. tests/test_chip_compile.py keeps the v5e compiles in
tier-1; `chip_smoke.py` checks values, and the mask applied, on the chip.

Attention-probabilities dropout lives INSIDE the kernels (PR 32): the keep
decision of a score is a counter-based hash of (two seed words from the
layer's dropout key, batch row, head, absolute query row, absolute key
column), computed on the strip a kernel holds and, by the same function,
on the whole ``[B, H, Sq, Sk]`` by `dropout_keep_mask`, so a dense program
reproduces the kernels' mask exactly, on the CPU and on the chip. The row
sum and the saved lse come from the undropped probabilities; the backward
kernel rebuilds the mask; no S² tensor is kept. Without a key or at rate
0 the kernels hold no dropout code (no operand, no hash, no select). The
ring's pair kernels (`flash_pair_*`) have no dropout path.

Layout (what makes it fast on a v5e, whose MXU and vector lanes are 128
wide while a head is 64): the kernels read q, k, v as ``[B, S, H·D]`` —
the projections' own layout, a free reshape of ``[B, S, H, D]`` — in
blocks of ``(1, block, W)`` with ``W`` = 128 lanes = ``G`` = 128/D heads.
No ``[B,S,H,D] -> [BH,S,D]`` transposes surround the call, no 64-wide
minor dimension is padded to 128 in HBM, every load and store is
lane-dense. Inside a block the heads are told apart by zeroing the other
heads' lanes of the ROW-side operand (q in the forward and dq kernels,
k and v in the dkv kernel): ``(q ⊙ lanes_g) kᵀ`` contracts 128 lanes and
is exactly head g's ``q_g k_gᵀ``, at the MXU cost the 64-deep contraction
had anyway (depth is free up to 128); ``p_g v`` yields head g's output in
head g's lanes (the others are dropped at the flush). ``D`` a multiple of
128 is one head a block; a ``H·D`` that 128 does not divide is one block.

Grouped-query attention (PR 35): k and v may hold fewer heads than q,
``[B, S, H_kv·D]``. The grid gains a dimension, (B, K/V block, q sub-block,
Sq/bq, Sk/bk): a step holds one 128-lane K/V block and one of the ``H/H_kv``
128-lane blocks of q its heads serve, and the bodies are the equal-heads
ones but for a lane rotation that brings a Q head to its K/V head's lanes
(and back at a flush). dK/dV accumulate in VMEM over a K/V block's
sub-blocks too and leave the kernel summed over each group; no repeat of K
and V stands before the call. With equal heads none of it is traced: the
Mosaic modules are the ones the kernels had before (docs/KERNELS.md).

Operands go to the MXU in their input dtype with f32 accumulation (bf16
in, bf16 probabilities for the second matmul, as the dense path does);
f32 inputs stay f32 at full precision. The softmax is f32 throughout.

Backward is the standard flash recomputation: forward saves only the
softmax log-sum-exp per row and the P-tiles are rebuilt on the fly.
`flash_attention`'s gradient is ONE fused kernel (5 matmuls and one exp a
tile; dK/dV of the whole key sequence accumulate in VMEM; ``pᵀ do`` and
``dsᵀ q`` contract the tile's rows, which costs an XLU transpose of the
left operand and was measured worth it: 1.96 against 2.30 ms a layer).
Ring attention needs dQ and dK/dV of one (q-block, k-block) pair apart:
`flash_pair_dq` / `flash_pair_dkv` are two kernels (7 matmuls, 2 exps);
the dkv one works on the TRANSPOSED tile (``sᵀ = k qᵀ``), so its matmuls
are all plain and the per-row statistics broadcast along sublanes.

Row statistics (lse, delta, the key-validity mask) travel with rows along
LANES — ``[B, H/G, G, S]`` f32, 4 bytes a row in HBM (a lane-replicated
``[.., S, 128]`` form would be 100 MB a layer at GPT-2's shape). The dkv
kernel uses them as they are; the forward and dq kernels turn a block of
them into the lane-replicated ``(block, 128)`` column form once per query
block (one XLU transpose, not one per tile) and keep that in VMEM scratch.

Kernel structure: the reduction over key/query blocks is a GRID dimension,
not an in-kernel loop. The innermost grid dim is declared ``arbitrary``
(sequential), the online softmax / gradient accumulators live in VMEM
scratch that persists across those steps, and ``pl.when`` gates the init,
the causal skip and the flush; Mosaic double-buffers each block's DMA
behind the previous tile's compute. Blocks are large (1024 rows: S=1024 is
one grid step a head group) because a tile's cost was measured to be mostly
per tile and per row, not per score; inside, a tile is worked through in
256-row strips, each against the key columns it can see, so the tile the
causal diagonal crosses does 10/16 of the square's work and the tiles below
it run without any mask. An absent key mask is absent from the kernel (no
operand, no compare).

Everything runs under `interpret=True` off-TPU, so the CPU test mesh
exercises the exact kernel code path.

Reference integration point: the model zoo's ``attention_impl`` contract
(models/bert.py BertSelfAttention); the reference framework has no custom
kernels at all — its attention is whatever HF/torch emits (SURVEY.md §2.8).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# Row statistics are kept full-lane-width (rows, 128) with every lane
# holding the same value: full-width loads/stores are the fast path and a
# (rows, 128) statistic broadcasts over a (rows, 128k) tile by re-use.
_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# (batch, head group, outer block) grid dims are parallel; the innermost
# reduction dim must stay sequential because the VMEM scratch accumulators
# carry across it.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


# ---------------------------------------------------------------------------
# in-kernel helpers
# ---------------------------------------------------------------------------


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic as (rows, n)."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _cols(row):
    """A (1, rows) statistic, rows along lanes, as lane-replicated
    (rows, 128): broadcast over sublanes (free), one XLU transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _head_lanes(g, d, width):
    """(1, width) bool: the lanes of head ``g`` of a block's ``width``."""
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= g * d) & (lane < (g + 1) * d)


def _head_rows(x, g, d, heads, scale=None):
    """Block ``x`` (rows, W) with every lane outside head ``g`` zeroed (and
    the rest scaled): contracting its 128 lanes against an unmasked
    operand is head g's own contraction. f32 arithmetic (the v5e VPU has
    no bf16), result in x's dtype."""
    if heads == 1 and scale is None:
        return x
    y = x.astype(jnp.float32)
    if scale is not None:
        y = y * scale
    if heads > 1:
        y = jnp.where(_head_lanes(g, d, x.shape[1]), y, 0.0)
    return y.astype(x.dtype)


def _merge_heads(per_head, d):
    """[(rows, W) per head, valid in that head's lanes] -> one (rows, W)."""
    out = per_head[0]
    for g in range(1, len(per_head)):
        out = jnp.where(_head_lanes(g, d, out.shape[1]), per_head[g], out)
    return out


# Grouped-query attention (``group`` = H / H_kv > 1; the module docstring has
# the layout): a grid step holds one K/V block and ONE of the ``group`` blocks
# of q its heads serve. With ``group`` 1 nothing below is traced.


def _kv_head(sub, g, heads, group):
    """The K/V head, within its block, of Q head ``g`` of q sub-block
    ``sub`` (both may be traced)."""
    return (sub * heads + g) // group


def _q_rows(x, g, d, heads, group, sub, scale=None):
    """`_head_rows` of Q head ``g``; with grouped heads the head sits in the
    lanes of its K/V head."""
    if group == 1 or heads == 1:
        return _head_rows(x, g, d, heads, scale)
    j = _kv_head(sub, g, heads, group)
    y = x.astype(jnp.float32)
    if scale is not None:
        y = y * scale
    y = pltpu.roll(y, (j - g + heads) % heads * d, 1)
    return jnp.where(_head_lanes(j, d, x.shape[1]), y, 0.0).astype(x.dtype)


def _back_to_own_lanes(per_head, d, group, sub):
    """``per_head[g]`` (rows, W) f32, valid in the lanes of head g's K/V
    head, with head g back in its own lanes (what `_merge_heads` takes)."""
    heads = len(per_head)
    if group == 1 or heads == 1:
        return per_head
    return [pltpu.roll(x, (g - _kv_head(sub, g, heads, group) + heads)
                       % heads * d, 1) for g, x in enumerate(per_head)]


def _precision(dtype):
    # f32 operands keep f32 accuracy (the benchmark's reference check runs
    # the model in f32 through this kernel); bf16 is the MXU's native pass
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot_nt(a, b):
    """a (m, c) · b (n, c)ᵀ -> (m, n) f32; the MXU takes the transposed
    right operand natively."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=_precision(a.dtype))


def _dot(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=_precision(a.dtype))


#: Rows of one strip of a tile. A tile is worked through in strips of rows
#: (query rows; key rows in the dkv kernel), each against the columns it can
#: see: all of them on a tile inside the causal triangle, the columns up to
#: its own last row on the tile the diagonal crosses. A 1024-row diagonal
#: tile then does 10/16 of the square's work in four [256, 256..1024]
#: matmuls a head instead of 16/16, with no more grid steps.
_STRIP = 256


def _strips(rows, cols, diagonal, transposed=False):
    """[(r0, r1, c0, c1)]: the strips of a (rows, cols) tile and the column
    range each works on. ``transposed`` (the dkv kernel): rows are keys and
    see the queries from their own first row on, not up to their last."""
    h = _STRIP if rows % _STRIP == 0 else rows
    if not diagonal:
        return [(r, r + h, 0, cols) for r in range(0, rows, h)]
    if transposed:
        return [(r, r + h, r, cols) for r in range(0, rows, h)]
    return [(r, r + h, 0, r + h) for r in range(0, rows, h)]


def _visible(r0, r1, c0, c1, transposed=False):
    """(r1-r0, c1-c0) bool of a strip of an aligned diagonal tile:
    key <= query. Rows are queries and columns keys, or the reverse when
    ``transposed``."""
    shape = (r1 - r0, c1 - c0)
    r = lax.broadcasted_iota(jnp.int32, shape, 0) + r0
    c = lax.broadcasted_iota(jnp.int32, shape, 1) + c0
    return (r <= c) if transposed else (c <= r)


def _for_each_head(heads, body):
    """``body(g)`` for every head of the block. A `fori_loop`, so the body is
    traced once (every program that holds a kernel pays its trace again in
    every run: set-up time), and unrolled when Mosaic lowers it, so the
    heads' instructions still interleave: a real loop read 130.7 ms a step
    in `gpt2-124m.s1024` where the unrolled body reads 127.8 (PR 30)."""
    if heads == 1:
        body(0)
    else:
        lax.fori_loop(0, heads, lambda g, carry: body(g), None, unroll=True)


def _scores(qg, k_ref, mask_ref, strip, diagonal):
    """Masked scores (rows, cols) f32 of one strip: ``qg`` is the query
    block with one head's lanes kept and the scale folded in."""
    r0, r1, c0, c1 = strip
    s = _dot_nt(qg[r0:r1], k_ref[0, c0:c1, :])
    if mask_ref is not None:
        s = jnp.where(mask_ref[0, :, c0:c1] > 0, s, -jnp.inf)    # [1, cols]
    if diagonal:
        s = jnp.where(_visible(r0, r1, c0, c1), s, -jnp.inf)
    return s


def _on_causal_tiles(causal, key_block, query_block, blocks, tile):
    """Run ``tile(diagonal)`` if this grid step's (query block, key block)
    tile has work: always without ``causal``; with it (blocks are square
    and aligned) the tiles strictly inside the triangle unmasked, the
    diagonal tile masked, the rest skipped. A sequence of one block
    (``blocks == 1``: S <= 1024) is its diagonal tile and nothing else: no
    branch, and half the kernel body to trace and lower in every run."""
    if not causal:
        tile(False)
    elif blocks == 1:
        tile(True)
    else:
        pl.when(key_block < query_block)(lambda: tile(False))
        pl.when(key_block == query_block)(lambda: tile(True))


# ---------------------------------------------------------------------------
# attention-probabilities dropout: the keep decision of one score is a pure
# integer function of (seed words, batch row, head, absolute query row,
# absolute key column), so the kernels (on the strip they hold) and a dense
# program (`dropout_keep_mask`, on the whole [B, H, Sq, Sk]) draw the same
# mask whatever the tiling
# ---------------------------------------------------------------------------

_MUL_BATCH, _MUL_HEAD = 0x9E3779B1, 0x85EBCA77
_MUL_ROW, _MUL_COL = 0xC2B2AE3D, 0x27D4EB2F


def _mix(x):
    """A 32-bit finalizer (xor-shift / multiply rounds, "lowbias32"): every
    input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _keep(seed0, seed1, batch, head, rows, cols, rate):
    """bool, the broadcast of (batch, head, rows, cols): which scores stay.
    ``seed0``/``seed1`` are uint32 scalars, the coordinates uint32 (scalars
    or arrays that broadcast against each other: the kernels pass scalar
    batch and head, a (r, 1) column of rows and a (1, c) row of columns).
    keep <=> 32 hashed bits < round((1 - rate) * 2**32): |p_keep - (1 -
    rate)| <= 2**-32. The two seed words each give a (batch, head) stream
    word; a row's word is mixed once per row (a thin column), so a score
    costs one xor, one `_mix` and one compare."""
    def stream(seed, salt):
        return _mix(_mix(seed + batch * jnp.uint32(_MUL_BATCH))
                    ^ (head * jnp.uint32(_MUL_HEAD) + jnp.uint32(salt)))

    row_word = _mix(stream(seed0, 0) + rows * jnp.uint32(_MUL_ROW))
    col_word = stream(seed1, 1) + cols * jnp.uint32(_MUL_COL)
    threshold = min(round((1.0 - rate) * 2 ** 32), 2 ** 32 - 1)
    return _mix(row_word ^ col_word) < jnp.uint32(threshold)


def _keep_strip(seed_ref, batch, head, row0, col0, shape, rate):
    """`_keep` on a (rows, cols) strip whose first score is (row0, col0) of
    the whole sequence pair."""
    u32 = lambda x: jnp.asarray(x, jnp.int32).astype(jnp.uint32)  # noqa: E731
    rows = lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0) + row0
    cols = lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1) + col0
    return _keep(seed_ref[0], seed_ref[1], u32(batch), u32(head), u32(rows),
                 u32(cols), rate)


def dropout_seed_words(rng):
    """Two uint32 words from a dropout key (typed or raw ``uint32[2]``; a
    wider key's words are folded by xor): the kernels' seed operand."""
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    words = rng.astype(jnp.uint32).reshape(-1, 2)
    return functools.reduce(jnp.bitwise_xor, list(words))


def dropout_keep_mask(seed, batch, heads, sq, sk, rate):
    """The ``[B, H, Sq, Sk]`` bool keep mask the kernels apply for ``seed``
    (a dropout key or its two words), built outside any kernel: a dense
    program that drops with it reproduces the kernels' result."""
    seed = dropout_seed_words(jnp.asarray(seed))
    grid = lambda n, axis: lax.broadcasted_iota(  # noqa: E731
        jnp.uint32, tuple(n if a == axis else 1 for a in range(4)), axis)
    keep = _keep(seed[0], seed[1], grid(batch, 0), grid(heads, 1),
                 grid(sq, 2), grid(sk, 3), rate)
    return jnp.broadcast_to(keep, (batch, heads, sq, sk))


# ---------------------------------------------------------------------------
# forward kernel: grid (B, H/G, Sq/bq, Sk/bk); scratch carries the online
# softmax of each of the block's G heads
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, has_mask, heads, d, nk, rate=0.0,
                group=1):
    """``rate`` > 0: the first operand is the two dropout seed words (SMEM);
    the row sum and the saved lse come from the undropped ``p``, the context
    from ``p ⊙ keep`` (its ``1 / (1 - rate)`` is applied once, at the flush).
    At rate 0 none of that is in the kernel."""
    seed_ref, refs = (refs[0], refs[1:]) if rate else (None, refs)
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    o_ref, lse_ref, m_s, l_s, acc_s = refs[3 + has_mask:]
    # grouped heads: grid (B, K/V block, q sub-block, Sq/bq, Sk/bk)
    sub = pl.program_id(2) if group > 1 else 0
    qi, kj = pl.program_id(2 + (group > 1)), pl.program_id(3 + (group > 1))
    if rate:    # the mask's batch row and head group (asked for out here:
        bi, gi = pl.program_id(0), pl.program_id(1)    # not inside a loop)
        if group > 1:
            gi = gi * group + sub
    bq, width = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_BIG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def tile(diagonal):
        def head(g):
            qg = _q_rows(q_ref[0], g, d, heads, group, sub, scale)  # [bq, W]
            for strip in _strips(bq, bk, diagonal):
                r0, r1, c0, c1 = strip
                rows = slice(r0, r1)
                s = _scores(qg, k_ref, mask_ref, strip, diagonal)  # [h, c]
                m_prev, l_prev = m_s[g, rows], l_s[g, rows]      # [h, 128]
                # the running max starts at a finite floor, so a row with
                # no valid key so far keeps p = exp(-inf - floor) = 0
                m_next = jnp.maximum(m_prev,
                                     jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - _lanes(m_next, c1 - c0))
                alpha = jnp.exp(m_prev - m_next)
                l_s[g, rows] = alpha * l_prev + jnp.sum(p, axis=1,
                                                        keepdims=True)
                m_s[g, rows] = m_next
                if rate:
                    p = jnp.where(_keep_strip(
                        seed_ref, bi, gi * heads + g, qi * bq + r0,
                        kj * bk + c0, p.shape, rate), p, 0.0)
                v2 = v_ref[0, c0:c1, :]
                acc_s[g, rows] = (acc_s[g, rows] * _lanes(alpha, width)
                                  + _dot(p.astype(v2.dtype), v2))  # [h, W]

        _for_each_head(heads, head)

    _on_causal_tiles(causal, kj, qi, nk, tile)

    @pl.when(kj == nk - 1)
    def _flush():
        outs = []
        for g in range(heads):
            l = jnp.maximum(l_s[g], 1e-30)                       # all-masked
            kept = l * (1.0 - rate) if rate else l
            outs.append(acc_s[g] / _lanes(kept, width))
            lse_ref[0, 0, g:g + 1, :] = jnp.transpose(
                m_s[g] + jnp.log(l))[:1, :]
        o_ref[0] = _merge_heads(_back_to_own_lanes(outs, d, group, sub),
                                d).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *,
                    scale, causal, heads, d, nq):
    """dK/dV on the transposed tile ``sᵀ = k qᵀ`` ([bk, bq]). Takes no key
    mask: a masked key's rows of dk/dv are zeroed by the caller, and no
    other row sees them."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def tile(diagonal):
        def head(g):
            kg = _head_rows(k_ref[0], g, d, heads, scale)        # [bk, W]
            vg = _head_rows(v_ref[0], g, d, heads)
            # strips of KEY rows; each sees the queries from its own on
            for k0, k1, q0, q1 in _strips(bk, bq, diagonal, transposed=True):
                keys = slice(k0, k1)
                q2, do2 = q_ref[0, q0:q1, :], do_ref[0, q0:q1, :]
                st = _dot_nt(kg[keys], q2)                       # [h, q]
                if diagonal:
                    st = jnp.where(_visible(k0, k1, q0, q1, transposed=True),
                                   st, -jnp.inf)
                pt = jnp.exp(st - lse_ref[0, 0, pl.ds(g, 1), q0:q1])  # [1, q]
                dpt = _dot_nt(vg[keys], do2)
                dst = pt * (dpt - delta_ref[0, 0, pl.ds(g, 1), q0:q1])
                dv_s[g, keys] = (dv_s[g, keys]
                                 + _dot(pt.astype(do2.dtype), do2))  # [h, W]
                dk_s[g, keys] = dk_s[g, keys] + _dot(dst.astype(q2.dtype), q2)

        _for_each_head(heads, head)

    # causal: query blocks strictly before this key block contribute nothing
    _on_causal_tiles(causal, ki, qi, nq, tile)

    @pl.when(qi == nq - 1)
    def _flush():
        dk = _merge_heads([dk_s[g] for g in range(heads)], d)
        dv = _merge_heads([dv_s[g] for g in range(heads)], d)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _dot_tn(a, b):
    """a (c, m)ᵀ · b (c, n) -> (m, n) f32: Mosaic transposes ``a`` on the
    XLU first."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=_precision(a.dtype))


def _bwd_kernel(*refs, scale, causal, has_mask, with_kv, heads, d, nq, nk,
                rate=0.0, group=1):
    """dQ of a query block, accumulated over its key blocks; ``with_kv``
    (the fused backward): dK and dV too, from the same recomputation of each
    P-tile — 5 matmuls and one exp a tile where a dq + dkv pair makes 7 and
    2. dK/dV of the whole key sequence then stay in VMEM scratch for a
    (batch, head group); ``pᵀ do`` and ``dsᵀ q`` contract the tile's rows
    (a transposed left operand).

    ``rate`` > 0 (the first operand is then the two seed words, SMEM): the
    forward's keep mask is rebuilt from the same function. With ``r`` the
    rate, ``dp = (do vᵀ) ⊙ keep / (1-r)``, ``ds = p ⊙ (dp - delta)``, ``dv =
    (p ⊙ keep)ᵀ do / (1-r)``; the kernel works on ``(1-r) ds = p ⊙ ((do vᵀ)
    ⊙ keep - (1-r) delta)`` and applies each ``1 / (1-r)`` at a flush, so a
    score pays the hash and two selects and no multiply."""
    seed_ref, refs = (refs[0], refs[1:]) if rate else (None, refs)
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    do_ref, lse_ref, delta_ref, dq_ref = refs[3 + has_mask:7 + has_mask]
    if with_kv:
        dk_ref, dv_ref, lse_s, delta_s, dq_s, dk_s, dv_s = refs[7 + has_mask:]
    else:
        lse_s, delta_s, dq_s = refs[7 + has_mask:]
    sub = pl.program_id(2) if group > 1 else 0
    qi, kj = pl.program_id(2 + (group > 1)), pl.program_id(3 + (group > 1))
    if rate:
        bi, gi = pl.program_id(0), pl.program_id(1)
        if group > 1:
            gi = gi * group + sub
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    key0 = 0 if nk == 1 else pl.multiple_of(kj * bk, bk)

    if with_kv:
        first = (qi == 0) & (kj == 0)
        if group > 1:   # dK/dV accumulate over a K/V block's q sub-blocks too
            first = first & (sub == 0)

        @pl.when(first)
        def _init_kv():
            dk_s[...] = jnp.zeros_like(dk_s)
            dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)
        for g in range(heads):  # columns once a query block, not once a tile
            lse_s[g] = _cols(lse_ref[0, 0, g:g + 1, :])
            delta = _cols(delta_ref[0, 0, g:g + 1, :])
            delta_s[g] = delta * (1.0 - rate) if rate else delta

    def tile(diagonal):
        def head(g):
            qg = _q_rows(q_ref[0], g, d, heads, group, sub, scale)  # [bq, W]
            dog = _q_rows(do_ref[0], g, d, heads, group, sub)
            for strip in _strips(bq, bk, diagonal):
                r0, r1, c0, c1 = strip
                rows = slice(r0, r1)
                k2 = k_ref[0, c0:c1, :]
                s = _scores(qg, k_ref, mask_ref, strip, diagonal)  # [h, c]
                p = jnp.exp(s - _lanes(lse_s[g, rows], c1 - c0))
                dp = _dot_nt(dog[rows], v_ref[0, c0:c1, :])
                kept = p                          # p ⊙ keep, what dv sees
                if rate:
                    keep = _keep_strip(
                        seed_ref, bi, gi * heads + g, qi * bq + r0,
                        kj * bk + c0, p.shape, rate)
                    dp, kept = jnp.where(keep, dp, 0.0), jnp.where(keep, p,
                                                                   0.0)
                ds = (p * (dp - _lanes(delta_s[g, rows], c1 - c0))
                      ).astype(k2.dtype)
                dq_s[g, rows] = dq_s[g, rows] + _dot(ds, k2)     # [h, W]
                if with_kv:
                    keys = pl.ds(key0 + c0, c1 - c0)
                    # grouped heads: the row-side operands in their K/V
                    # head's lanes (zero elsewhere; qg carries the scale)
                    own = group == 1
                    dv_s[g, keys] = dv_s[g, keys] + _dot_tn(
                        kept.astype(k2.dtype),
                        do_ref[0, rows, :] if own else dog[rows])  # [c, W]
                    dk_s[g, keys] = dk_s[g, keys] + _dot_tn(
                        ds, q_ref[0, rows, :] if own else qg[rows])

        _for_each_head(heads, head)

    _on_causal_tiles(causal, kj, qi, nk, tile)
    undrop = 1.0 / (1.0 - rate)

    @pl.when(kj == nk - 1)
    def _flush():
        dq = _merge_heads(_back_to_own_lanes(
            [dq_s[g] for g in range(heads)], d, group, sub), d)
        dq_ref[0] = (dq * (scale * undrop)).astype(dq_ref.dtype)

    if with_kv:
        last = (qi == nq - 1) & (kj == nk - 1)
        if group > 1:
            last = last & (sub == group - 1)

        @pl.when(last)
        def _flush_kv():
            if group == 1:
                dk = _merge_heads([dk_s[g] for g in range(heads)], d)
                dv = _merge_heads([dv_s[g] for g in range(heads)], d)
                dk = dk * (scale * undrop)
            else:   # each head's term is zero outside its K/V head's lanes,
                dk = sum(dk_s[g] for g in range(heads))   # scaled already
                dv = sum(dv_s[g] for g in range(heads))
                dk = dk * undrop if rate else dk
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = (dv * undrop if rate else dv).astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers + custom VJP over [B, S, H·D]
# ---------------------------------------------------------------------------


def _sublane_multiple(dtype) -> int:
    """Native sublane tile for a dtype on TPU: (8, 128) tiles hold 32-bit
    elements; 16-bit operands pack two per 32-bit word -> (16, 128);
    8-bit -> (32, 128)."""
    bits = jnp.dtype(dtype).itemsize * 8
    return {32: 8, 16: 16, 8: 32}.get(bits, 8)


#: Preferred sequence block of every kernel: S=1024 is one grid step a head
#: group, worked through in `_STRIP`-row strips. At (16, 1024, 12, 64) causal
#: bf16 forward + backward on a v5e (scripts/flash_ab.py, PR 30): whole
#: masked tiles of 256 / 512 / 1024 rows read 3.97 / 3.04 / 3.07 ms, so the
#: cost of a tile is mostly per tile and per row, not per score.
_BLOCK = 1024


def _pick_block(s: int, pref: Optional[int] = None,
                dtype=jnp.float32) -> int:
    """Largest divisor of ``s`` that is <= ``pref`` by halving — refusing
    blocks below the dtype's native sublane tile (a bf16 operand blocked
    at 8 rows passes the naive %8 rule but mis-tiles on chip; the CPU
    interpreter would never notice). The per-row statistics travel with
    rows along lanes, so a block that is not the whole sequence must also
    be a multiple of 128."""
    b = min(s, pref or _BLOCK)
    while s % b:
        b //= 2
    b = max(b, 1)
    need = _sublane_multiple(dtype)
    if b != s and (b % need or b % _LANES):
        raise ValueError(
            f"flash attention: sequence length {s} only tiles into "
            f"{b}-row blocks, below the {jnp.dtype(dtype).name} native "
            f"sublane tile ({need}) or the 128-lane row of the row "
            f"statistics; pad the sequence to a multiple of {_LANES}"
        )
    return b


def check_mosaic_block(block: tuple, array: tuple,
                       dtype=jnp.float32) -> None:
    """Enforce Mosaic's block-shape rule at trace time, on EVERY backend.

    The real-TPU lowering requires the last two dims of each block be
    divisible by the operand dtype's native tile — (8, 128) for 32-bit,
    (16, 128) for 16-bit, (32, 128) for 8-bit — or equal the array's
    dims. ``interpret=True`` (the CPU test mesh) never applies the rule,
    so a violating spec sails through the whole suite and dies on first
    chip contact — exactly what happened with the rank-2 ``(1, S)``
    vector specs on 2026-07-31. Calling this from the wrappers makes the
    CPU tests fail the same way the chip would."""
    need = _sublane_multiple(dtype)
    sub, lane = block[-2], block[-1]
    if sub % need and sub != array[-2]:
        raise ValueError(
            f"Mosaic-illegal block {block} for array {array} "
            f"({jnp.dtype(dtype).name}): second-to-last block dim {sub} is "
            f"neither a multiple of the native sublane tile {need} nor the "
            f"array dim {array[-2]}"
        )
    if lane % 128 and lane != array[-1]:
        raise ValueError(
            f"Mosaic-illegal block {block} for array {array}: last block dim "
            f"{lane} is neither a multiple of 128 nor the array dim "
            f"{array[-1]}"
        )


def _check_specs(specs, arrays) -> None:
    """Validate the ACTUAL BlockSpec objects handed to ``pallas_call``
    (reading ``spec.block_shape`` — no hand-copied shadow list to drift).
    ``arrays`` pairs each spec with ``(shape, dtype)``."""
    for spec, (shape, dtype) in zip(specs, arrays, strict=True):
        check_mosaic_block(tuple(spec.block_shape), tuple(shape), dtype)


def _group_width(heads: int, d: int) -> int:
    """Lanes of one block along ``H·D``: one head if it fills whole lane
    rows, else as many heads as make up 128 lanes, else (a width 128 does
    not divide) all of them — a block as wide as the array is always legal."""
    if d % _LANES == 0:
        return d
    if _LANES % d == 0 and (heads * d) % _LANES == 0:
        return _LANES
    return heads * d


def _blocks(q, k, causal):
    sq, sk = q.shape[1], k.shape[1]
    if causal and sq != sk:
        raise ValueError(
            f"causal flash attention needs equal query and key lengths "
            f"(square aligned tiles), got {sq} and {sk}")
    bq = _pick_block(sq, dtype=q.dtype)
    bk = bq if causal else _pick_block(sk, dtype=k.dtype)
    return bq, bk


def _rows_spec(heads_per_block, block, index_map):
    """Spec of a per-row statistic ``[B, H/G, G, S]`` (rows along lanes)."""
    return pl.BlockSpec((1, 1, heads_per_block, block), index_map)


def _inner_clamped(causal, outer_first: bool):
    """Index of the inner (reduction) block a grid step fetches. Causal
    grids still step through every (outer, inner) pair, but tiles past the
    diagonal are ``pl.when``-skipped — clamping the fetch index to the
    diagonal block means those steps re-request the block already in the
    window, so Mosaic issues no DMA for them. ``outer_first``: the inner
    blocks run 0..outer (keys under a query block); else outer..n (queries
    under a key block)."""
    if not causal:
        return lambda outer, inner: inner
    if outer_first:
        return lambda outer, inner: jnp.minimum(inner, outer)
    return lambda outer, inner: jnp.maximum(inner, outer)


def _query_major(q, k, v, kv_mask, heads, causal):
    """What the kernels whose grid is (B, H/G, Sq/bq, Sk/bk) share: the
    geometry, and specs / shapes / operands of q, k, v and the key mask.
    Grouped heads (k, v narrower than q by ``group``): the grid is (B, K/V
    blocks, ``group`` q sub-blocks, Sq/bq, Sk/bk), the block of q, of the
    output and of the row statistics follows (K/V block, sub-block), the
    K/V block the K/V block alone."""
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // heads
    group = hd // k.shape[2]
    width = _group_width(heads // group, d)
    bq, bk = _blocks(q, k, causal)
    kj = _inner_clamped(causal, outer_first=True)
    if group == 1:
        def at(index):      # index(b, K/V block, q block, block i, block j)
            return lambda b, g, i, j: index(b, g, g, i, j)
    else:
        def at(index):
            return lambda b, g, c, i, j: index(b, g, g * group + c, i, j)
    q_spec = pl.BlockSpec((1, bq, width), at(lambda b, g, c, i, j: (b, i, c)))
    k_spec = pl.BlockSpec((1, bk, width),
                          at(lambda b, g, c, i, j: (b, kj(i, j), g)))
    in_specs = [q_spec, k_spec, k_spec]
    arrays = [(q.shape, q.dtype), (k.shape, k.dtype), (v.shape, v.dtype)]
    operands = [q, k, v]
    if kv_mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), at(lambda b, g, c, i, j: (b, 0, kj(i, j)))))
        arrays.append(((b, 1, sk), jnp.int32))
        operands.append(kv_mask.astype(jnp.int32)[:, None, :])
    blocks = (hd // width,) if group == 1 else (k.shape[2] // width, group)
    geometry = dict(
        d=d, width=width, hpb=width // d, groups=hd // width, bq=bq,
        nq=sq // bq, nk=sk // bk, grid=(b,) + blocks + (sq // bq, sk // bk),
        q_spec=q_spec, group=group, at=at,
        rows=_rows_spec(width // d, bq,
                        at(lambda b, g, c, i, j: (b, c, 0, i))))
    return geometry, in_specs, arrays, operands


def _compiler_params(geo, kv_too: bool):
    """Batch, head-block (and q sub-block) and query-block grid dims are
    parallel, the key-block reduction sequential; ``kv_too`` (the fused
    backward): dK/dV accumulate across query blocks, and across a K/V
    block's q sub-blocks, as well, so those run in order too."""
    outer = len(geo["grid"]) - 2
    if kv_too:
        order = ("parallel",) * 2 + ("arbitrary",) * outer
    else:
        order = ("parallel",) * (outer + 1) + ("arbitrary",)
    return pltpu.CompilerParams(
        dimension_semantics=order,
        vmem_limit_bytes=_COMPILER_PARAMS.vmem_limit_bytes)


def _dropout_call(seed, rate, name):
    """What a live dropout rate adds to a `pallas_call`, as (the kernel's
    keywords, the leading in_specs, the leading operands, the call's
    keywords): the rate, the two seed words as a first operand in SMEM, and
    a name that says the kernel draws a mask (how
    `dropout_kernel_calls_per_step` tells it from one that does not). At
    rate 0 all four are empty: the call is the call without dropout."""
    if not rate:
        return {}, [], [], {}
    return ({"rate": rate}, [pl.BlockSpec(memory_space=pltpu.SMEM)], [seed],
            {"name": f"{name}_dropout"})


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "out_dtype", "interpret", "rate"))
def _fwd_call(q, k, v, kv_mask, seed=None, *, heads, scale, causal,
              out_dtype, interpret, rate=0.0):
    """(o ``[B, Sq, H·D]``, lse ``[B, H/G, G, Sq]``). One jitted function
    a shape, so a model's layers lower one Pallas body between them.
    ``seed`` (``uint32[2]``) with a ``rate`` > 0: probabilities dropout."""
    geo, in_specs, arrays, operands = _query_major(q, k, v, kv_mask, heads,
                                                   causal)
    hpb, bq, width = geo["hpb"], geo["bq"], geo["width"]
    drop, seed_spec, seed_operand, named = _dropout_call(seed, rate,
                                                         "flash_fwd")
    out_specs = [geo["q_spec"], geo["rows"]]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, out_dtype),
        jax.ShapeDtypeStruct((q.shape[0], geo["groups"], hpb, q.shape[1]),
                             jnp.float32),
    ]
    _check_specs(in_specs + out_specs,
                 arrays + [(o.shape, o.dtype) for o in out_shape])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          has_mask=kv_mask is not None, heads=hpb,
                          d=geo["d"], nk=geo["nk"], group=geo["group"],
                          **drop),
        grid=geo["grid"],
        in_specs=seed_spec + in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((hpb, bq, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((hpb, bq, _LANES), jnp.float32),   # running denom l
            pltpu.VMEM((hpb, bq, width), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        compiler_params=_compiler_params(geo, kv_too=False),
        **named,
    )(*seed_operand, *operands)


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "out_dtype", "with_kv", "interpret", "rate"))
def _bwd_call(q, k, v, kv_mask, do, lse, delta, seed=None, *, heads, scale,
              causal, out_dtype, with_kv, interpret, rate=0.0):
    """dQ ``[B, Sq, H·D]`` given the GLOBAL ``lse``/``delta``
    (``[B, H/G, G, Sq]``); ``with_kv``: (dQ, dK, dV) from the one fused
    kernel. ``seed`` and ``rate``: the forward call's."""
    geo, in_specs, arrays, operands = _query_major(q, k, v, kv_mask, heads,
                                                   causal)
    hpb, bq, width = geo["hpb"], geo["bq"], geo["width"]
    drop, seed_spec, seed_operand, named = _dropout_call(seed, rate,
                                                         "flash_bwd")
    in_specs += [geo["q_spec"], geo["rows"], geo["rows"]]
    arrays += [(do.shape, do.dtype), (lse.shape, lse.dtype),
               (delta.shape, delta.dtype)]
    out_specs = [geo["q_spec"]]
    out_shape = [jax.ShapeDtypeStruct(q.shape, out_dtype)]
    scratch = [
        pltpu.VMEM((hpb, bq, _LANES), jnp.float32),       # lse, as columns
        pltpu.VMEM((hpb, bq, _LANES), jnp.float32),       # delta, as columns
        pltpu.VMEM((hpb, bq, width), jnp.float32),        # dq accumulator
    ]
    if with_kv:
        sk = k.shape[1]
        kv_spec = pl.BlockSpec((1, sk, width),
                               geo["at"](lambda b, g, c, i, j: (b, 0, g)))
        out_specs += [kv_spec, kv_spec]
        out_shape += [jax.ShapeDtypeStruct(k.shape, out_dtype),
                      jax.ShapeDtypeStruct(v.shape, out_dtype)]
        scratch += [pltpu.VMEM((hpb, sk, width), jnp.float32)] * 2  # dk, dv
    _check_specs(in_specs + out_specs,
                 arrays + [(o.shape, o.dtype) for o in out_shape])
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          has_mask=kv_mask is not None, with_kv=with_kv,
                          heads=hpb, d=geo["d"], nq=geo["nq"], nk=geo["nk"],
                          group=geo["group"], **drop),
        grid=geo["grid"],
        in_specs=seed_spec + in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_compiler_params(geo, kv_too=True),
        **named,
    )(*seed_operand, *operands, do, lse, delta)
    return out if with_kv else out[0]


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "out_dtype", "interpret"))
def _dkv_call(q, k, v, kv_mask, do, lse, delta, *, heads, scale, causal,
              out_dtype, interpret):
    """(dK, dV) ``[B, Sk, H·D]`` given the GLOBAL ``lse``/``delta``."""
    b, sq, hd = q.shape
    sk, d = k.shape[1], hd // heads
    width = _group_width(heads, d)
    hpb = width // d
    bq, bk = _blocks(q, k, causal)
    nq = sq // bq
    qi = _inner_clamped(causal, outer_first=False)
    q_spec = pl.BlockSpec((1, bq, width),
                          lambda b, g, j, i: (b, qi(j, i), g))
    k_spec = pl.BlockSpec((1, bk, width), lambda b, g, j, i: (b, j, g))
    rows = _rows_spec(hpb, bq, lambda b, g, j, i: (b, g, 0, qi(j, i)))
    in_specs = [q_spec, k_spec, k_spec, q_spec, rows, rows]
    out_shape = [jax.ShapeDtypeStruct(k.shape, out_dtype),
                 jax.ShapeDtypeStruct(v.shape, out_dtype)]
    _check_specs(
        in_specs + [k_spec, k_spec],
        [(q.shape, q.dtype), (k.shape, k.dtype), (v.shape, v.dtype),
         (do.shape, do.dtype), (lse.shape, lse.dtype),
         (delta.shape, delta.dtype)]
        + [(o.shape, o.dtype) for o in out_shape])
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          heads=hpb, d=d, nq=nq),
        grid=(b, hd // width, sk // bk, nq),
        in_specs=in_specs,
        out_specs=[k_spec, k_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((hpb, bk, width), jnp.float32),    # dk accumulator
            pltpu.VMEM((hpb, bk, width), jnp.float32),    # dv accumulator
        ],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, do, lse, delta)
    if kv_mask is not None:
        # a masked key has p = 0 in every row: its gradient rows are zero.
        # The kernel does not look at the mask (its tile is transposed, the
        # mask would be a lane-sparse column); what it computed for those
        # rows, inf and NaN included, is dropped here
        keep = (kv_mask > 0)[:, :, None]
        dk, dv = jnp.where(keep, dk, 0), jnp.where(keep, dv, 0)
    return dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, kv_mask, seed, heads, scale, causal, rate):
    """Attention over ``[B, S, H·D]`` operands (``kv_mask`` ``[B, Sk]`` or
    None; ``seed`` ``uint32[2]`` where ``rate`` > 0, else None)."""
    return _flash_fwd(q, k, v, kv_mask, seed, heads, scale, causal, rate)[0]


def _flash_fwd(q, k, v, kv_mask, seed, heads, scale, causal, rate):
    o, lse = _fwd_call(q, k, v, kv_mask, seed, heads=heads, scale=scale,
                       causal=causal, out_dtype=q.dtype,
                       interpret=_interpret(), rate=rate)
    return o, (q, k, v, kv_mask, seed, o, lse)


def _flash_bwd(heads, scale, causal, rate, res, do):
    q, k, v, kv_mask, seed, o, lse = res
    b, sq, hd = q.shape
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(b, sq, heads, hd // heads), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(lse.shape)
    dq, dk, dv = _bwd_call(q, k, v, kv_mask, do, lse, delta, seed,
                           heads=heads, scale=scale, causal=causal,
                           out_dtype=q.dtype, with_kv=True,
                           interpret=_interpret(), rate=rate)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _equal_heads(q, k):
    """The ring's pair kernels take folded ``[BH, S, D]`` operands with as
    many K/V heads as Q heads; grouped heads are `flash_attention`'s."""
    if q.shape[0] != k.shape[0] or q.shape[2] != k.shape[2]:
        raise ValueError(
            f"the pair kernels need H_kv == H: q is folded to {q.shape}, k "
            f"to {k.shape}; repeat K and V, or use `flash_attention`")


def _folded_rows(x):
    """``[BH, S]`` statistic of the folded API -> ``[BH, 1, 1, S]``."""
    return x[:, None, None, :]


def flash_pair_fwd(q, k, v, kv_mask, scale, causal, out_dtype=None):
    """(o, lse) for one (q-block, k-block) pair over folded ``[BH, S, D]``
    operands — ring attention's per-step forward building block.
    ``out_dtype`` (default: q's dtype) lets the ring keep the per-block
    contributions in fp32 for its cross-block accumulation."""
    _equal_heads(q, k)
    o, lse = _fwd_call(q, k, v, kv_mask, heads=1, scale=scale, causal=causal,
                       out_dtype=jnp.dtype(out_dtype or q.dtype),
                       interpret=_interpret())
    return o, lse[:, 0, 0, :]


def flash_pair_dq(q, k, v, kv_mask, do, lse, delta, scale, causal,
                  out_dtype=None):
    """dQ for one (q-block, k-block) pair given GLOBAL ``lse``/``delta``
    (folded ``[BH, S, D]`` operands). This is the flash backward's dq leg;
    exposed separately so ring attention can run it per ring step."""
    _equal_heads(q, k)
    return _bwd_call(q, k, v, kv_mask, do, _folded_rows(lse),
                     _folded_rows(delta), heads=1, scale=scale,
                     causal=causal, with_kv=False,
                     out_dtype=jnp.dtype(out_dtype or q.dtype),
                     interpret=_interpret())


def flash_pair_dkv(q, k, v, kv_mask, do, lse, delta, scale, causal,
                   out_dtype=None):
    """dK/dV for one (q-block, k-block) pair given GLOBAL ``lse``/``delta``
    (see `flash_pair_dq`)."""
    _equal_heads(q, k)
    return _dkv_call(q, k, v, kv_mask, do, _folded_rows(lse),
                     _folded_rows(delta), heads=1, scale=scale,
                     causal=causal,
                     out_dtype=jnp.dtype(out_dtype or k.dtype),
                     interpret=_interpret())


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    dropout_rng: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
) -> jax.Array:
    """Tiled exact attention over ``[B, S, H, D]`` inputs.

    ``k`` and ``v`` may hold fewer heads than ``q``, ``[B, S, H_kv, D]``
    with ``H_kv`` dividing ``H`` (grouped-query attention: K/V head ``j``
    serves Q heads ``j * H/H_kv`` on): the kernels read K and V as they
    are and return dK/dV summed over each group, nothing is repeated in
    HBM; the heads must tile the 128 lanes (`grouped_heads_tile`).

    ``kv_mask``: optional key-validity mask ``[B, S_k]`` (True = attend).
    Differentiable (flash backward). ``causal`` needs ``S_q == S_k``.
    ``dropout_rng`` with a static ``dropout_rate`` > 0: dropout of the
    attention probabilities inside the kernels, by the mask
    `dropout_keep_mask` builds for the same key; without a key or at rate 0
    the kernels hold no dropout code.

    Sequence-length constraint: a sequence of at most 512 rows is one
    block and always legal (``S_q = 1`` decode included); a longer one is
    tiled by halving 512 down to a divisor of ``S``, which must be a
    multiple of 128 (the row statistics travel with rows along lanes) —
    else ``ValueError`` at trace time on every backend, because on a real
    TPU that block would mis-tile; pad the sequence to a multiple of 128.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = float(D ** -0.5 if scale is None else scale)
    rate = float(dropout_rate) if dropout_rng is not None else 0.0
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"dropout_rate must lie in [0, 1), got {rate}")
    if not grouped_heads_tile(H, Hkv, D):
        raise ValueError(
            f"{H} query heads over {Hkv} K/V heads of {D} do not tile the "
            "kernels' 128-lane blocks (`grouped_heads_tile`)")
    seed = dropout_seed_words(dropout_rng) if rate else None
    # [B,S,H,D] -> [B,S,H·D] is free: no transpose surrounds the kernels
    o = _flash(q.reshape(B, Sq, H * D), k.reshape(B, Sk, Hkv * D),
               v.reshape(B, Sk, Hkv * D), kv_mask, seed, H, scale, causal,
               rate)
    return o.reshape(B, Sq, H, D)


def grouped_heads_tile(heads: int, kv_heads: int, d: int) -> bool:
    """Whether ``heads`` query heads over ``kv_heads`` K/V heads of width
    ``d`` fit the kernels. Equal counts always do; fewer K/V heads need
    whole groups and a K/V block of whole 128-lane rows (one head of a
    multiple of 128 lanes, or ``128/d`` heads that fill 128), so that a Q
    head reaches its K/V head's lanes by a rotation inside one lane row."""
    return heads == kv_heads or (heads % kv_heads == 0 and (
        d % _LANES == 0
        or (_LANES % d == 0 and (kv_heads * d) % _LANES == 0)))


def key_validity(mask):
    """The kernels' ``kv_mask`` ``[B, S]`` from the models' ADDITIVE
    key-padding mask ``[B, 1, 1, S]`` (0 = attend, -1e9 = padding)."""
    return mask.reshape(mask.shape[0], mask.shape[-1]) > -1.0


def make_flash_attention_impl():
    """Model-zoo ``attention_impl`` (models/bert.py contract) backed by the
    kernel, attention-probabilities dropout included (the kernels' own
    mask: `dropout_keep_mask`)."""

    def impl(q, k, v, mask, dropout_rng=None, dropout_rate=0.0, dtype=None):
        with jax.named_scope("attention"):
            return flash_attention(
                q, k, v, kv_mask=None if mask is None else key_validity(mask),
                dropout_rng=dropout_rng, dropout_rate=dropout_rate)

    return impl
