"""Flash attention as Pallas TPU kernels (forward + backward).

The hot op of every transformer in the zoo: the whole
score-softmax-weighted-sum pipeline stays in VMEM per (query-block,
key-block) tile, the S×S score matrix is never materialized in HBM
(memory O(S·D) instead of O(S²)), and the MXU sees back-to-back
[bq,D]×[D,bk] / [bq,bk]×[bk,D] matmuls (Dao et al. 2022, blockwise online
softmax — same math as `parallel.ring_attention`, which distributes ACROSS
chips what this kernel tiles WITHIN one).

STATUS (PR 24): the kernels compile for a TPU v5e and `chip_smoke.py`
checks them on the chip — forward and q/k/v gradients against dense
attention at S=1024, and a GPT-2 train step whose compiled text carries
the `tpu_custom_call`. tests/test_chip_compile.py keeps the v5e compile
in tier-1. The memory claim above is structural; SPEED against XLA's
fused attention is not measured — the smoke prints one timing, labelled
as such, and `python scripts/flash_ab.py` is the A/B tool (ROADMAP A2).

Backward is the standard flash recomputation: forward saves only the
softmax log-sum-exp per row; dQ and dK/dV are computed by two kernels that
rebuild each P-tile on the fly.

Kernel structure (the part that decides TPU performance): the reduction
over key/query blocks is a GRID dimension, not an in-kernel loop. The
innermost grid dim is declared ``arbitrary`` (sequential), the online
softmax / gradient accumulators live in VMEM scratch that persists across
those steps, and ``pl.when`` gates the j==0 init and the j==last flush.
That shape lets Mosaic double-buffer each (1, bk, D) K/V block DMA behind
the previous tile's compute — the first version of this file instead
looped over an all-resident K/V block inside one kernel invocation, which
serialized everything.

Everything runs under `interpret=True` off-TPU, so the CPU test mesh
exercises the exact kernel code path.

Layout note (Mosaic, the real-TPU lowering): the last two dims of every
block must be (8k, 128k) or equal the array's dims — a rank-2 operand
blocked ``(1, S)`` over a ``[BH, S]`` array is rejected because the
leading 1 is neither. The per-row vectors (kv mask, lse, delta) therefore
travel as ``[BH, S, 1]`` inside the kernels (blocks ``(1, bs, 1)``: both
trailing dims legal), while the public API stays rank-2. interpret=True
never checks this, which is why only real-chip runs could catch it.

Reference integration point: the model zoo's ``attention_impl`` contract
(models/bert.py BertSelfAttention); the reference framework has no custom
kernels at all — its attention is whatever HF/torch emits (SURVEY.md §2.8).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
# Row-statistic scratch is kept full-lane-width (bq, 128) with every lane
# holding the same value: full-width loads/stores are the fast path and
# sidestep sub-lane masked writes.
_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Leading (BH, q-or-k block) grid dims are parallel — Mosaic may split
# them across cores; the innermost reduction dim must stay sequential
# because the VMEM scratch accumulators carry across it.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024,
)


def _bcast_rows(x, bq):
    """[bq] or [bq, 1] row statistic -> full-width (bq, LANES)."""
    return jnp.broadcast_to(x.reshape(bq, 1), (bq, _LANES))


# ---------------------------------------------------------------------------
# forward kernel: grid (BH, Sq/bq, Sk/bk); scratch carries the online softmax
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, scale, causal, bq, bk, nk):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_BIG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # causal: key blocks strictly after this query block contribute nothing
    work = (kj * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(work)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale                 # [bq, D]
        k = k_ref[0].astype(jnp.float32)                         # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bq, bk]
        valid = jnp.broadcast_to(mask_ref[0, :, 0] > 0, s.shape)
        if causal:
            q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)
            k_pos = kj * bk + jax.lax.iota(jnp.int32, bk)
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(valid, s, -jnp.inf)
        bm = jnp.maximum(jnp.max(s, axis=-1), _NEG_BIG)          # [bq]
        p = jnp.exp(s - bm[:, None])                             # [bq, bk]
        m_prev = m_s[:, :1]                                      # [bq, 1]
        m_new = jnp.maximum(m_prev, bm[:, None])
        alpha = jnp.exp(m_prev - m_new)
        corr = jnp.exp(bm[:, None] - m_new)
        l_new = l_s[:, :1] * alpha + jnp.sum(p, -1, keepdims=True) * corr
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bq, D]
        acc_s[...] = acc_s[...] * alpha + pv * corr
        m_s[...] = _bcast_rows(m_new, bq)
        l_s[...] = _bcast_rows(l_new, bq)

    @pl.when(kj == nk - 1)
    def _flush():
        l = jnp.maximum(l_s[:, :1], 1e-30)                       # all-masked
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_s[:, 0] + jnp.log(l[:, 0])


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_s, *, scale, causal, bq, bk, nk):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    work = (kj * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(work)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale                 # [bq, D]
        k = k_ref[0].astype(jnp.float32)                         # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                       # [bq, D]
        lse = lse_ref[0, :, 0]                                   # [bq]
        delta = delta_ref[0, :, 0]                               # [bq]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        valid = jnp.broadcast_to(mask_ref[0, :, 0] > 0, s.shape)
        if causal:
            q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)
            k_pos = kj * bk + jax.lax.iota(jnp.int32, bk)
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)     # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bq, bk]
        ds = p * (dp - delta[:, None])
        dq_s[...] = dq_s[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bq, D]

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                    scale, causal, bq, bk, nq):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # causal: query blocks strictly before this key block contribute nothing
    work = (qi * bq + bq - 1 >= ki * bk) if causal else True

    @pl.when(work)
    def _update():
        k = k_ref[0].astype(jnp.float32)                         # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * scale                 # [bq, D]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]                                   # [bq]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bq, bk]
        valid = jnp.broadcast_to(mask_ref[0, :, 0] > 0, s.shape)
        if causal:
            q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)
            k_pos = ki * bk + jax.lax.iota(jnp.int32, bk)
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [bk, D]
        # q is pre-scaled: d(s)/d(k) = q_raw*scale
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers + custom VJP over [BH, S, D]
# ---------------------------------------------------------------------------


def _sublane_multiple(dtype) -> int:
    """Native sublane tile for a dtype on TPU: (8, 128) tiles hold 32-bit
    elements; 16-bit operands pack two per 32-bit word -> (16, 128);
    8-bit -> (32, 128)."""
    bits = jnp.dtype(dtype).itemsize * 8
    return {32: 8, 16: 16, 8: 32}.get(bits, 8)


def _pick_block(s: int, pref: int = 128, dtype=jnp.float32) -> int:
    """Largest divisor of ``s`` that is <= ``pref`` by halving — refusing
    blocks below the dtype's native sublane tile (a bf16 operand blocked
    at 8 rows passes the naive %8 rule but mis-tiles on chip; the CPU
    interpreter would never notice)."""
    b = min(s, pref)
    while s % b:
        b //= 2
    b = max(b, 1)
    need = _sublane_multiple(dtype)
    if b != s and b % need:
        raise ValueError(
            f"flash attention: sequence length {s} only tiles into "
            f"{b}-row blocks, below the {jnp.dtype(dtype).name} native "
            f"sublane tile ({need}); pad the sequence to a multiple of "
            f"{need} (ideally {pref})"
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


def _k_index_map(causal, bq, bk):
    """K/V/mask index map for the (b, qi, kj) grids. Causal grids still
    step through every (qi, kj) pair, but blocks past the diagonal are
    ``pl.when``-skipped — clamping the fetch index to the last contributing
    block means those steps re-request the block already in the window, so
    Mosaic issues no DMA for them (halves causal K/V traffic)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)


def _q_index_map_dkv(causal, bq, bk):
    """q/do/lse/delta index map for the dkv (b, kj, qi) grids: clamp UP to
    the first contributing query block (see `_k_index_map`)."""
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (b, jnp.maximum(i, (j * bk) // bq), 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q, k, v, kv_mask, scale, causal):
    o, _ = _flash_fwd_impl(q, k, v, kv_mask, scale, causal)
    return o


def _flash_fwd_impl(q, k, v, kv_mask, scale, causal, out_dtype=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, dtype=q.dtype)
    bk = _pick_block(sk, dtype=k.dtype)
    grid = (bh, sq // bq, sk // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=sk // bk
    )
    kmap = _k_index_map(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, bk, d), kmap),                        # k
        pl.BlockSpec((1, bk, d), kmap),                        # v
        pl.BlockSpec((1, bk, 1), kmap),                        # mask
    ]
    out_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
    ]
    out_o_dtype = out_dtype or q.dtype
    _check_specs(
        in_specs + out_specs,
        [((bh, sq, d), q.dtype), ((bh, sk, d), k.dtype),
         ((bh, sk, d), v.dtype), ((bh, sk, 1), kv_mask.dtype),
         ((bh, sq, d), out_o_dtype), ((bh, sq, 1), jnp.float32)],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),        # output accumulator
        ],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, kv_mask[:, :, None])
    return o, lse[:, :, 0]


def _flash_fwd(q, k, v, kv_mask, scale, causal):
    o, lse = _flash_fwd_impl(q, k, v, kv_mask, scale, causal)
    return o, (q, k, v, kv_mask, o, lse)


def flash_pair_fwd(q, k, v, kv_mask, scale, causal, out_dtype=None):
    """(o, lse) for one (q-block, k-block) pair over folded ``[BH, S, D]``
    operands — ring attention's per-step forward building block.
    ``out_dtype`` (default: q's dtype) lets the ring keep the per-block
    contributions in fp32 for its cross-block accumulation."""
    return _flash_fwd_impl(q, k, v, kv_mask, scale, causal,
                           out_dtype=out_dtype)


def flash_pair_dq(q, k, v, kv_mask, do, lse, delta, scale, causal,
                  out_dtype=None):
    """dQ for one (q-block, k-block) pair given GLOBAL ``lse``/``delta``
    (folded ``[BH, S, D]`` operands). This is the flash backward's dq leg;
    exposed separately so ring attention can run it per ring step."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, dtype=q.dtype)
    bk = _pick_block(sk, dtype=k.dtype)
    kmap = _k_index_map(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, bk, d), kmap),                        # k
        pl.BlockSpec((1, bk, d), kmap),                        # v
        pl.BlockSpec((1, bk, 1), kmap),                        # mask
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),   # lse
        pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),   # delta
    ]
    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    _check_specs(
        in_specs + out_specs,
        [((bh, sq, d), q.dtype), ((bh, sk, d), k.dtype),
         ((bh, sk, d), v.dtype), ((bh, sk, 1), kv_mask.dtype),
         ((bh, sq, d), do.dtype), ((bh, sq, 1), jnp.float32),
         ((bh, sq, 1), jnp.float32),
         ((bh, sq, d), out_dtype or q.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=sk // bk),
        grid=(bh, sq // bq, sk // bk),
        in_specs=in_specs,
        out_specs=out_specs[0],
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, kv_mask[:, :, None], do, lse[:, :, None],
      delta[:, :, None])


def flash_pair_dkv(q, k, v, kv_mask, do, lse, delta, scale, causal,
                   out_dtype=None):
    """dK/dV for one (q-block, k-block) pair given GLOBAL ``lse``/``delta``
    (see `flash_pair_dq`)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, dtype=q.dtype)
    bk = _pick_block(sk, dtype=k.dtype)
    qmap = _q_index_map_dkv(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), qmap),                        # q
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, bk, 1), lambda b, j, i: (b, j, 0)),   # mask
        pl.BlockSpec((1, bq, d), qmap),                        # do
        pl.BlockSpec((1, bq, 1), qmap),                        # lse
        pl.BlockSpec((1, bq, 1), qmap),                        # delta
    ]
    out_specs = [
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
    ]
    _check_specs(
        in_specs + out_specs,
        [((bh, sq, d), q.dtype), ((bh, sk, d), k.dtype),
         ((bh, sk, d), v.dtype), ((bh, sk, 1), kv_mask.dtype),
         ((bh, sq, d), do.dtype), ((bh, sq, 1), jnp.float32),
         ((bh, sq, 1), jnp.float32),
         ((bh, sk, d), out_dtype or k.dtype),
         ((bh, sk, d), out_dtype or v.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=sq // bq),
        grid=(bh, sk // bk, sq // bq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, kv_mask[:, :, None], do, lse[:, :, None],
      delta[:, :, None])


def _flash_bwd(scale, causal, res, do):
    q, k, v, kv_mask, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = flash_pair_dq(q, k, v, kv_mask, do, lse, delta, scale, causal)
    dk, dv = flash_pair_dkv(q, k, v, kv_mask, do, lse, delta, scale, causal)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Tiled exact attention over ``[B, S, H, D]`` inputs.

    ``kv_mask``: optional key-validity mask ``[B, S_k]`` (True = attend).
    Differentiable (flash backward). Sequence lengths must divide by the
    chosen block (128 or the largest power-of-two divisor).

    Sequence-length constraint (dtype-dependent): the block picked by
    halving 128 down to a divisor of ``S`` must be at least the dtype's
    native sublane tile — 8 rows for f32, **16 for bf16/f16**, 32 for
    8-bit types. A length whose largest such divisor falls below the tile
    (e.g. ``S=136`` in bf16: largest halving divisor 8) raises
    ``ValueError`` at trace time on every backend, because on a real TPU
    that block would mis-tile; pad the sequence to a multiple of 16
    (ideally 128). ``S`` at or below the preferred block (one block total)
    is always legal.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    if kv_mask is None:
        kv_mask = jnp.ones((B, Sk), jnp.int32)
    # [B,S,H,D] -> [B*H, S, D]; mask -> [B*H, Sk]
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    mask_bh = jnp.repeat(kv_mask.astype(jnp.int32), H, axis=0)
    o = _flash(fold(q), fold(k), fold(v), mask_bh, scale, causal)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def make_flash_attention_impl():
    """Model-zoo ``attention_impl`` (models/bert.py contract) backed by the
    kernel. Attention-prob dropout is not expressible in the tiled kernel,
    so a live dropout rate raises (as `models.gpt.flash_causal_attention_impl`
    does): a caller who asked for the kernel must never time the dense path
    under its name. Zero ``attention_probs_dropout_prob`` to use it."""

    def impl(q, k, v, mask, dropout_rng=None, dropout_rate=0.0, dtype=None):
        if dropout_rng is not None and dropout_rate > 0.0:
            raise ValueError(
                "flash attention kernel has no attention-dropout path; "
                "set attention_probs_dropout_prob=0"
            )
        with jax.named_scope("attention"):
            kv_mask = None
            if mask is not None:
                # model masks are ADDITIVE [B,1,1,S]; kernel wants validity
                kv_mask = mask.reshape(mask.shape[0], mask.shape[-1]) > -1.0
            return flash_attention(q, k, v, kv_mask=kv_mask)

    return impl
