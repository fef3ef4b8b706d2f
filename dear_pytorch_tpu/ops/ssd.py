"""Mamba-2's state-space recurrence by chunks (SSD: Dao & Gu 2024,
"Transformers are SSMs", section 6), forward and backward, in jittable XLA.

The recurrence, a head ``h`` of width ``p`` with a state ``[p, n]``; ``B``
and ``C`` are shared by the ``heads / groups`` heads of a group:

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t        S_{-1} = 0
    y_t = S_t C_t + D * x_t

With ``a_t = dt_t * A`` (<= 0) and ``cum`` its inclusive running sum inside
a chunk of ``Q`` positions, the same ``y`` is four batched matmuls a chunk
and one short recurrence over the ``S / Q`` chunks:

    intra   Y[i] += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    states  Z_c   = sum_j exp(cum_last - cum_j) dt_j x_j (x) B_j
    carry   S_c   = exp(cum_last of chunk c-1) S_{c-1} + Z_{c-1}   (lax.scan)
    inter   Y[i] += exp(cum_i) (S_c C_i)

Precision: ``cum``, every exponential and the carried states are float32
whatever the operands' dtype. A decay factor is only ever ``exp`` of a
non-positive difference of running sums: the upper triangle is masked to
``-inf`` BEFORE the exponential and nothing is divided by a decay, so no
factor overflows and none is ``0 / 0`` however long the chunk or fast the
decay. The matmuls take their operands in ``x``'s dtype (bf16 in the
benchmark's cell) and accumulate in float32.

The backward pass recomputes: the whole core sits under `jax.checkpoint`,
so a layer's residuals are the op's inputs alone, and the ``[chunks, h, Q,
Q]`` decay and ``C B^T`` matrices (0.27 GB a layer in float32 at S=4096,
Q=256, 64 heads), the chunk states and the carried states exist only while
one layer's gradient is computed, as the source's kernels recompute them.
`jax.checkpoint` and not a hand-written `custom_vjp`: the gradient of four
einsums, a cumsum and a scan is what autodiff writes anyway, a hand-written
one would hold the same intermediates, and the recomputation is a third
more matmul work in an op whose time is its elementwise passes over the
decay matrices (docs/KERNELS.md has the chip timings). A Pallas kernel that
keeps those matrices in VMEM is ROADMAP A11.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def ssd_chunked_scan(x, dt, A, B, C, D, chunk: int):
    """``y [B, S, h, p]`` of the recurrence above.

    ``x [B, S, h, p]``; ``dt [B, S, h]`` the discretisation step (already
    positive: the caller's ``softplus``); ``A [h]`` negative; ``B`` and ``C``
    ``[B, S, g, n]`` with ``g`` dividing ``h`` (head ``i`` reads group
    ``i // (h / g)``); ``D [h]`` the skip. ``S`` is a multiple of ``chunk``.
    Differentiable in every array argument; the result is in ``x``'s dtype.
    """
    seq, heads, groups = x.shape[1], x.shape[2], B.shape[2]
    if seq % chunk:
        raise ValueError(f"sequence {seq} is not a multiple of the chunk "
                         f"{chunk}")
    if heads % groups:
        raise ValueError(f"{groups} groups do not divide {heads} heads")
    return _core(x, dt, A, B, C, D, chunk)


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _core(x, dt, A, B, C, D, chunk):
    batch, seq, heads, width = x.shape
    groups, state = B.shape[2], B.shape[3]
    per, chunks, dtype = heads // groups, seq // chunk, x.dtype
    f32 = jnp.float32

    # [b, chunk c, position, group g, head of the group r, ...]
    def by_chunk(t, *tail):
        return t.reshape(batch, chunks, chunk, *tail)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=f32)

    dt = by_chunk(dt.astype(f32), groups, per)
    xs = by_chunk(x, groups, per, width)
    Bs, Cs = by_chunk(B, groups, state), by_chunk(C, groups, state)
    cum = jnp.cumsum(dt * A.astype(f32).reshape(groups, per), axis=2)
    last = cum[:, :, -1]                                    # [b, c, g, r]

    # intra-chunk: the [Q, Q] lower-triangular decay times C B^T, a head's
    # matrix in the two minor dimensions
    rows = jnp.moveaxis(cum, 2, -1)                         # [b, c, g, r, i]
    lower = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(lower, rows[..., :, None] - rows[..., None, :],
                              -jnp.inf))                    # [b,c,g,r,i,j]
    scores = dot("bcign,bcjgn->bcgij", Cs, Bs)
    mixed = (scores[:, :, :, None] * decay).astype(dtype)
    xdt = (xs.astype(f32) * dt[..., None]).astype(dtype)
    y = dot("bcgrij,bcjgrp->bcigrp", mixed, xdt)

    # what each chunk adds to the state by its end, then the states that
    # enter each chunk (the one recurrence, S / Q steps long)
    to_end = jnp.exp(last[:, :, None] - cum)                # [b, c, j, g, r]
    added = dot("bcjgn,bcjgrp->bcgrpn", Bs,
                (xs.astype(f32) * (dt * to_end)[..., None]).astype(dtype))

    def carry(entering, chunk_c):
        total, added_c = chunk_c
        return jnp.exp(total)[..., None, None] * entering + added_c, entering

    _, entering = lax.scan(
        carry, jnp.zeros(added.shape[:1] + added.shape[2:], f32),
        (jnp.moveaxis(last, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [b,c,g,r,p,n]

    # inter-chunk: the entering state read by C, decayed to each position
    y = y + (dot("bcign,bcgrpn->bcigrp", Cs, entering.astype(dtype))
             * jnp.exp(cum)[..., None])
    y = y + D.astype(f32).reshape(groups, per)[:, :, None] * xs.astype(f32)
    return y.reshape(x.shape).astype(dtype)
