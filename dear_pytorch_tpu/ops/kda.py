"""Kimi Delta Attention (KDA: Kimi Linear, arXiv:2510.26692): a delta rule
with a decay per key channel, by chunks, forward and backward, in jittable
XLA.

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
docstring has the reasons). A Pallas kernel holding a chunk's matrices in
VMEM is ROADMAP's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

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
    ``chunk``; ``scale`` defaults to ``K ** -0.5``. ``heads_at_once`` (a
    divisor of ``H``; None: all) runs the heads that many at a time, one
    after another (a `lax.map`): the backward pass's recomputed chunk
    matrices are then that share of the whole, the recurrence over chunks
    runs ``H / heads_at_once`` times. Differentiable in every array
    argument."""
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
    statics = (chunk, sub_chunk(chunk, lower_bound), scale,
               float(lower_bound))
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
