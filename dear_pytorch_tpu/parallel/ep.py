"""Expert parallelism: GShard-style mixture-of-experts over an 'ep' axis.

Absent from the reference (SURVEY.md §2.9) — completes the framework's
parallelism axes (dp / sp / tp / pp / ep). The formulation is the canonical
TPU one (GShard, Lepikhin et al. 2020; Switch, Fedus et al. 2021): routing
becomes dense einsums against one-hot dispatch/combine tensors with a
STATIC per-expert capacity, so shapes stay fixed for XLA; the expert
weights carry a leading expert dim sharded over 'ep', and the SPMD
partitioner turns the dispatch einsums into the all-to-alls that
CUDA MoE frameworks schedule by hand.

Training runs through `parallel.tp.make_tp_train_step` with `EP_RULES`
(the machinery is generic: rules + annotations + jit), e.g.::

    step = make_tp_train_step(loss_fn, params, mesh=mesh,
                              rules=EP_RULES, tp_axis='ep')

`RoutedExperts` is the other expert layer here, the one present-day sparse
decoders use (`models/glm_moe.py`): top-k routing over ALL of the model's
experts, no capacity and no dropped token, computed for the experts THIS
chip holds (one share of an expert-parallel group). It runs without its
exchange: what the absent experts would add is left out, nothing stands in
for the absent chips. Its stacked ``wi`` / ``wo`` keep the leading expert
dimension `EP_RULES` shards.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from dear_pytorch_tpu.ops import grouped_matmul, moe_rows

EP_AXIS = "ep"

#: partition rules for `tp.make_tp_train_step(rules=EP_RULES, tp_axis='ep')`
EP_RULES: tuple = (
    (r"(^|/)wi$", lambda ep: jax.P(ep, None, None)),
    (r"(^|/)wo$", lambda ep: jax.P(ep, None, None)),
    # router stays replicated (matched by the default rule)
)


class MoeMlp(nn.Module):
    """Top-1 (switch) routed MLP with static capacity.

    Input ``[T, H]`` (flatten batch/sequence first). Tokens beyond an
    expert's capacity are dropped (output 0 for them — the standard switch
    behavior; pick ``capacity_factor`` >= num_experts to make dropping
    impossible in tests).
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        T, H = x.shape
        E = self.num_experts
        C = max(int(self.capacity_factor * T / E), 1)

        router = self.param(
            "router", nn.initializers.lecun_normal(), (H, E), jnp.float32
        )
        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (E, H, self.mlp_dim),
            jnp.float32,
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (E, self.mlp_dim, H),
            jnp.float32,
        )

        logits = x.astype(jnp.float32) @ router              # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                  # [T]
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)    # [T, E]
        # position of each token within its expert's queue (0-based); the
        # `- onehot` keeps non-selected entries at 0 so the row-sum is just
        # the selected expert's position
        pos = jnp.cumsum(onehot, axis=0) * onehot - onehot       # [T, E]
        pos_sel = jnp.sum(pos, axis=-1)                          # [T]
        # overflow positions (>= C) one-hot to an all-zero row: the token
        # is dropped without any explicit mask
        pos_oh = jax.nn.one_hot(
            pos_sel.astype(jnp.int32), C, dtype=jnp.float32
        )                                                        # [T, C]
        dispatch = onehot[:, :, None] * pos_oh[:, None, :]       # [T, E, C]
        gate = jnp.sum(probs * onehot, axis=-1)                  # [T]
        combine = dispatch * gate[:, None, None]                 # [T, E, C]

        xin = jnp.einsum("tec,th->ech", dispatch,
                         x.astype(jnp.float32))                  # [E, C, H]
        h = jax.nn.gelu(
            jnp.einsum("ech,ehf->ecf", xin, wi.astype(jnp.float32))
        )
        out_e = jnp.einsum("ecf,efh->ech", h, wo.astype(jnp.float32))
        y = jnp.einsum("tec,ech->th", combine, out_e)
        return y.astype(x.dtype)


def aux_load_balance_loss(x, router_kernel, num_experts: int) -> jax.Array:
    """Switch transformer's load-balancing auxiliary loss (Fedus et al.
    2021, eq. 4): E * <fraction routed to e> . <mean router prob for e>."""
    logits = x.astype(jnp.float32) @ router_kernel
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(jnp.argmax(probs, -1), num_experts)
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac * mean_prob)


# -- dropless top-k experts, one chip's share --------------------------------


@jax.custom_vjp
def _spread(x, order, inverse, valid):
    """Rows of ``x`` ``[T, H]`` in assignment order ``[T*k, H]``: row ``i``
    is token ``order[i] // k``. ``order`` is a permutation of the ``T*k``
    (token, slot) assignments and ``inverse`` its inverse, so the gradient
    is a gather by ``inverse`` and a sum over a token's ``k`` slots: no
    scatter-add in either direction. ``valid`` ``[T*k]`` marks the rows
    some held expert works on; the cotangent of any other row is whatever
    the grouped matmul left there and is dropped."""
    return x[order // (order.shape[0] // x.shape[0])]


def _spread_fwd(x, order, inverse, valid):
    return _spread(x, order, inverse, valid), (inverse, valid, x.shape[0])


def _spread_bwd(res, g):
    inverse, valid, tokens = res
    g = jnp.where(valid[:, None], g, 0)[inverse]
    return g.reshape(tokens, -1, g.shape[-1]).sum(axis=1), None, None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _unpermute(y, order, inverse):
    """``y[inverse]``: sorted rows back in (token, slot) order; the gradient
    is ``g[order]``."""
    return y[inverse]


def _unpermute_fwd(y, order, inverse):
    return y[inverse], order


def _unpermute_bwd(order, g):
    return g[order], None, None


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


class RoutedExperts(nn.Module):
    """Top-k routed SwiGLU experts without dropped tokens: the part of
    ``sum_k w_k * Expert_{idx_k}(x)`` whose experts live here.

    The router scores all ``router_width`` experts of the model; this chip
    holds ``experts_held`` of them, from ``expert_offset`` on. Scores are
    ``sigmoid`` (DeepSeek-V3's ``noaux_tc``: the top-k is taken on
    ``score + router_bias``, the weights on the score alone) or ``softmax``;
    ``norm_topk_prob`` divides the k weights by their sum (plus
    ``norm_topk_eps``, a constant of the source model's code), then
    ``routed_scaling_factor`` scales them. ``router_bias`` (the
    ``e_score_correction_bias``) is a parameter leaf behind `stop_gradient`:
    it moves the selection and receives no gradient. With ``n_group`` > 1
    the selection is group-limited (DeepSeek-V3's): the router's experts
    fall into ``n_group`` equal groups, each group scores the sum of its two
    best ``score + router_bias``, and the top-k is taken within the
    ``topk_group`` best groups; ``n_group`` 1 is the flat top-k.

    Dropless under any imbalance: the ``T*k`` assignments are sorted by
    held expert (absent ones last, in no group) and the feed-forward runs
    as grouped matmuls over the held experts' rows, so the buffers are
    ``T*k`` rows whatever the routing: the worst case, every token choosing
    k held experts, fits. Rows in no group are kept out of every result
    and every gradient. Two pairs of paths, each chosen from the backend,
    the shapes and the dtype alone (same mathematics, still dropless: with
    every assignment held the kernels walk all ``T*k`` rows):

    the rows between token order and sorted order: on a TPU, with rows of
    whole 128-lane tiles, the kernels of `ops.moe_rows`, which read the
    count of the held experts' rows on the device and neither read nor
    write the others; elsewhere the gathers `_spread` / `_unpermute`, the
    kernels' reference;

    the feed-forward between them: on a TPU, in bfloat16, with ``H`` and
    ``F`` whole lane tiles, the six kernels of `ops.grouped_matmul`, whose
    row tiles follow the groups (none past the count is visited), with the
    SwiGLU and its derivative in the matmuls' epilogues and f32
    accumulation; elsewhere (f32, the CPU, `init`) two `jax.lax.ragged_dot`s with the
    activation and an explicit mask on the rows of no group between them
    (XLA:TPU's grouped-matmul kernel leaves those unwritten), the kernels'
    reference.

    Input ``[T, H]``; returns the routed part ``[T, H]`` (add the shared
    expert outside: every chip computes that alike). Sows the assignments per
    held expert ``[experts_held]`` into ``intermediates/assignments``.
    ``wi`` is ``[E, H, 2F]``, gate then up.
    """

    router_width: int
    experts_held: int
    top_k: int
    mlp_dim: int
    expert_offset: int = 0
    scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-20
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros

    def route(self, x, router, router_bias):
        """(idx ``[T, k]`` over all experts, weights ``[T, k]`` f32)."""
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=lax.Precision.HIGHEST)
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif self.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown scoring {self.scoring!r}")
        choice = scores + lax.stop_gradient(router_bias)
        if self.n_group > 1:
            choice = self.limit_to_groups(choice)
        _, idx = lax.top_k(choice, self.top_k)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True)
                                 + self.norm_topk_eps)
        return idx, weights * self.routed_scaling_factor

    def limit_to_groups(self, choice):
        """``choice`` ``[T, router_width]`` with the experts outside each
        token's ``topk_group`` best groups at ``-inf``; a group's score is
        the sum of its two best entries."""
        T, width = choice.shape
        if width % self.n_group or (self.topk_group * width // self.n_group
                                    < self.top_k):
            raise ValueError(
                f"{self.n_group} groups of {width} experts, {self.topk_group}"
                f" kept, cannot give {self.top_k} experts a token")
        grouped = choice.reshape(T, self.n_group, width // self.n_group)
        best = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)     # [T, groups]
        _, kept = lax.top_k(best, self.topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(self.n_group), axis=1)
        return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(T, width)

    @nn.compact
    def __call__(self, x):
        T, H = x.shape
        E, k, F = self.experts_held, self.top_k, self.mlp_dim
        if not 0 <= self.expert_offset <= self.router_width - E:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + E}) "
                f"are not among the router's {self.router_width}")
        router = self.param("router", self.kernel_init,
                            (H, self.router_width), jnp.float32)
        router_bias = self.param("router_bias", self.bias_init,
                                 (self.router_width,), jnp.float32)
        wi = self.param("wi", self.kernel_init, (E, H, 2 * F), jnp.float32)
        wo = self.param("wo", self.kernel_init, (E, F, H), jnp.float32)

        with jax.named_scope("route"):
            idx, weights = self.route(x, router, router_bias)
        with jax.named_scope("dispatch"):
            local = idx - self.expert_offset
            held = (local >= 0) & (local < E)                    # [T, k]
            group = jnp.where(held, local, E).reshape(T * k)     # absent: E
            order = jnp.argsort(group, stable=True)
            inverse = jnp.argsort(order)
            sizes = jnp.sum(group[:, None] == jnp.arange(E)[None],
                            axis=0, dtype=jnp.int32)             # [E]
            self.sow("intermediates", "assignments", sizes)
            # sorted rows past the held experts' groups belong to no group:
            # the grouped matmul neither reads nor writes them (on the TPU
            # they hold whatever the buffer held), forward and backward
            valid = jnp.arange(T * k) < jnp.sum(sizes)
            # on a TPU, rows of whole lane tiles: kernels that move the held
            # experts' rows only (`ops.moe_rows`); else the gathers below,
            # which stay as their reference (and serve `init`, whose program
            # would pay the kernels' tracing for values it throws away)
            kernels = (moe_rows.applies(T, k, H)
                       and not self.is_initializing())
            if kernels:
                moved = moe_rows.dispatch(group.reshape(T, k), order, inverse,
                                          sizes)
                xs = moe_rows.spread(x.astype(self.dtype), moved)
            else:
                xs = _spread(x.astype(self.dtype), order, inverse, valid)
        with jax.named_scope("experts"):
            # on a TPU, bf16 and whole lane tiles: grouped-matmul kernels
            # that walk the held experts' rows only, the SwiGLU in their
            # epilogues (`ops.grouped_matmul`); else XLA's grouped matmul
            # and the mask, their reference
            if (grouped_matmul.applies(T * k, H, F, self.dtype)
                    and not self.is_initializing()):
                ys = grouped_matmul.feed_forward(
                    xs, wi.astype(self.dtype), wo.astype(self.dtype), sizes)
            else:
                gate_up = lax.ragged_dot(xs, wi.astype(self.dtype), sizes)
                gate_up = jnp.where(valid[:, None], gate_up, 0)
                act = jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:]
                ys = lax.ragged_dot(act, wo.astype(self.dtype), sizes)
        with jax.named_scope("combine"):
            if kernels:
                return moe_rows.combine(ys, weights, moved, x.dtype)
            back = _unpermute(ys, order, inverse).reshape(T, k, H)
            # a row outside every group is no expert's output: leave it out
            back = jnp.where(held[..., None], back.astype(jnp.float32), 0.0)
            return jnp.sum(back * weights[..., None], axis=1).astype(x.dtype)
