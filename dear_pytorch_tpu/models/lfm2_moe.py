"""Hybrid sparse decoder (LFM2-MoE family, ``model_type: lfm2_moe``):
pre-norm RMSNorm blocks whose mixer is chosen per layer from
``layer_types``, a gated short convolution (``conv``) or grouped-query
attention with per-head QK-norm before the rotary embedding
(``full_attention``); SwiGLU MLPs, ``num_dense_layers`` leading dense layers
and then expert layers (top-k of all experts by sigmoid score plus a
selection-only bias, **no** shared expert); a head tied to the embedding.

    h   = x + Operator(RMSNorm(x))          out = h + FFN(RMSNorm(h))
    conv:       [B ; C ; x~] = W_in y,  u = B * x~,
                c_t = sum_{j<L} w[j] * u_{t-(L-1)+j}   (depthwise, causal)
                Operator(y) = W_out (C * c)            (no activation)
    attention:  q, k = rotary(RMSNorm_head(W_q y)), rotary(RMSNorm_head(W_k y))
                Operator(y) = W_o softmax_causal(q k^T / sqrt(d)) v

Training form only (no convolution state, no KV cache: those are serving's).
One chip may hold a share of an expert-parallel group:
`Lfm2MoeConfig.experts_held` of ``num_experts``, from ``expert_offset`` on
(`parallel.ep.RoutedExperts`), and a slice of the vocabulary (``vocab_size``
is then the slice; the tied head and the loss are over the slice).

Named scopes (docs/OBSERVABILITY.md): the short convolution under ``conv``
(``conv/in_proj``, ``conv/filter``: both gates and the taps, everything
elementwise; ``conv/out_proj``); the attention layer's projections, QK-norm
and rotary under ``query`` / ``key`` / ``value`` / ``output``, its core under
the bare ``attention``; both FFN kinds under ``mlp``
(``mlp/moe/{route,dispatch,experts,combine}``); the tied head and the
cross-entropy under ``loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dear_pytorch_tpu.models.glm_moe import (
    RMSNorm,
    _swiglu,
    apply_rotary,
    rotary_tables,
)
from dear_pytorch_tpu.models.gpt import causal_attention
from dear_pytorch_tpu.models.losses import next_token_cross_entropy

#: LFM2-8B-A1B's mixers: 18 short convolutions, attention at 2, 6, 10, 14,
#: 18 and 21
_LFM2_8B_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    #: the mixer of each layer: ``"conv"`` or ``"full_attention"``
    layer_types: tuple = _LFM2_8B_LAYERS
    #: leading layers whose FFN is the dense SwiGLU
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    #: experts the router scores; this chip holds ``experts_held`` of them
    #: (None: all), from ``expert_offset`` on
    num_experts: int = 32
    experts_held: Any = None
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: the selection-only ``expert_bias``; without it the leaf is zero
    use_expert_bias: bool = True
    #: taps of the depthwise causal convolution
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    #: the inference limit; sizes nothing here (rotary positions, no table)
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    #: rematerialize each block in the backward pass (jax.checkpoint)
    remat: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.conv_bias:
            raise ValueError("the short convolution has no bias path")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


#: LFM2-8B-A1B as published (LiquidAI/LFM2-8B-A1B config.json): 24 layers,
#: 32 experts, 65,536 ids; 8.3B parameters, no one chip's
LFM2_8B_A1B = Lfm2MoeConfig()
#: the same blocks at test sizes: a dense conv layer, then one period
LFM2_MOE_TINY = Lfm2MoeConfig(
    vocab_size=96, hidden_size=64, num_hidden_layers=5,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, moe_intermediate_size=48, num_experts=16)


def causal_depthwise_conv(u, taps):
    """``c_t = sum_j taps[j] * u_{t-(L-1)+j}`` of ``u`` ``[B, S, C]`` with
    ``taps`` ``[L, C]``, zeros before the sequence: ``L`` shifted
    multiply-adds (on the chip they beat `lax.conv_general_dilated`
    depthwise, PERF.md PR 35). Shared with `models.granite_hybrid`'s Mamba-2
    mixer (four taps, a bias and a silu after it)."""
    seq, lag = u.shape[1], taps.shape[0] - 1
    padded = jnp.pad(u, ((0, 0), (lag, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + seq] for j in range(lag + 1))


def short_conv_filter(gates, taps):
    """``C * conv(B * x~)`` of ``gates`` ``[B, S, 3H]`` (``B``, ``C``,
    ``x~`` side by side, the source's order) with ``taps`` ``[L, H]``: the
    depthwise causal convolution (`causal_depthwise_conv`) between its two
    multiplicative gates. Elementwise throughout, f32 arithmetic, result in
    ``gates``' dtype."""
    b, c, x = jnp.split(gates.astype(jnp.float32), 3, axis=-1)
    return (c * causal_depthwise_conv(b * x, taps)).astype(gates.dtype)


class ShortConv(nn.Module):
    """The gated short convolution operator ``W_out (C * conv(B * x~))``."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        H = y.shape[-1]
        init = nn.initializers.normal(cfg.initializer_range)
        gates = nn.Dense(3 * H, use_bias=False, dtype=cfg.dtype,
                         kernel_init=init, name="in_proj")(y)
        taps = self.param("filter", init, (cfg.conv_L_cache, H), jnp.float32)
        with jax.named_scope("filter"):
            gated = short_conv_filter(gates, taps)
        return nn.Dense(H, use_bias=False, dtype=cfg.dtype, kernel_init=init,
                        name="out_proj")(gated)


class Lfm2Block(nn.Module):
    """``x + Operator(RMSNorm(x))``, then ``x + FFN(RMSNorm(x))``; ``mixer``
    is a ``layer_types`` entry, ``ffn`` ``"dense"`` (SwiGLU at
    ``intermediate_size``) or ``"expert"`` (the routed experts held here)."""

    config: Lfm2MoeConfig
    mixer: str
    ffn: str

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        B, S, H = x.shape
        init = nn.initializers.normal(cfg.initializer_range)

        def dense(features, name, axis=-1):
            return nn.DenseGeneral(features, axis=axis, use_bias=False,
                                   dtype=cfg.dtype, kernel_init=init,
                                   name=name)

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.dtype, name=name)

        y = norm("ln_1")(x)
        if self.mixer == "conv":
            x = x + ShortConv(cfg, name="conv")(y)
        else:
            nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
            # the norm of a head (one [d] weight for all heads of a kind)
            # comes before its rotation
            with jax.named_scope("query"):
                q = apply_rotary(norm("q_ln")(dense((nh, d), "q_proj")(y)),
                                 rope)
            with jax.named_scope("key"):
                k = apply_rotary(norm("k_ln")(dense((nkv, d), "k_proj")(y)),
                                 rope)
            with jax.named_scope("value"):
                v = dense((nkv, d), "v_proj")(y)
            # the grouped flash kernel where `models.gpt.flash_core_applies`,
            # else the dense program (which repeats k and v)
            ctx = causal_attention(q, k, v, None, dtype=cfg.dtype)
            x = x + dense(H, "output", axis=(-2, -1))(ctx)

        y = norm("ln_2")(x)
        with jax.named_scope("mlp"):
            if self.ffn == "dense":
                y = _swiglu(dense, y, cfg.intermediate_size, "mlp")
            else:
                # lazy import: models<->parallel would otherwise cycle
                from dear_pytorch_tpu.parallel.ep import RoutedExperts

                y = RoutedExperts(
                    router_width=cfg.num_experts,
                    experts_held=cfg.experts_held or cfg.num_experts,
                    expert_offset=cfg.expert_offset,
                    top_k=cfg.num_experts_per_tok,
                    mlp_dim=cfg.moe_intermediate_size,
                    norm_topk_prob=cfg.norm_topk_prob,
                    norm_topk_eps=1e-6,     # the source's literal
                    routed_scaling_factor=cfg.routed_scaling_factor,
                    dtype=cfg.dtype, kernel_init=init,
                    bias_init=(init if cfg.use_expert_bias
                               else nn.initializers.zeros),
                    name="moe")(y.reshape(B * S, H)).reshape(B, S, H)
        return x + y


class Lfm2MoeLmHeadModel(nn.Module):
    """``__call__(input_ids)`` -> logits ``[B, S, vocab]`` f32 over the
    vocabulary slice held here; ``logits[:, i]`` predict token ``i + 1``."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       embedding_init=nn.initializers.normal(
                           cfg.initializer_range), name="wte")
        block_cls = nn.remat(Lfm2Block) if cfg.remat else Lfm2Block
        rope = rotary_tables(input_ids.shape[1], cfg.head_dim, cfg.rope_theta)
        x = wte(input_ids)
        for i, mixer in enumerate(cfg.layer_types):
            ffn = "dense" if i < cfg.num_dense_layers else "expert"
            x = block_cls(cfg, mixer, ffn, name=f"h_{i}")(x, rope)
        # the source's `embedding_norm`
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="ln_f")(x)
        with jax.named_scope("loss"):
            return wte.attend(x).astype(jnp.float32)


def lfm2_moe_lm_loss(logits, input_ids):
    """Next-token cross-entropy of `Lfm2MoeLmHeadModel`'s logits (the
    targets shifted, the logits never sliced:
    `models.losses.next_token_cross_entropy`)."""
    with jax.named_scope("loss"):
        return next_token_cross_entropy(logits, input_ids)


def expert_assignments(cfg: Lfm2MoeConfig, intermediates) -> jax.Array:
    """``[expert layers, experts held]`` assignments made to each held
    expert, from the ``intermediates`` collection of one
    ``model.apply(..., mutable=["intermediates"])``: the blocks in order."""
    return jnp.stack([
        intermediates[f"h_{i}"]["moe"]["assignments"][0]
        for i in range(cfg.num_dense_layers, cfg.num_hidden_layers)
    ]).astype(jnp.float32)
