"""Present-day sparse decoder (GLM-4.x / DeepSeek-V3 family,
``model_type: glm4_moe_lite``): pre-norm RMSNorm blocks, multi-head latent
attention with rotary positions on a 64-lane slice of every head, SwiGLU
MLPs, a leading dense layer and then expert layers (top-k of all routed
experts by sigmoid score plus a selection-only bias, beside a shared
expert), an untied head, and an optional multi-token-prediction module
(DeepSeek-V3 report, eq. 21-25) that shares embedding and head.

Training form only: attention runs un-absorbed (full per-head keys and
values are built from the latent); the compressed cache and the absorbed
decode path are serving's. One chip may hold a share of an expert-parallel
group: `GlmMoeConfig.experts_held` of `n_routed_experts`, from
`expert_offset` on (`parallel.ep.RoutedExperts`), and a slice of the
vocabulary (``vocab_size`` is then the slice).

Named scopes are chosen so that the step's device time is booked as GPT-2's
is (docs/OBSERVABILITY.md): the latent projections, their norms and the
rotary under ``query`` / ``key`` / ``value`` / ``output``, the attention core
under the bare ``attention``, the MLPs under ``mlp`` (``mlp/moe/{route,
dispatch,experts,combine}``, ``mlp/shared``), both heads and cross-entropies
under ``loss``, and the prediction module's pieces additionally under
``mtp``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dear_pytorch_tpu.models.gpt import causal_attention
from dear_pytorch_tpu.models.losses import next_token_cross_entropy


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    #: experts the router scores; this chip holds ``experts_held`` of them
    #: (None: all), from ``expert_offset`` on
    n_routed_experts: int = 64
    experts_held: Any = None
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    #: 0 or 1 multi-token-prediction modules after the last block
    num_nextn_predict_layers: int = 1
    #: weight of the prediction module's loss (not in config.json; the
    #: GLM-4.5 report's first-stage value)
    mtp_loss_weight: float = 0.3
    #: rematerialize each block in the backward pass (jax.checkpoint)
    remat: bool = False
    dtype: Any = jnp.float32

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


#: GLM-4.7-Flash as published (zai-org/GLM-4.7-Flash config.json): 47
#: layers, 64 routed experts, 154,880 ids; 30B parameters, no one chip's
GLM47_FLASH = GlmMoeConfig()
#: the same block at test sizes
GLM_MOE_TINY = GlmMoeConfig(
    vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, intermediate_size=128,
    moe_intermediate_size=48, n_routed_experts=16)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + self.eps)
        return (y * scale).astype(self.dtype)


def rotary_tables(seq_len: int, dim: int, theta: float):
    """(cos, sin) ``[S, dim/2]`` f32 for positions ``0..S-1``."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, rope):
    """Rotate ``x`` ``[B, S, heads, dim]``; lane ``i`` pairs with lane
    ``i + dim/2`` (the half-split convention)."""
    cos, sin = (t[None, :, None, :] for t in rope)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class GlmBlock(nn.Module):
    """``x + MLA(RMSNorm(x))``, then ``x + FFN(RMSNorm(x))``; ``kind`` is
    ``"dense"`` (SwiGLU at ``intermediate_size``) or ``"expert"`` (the routed
    experts held here plus the shared expert)."""

    config: GlmMoeConfig
    kind: str

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        B, S, H = x.shape
        nh, nope, rot = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        init = nn.initializers.normal(cfg.initializer_range)

        def dense(features, name, axis=-1):
            return nn.DenseGeneral(features, axis=axis, use_bias=False,
                                   dtype=cfg.dtype, kernel_init=init,
                                   name=name)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        y = norm("ln_1")(x)
        with jax.named_scope("query"):
            c_q = norm("q_ln")(dense(cfg.q_lora_rank, "q_down")(y))
            q = dense((nh, cfg.qk_head_dim), "q_up")(c_q)
            q = jnp.concatenate(
                [q[..., :nope], apply_rotary(q[..., nope:], rope)], axis=-1)
        with jax.named_scope("key"):
            kv = dense(cfg.kv_lora_rank + rot, "kv_down")(y)
            c_kv = norm("kv_ln")(kv[..., :cfg.kv_lora_rank])
            # one rotary key head, shared by every head
            k_rope = apply_rotary(kv[..., None, cfg.kv_lora_rank:], rope)
            k = jnp.concatenate(
                [dense((nh, nope), "k_up")(c_kv),
                 jnp.broadcast_to(k_rope, (B, S, nh, rot))], axis=-1)
        with jax.named_scope("value"):
            v = dense((nh, cfg.v_head_dim), "v_up")(c_kv)
        # the flash kernel where `models.gpt.flash_core_applies`, else dense
        ctx = causal_attention(q, k, v, None, dtype=cfg.dtype)
        x = x + dense(H, "output", axis=(-2, -1))(ctx)

        y = norm("ln_2")(x)
        with jax.named_scope("mlp"):
            if self.kind == "dense":
                y = _swiglu(dense, y, cfg.intermediate_size, "mlp")
            else:
                # lazy import: models<->parallel would otherwise cycle
                from dear_pytorch_tpu.parallel.ep import RoutedExperts

                routed = RoutedExperts(
                    router_width=cfg.n_routed_experts,
                    experts_held=(cfg.experts_held
                                  or cfg.n_routed_experts),
                    expert_offset=cfg.expert_offset,
                    top_k=cfg.num_experts_per_tok,
                    mlp_dim=cfg.moe_intermediate_size,
                    norm_topk_prob=cfg.norm_topk_prob,
                    routed_scaling_factor=cfg.routed_scaling_factor,
                    dtype=cfg.dtype, kernel_init=init, bias_init=init,
                    name="moe")(y.reshape(B * S, H)).reshape(B, S, H)
                with jax.named_scope("shared"):
                    y = routed + _swiglu(
                        dense, y,
                        cfg.n_shared_experts * cfg.moe_intermediate_size,
                        "shared")
        return x + y


def _swiglu(dense, y, width, name):
    gate = dense(width, f"{name}_gate")(y)
    return dense(y.shape[-1], f"{name}_down")(
        jax.nn.silu(gate) * dense(width, f"{name}_up")(y))


class GlmMoeLmHeadModel(nn.Module):
    """``__call__(input_ids)`` -> ``(logits, mtp_logits)``, both
    ``[B, S, vocab]`` f32. ``logits[:, i]`` predict token ``i + 1``;
    ``mtp_logits[:, i]`` (None without the prediction module) predict token
    ``i + 2`` from the last block's state at ``i`` and the embedding of
    token ``i + 1``. The module runs on all ``S`` positions so that the
    attention core keeps its tiling (the last one sees a wrapped-around
    token; `glm_moe_lm_loss` leaves it out)."""

    config: GlmMoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        init = nn.initializers.normal(cfg.initializer_range)
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=init,
                       dtype=cfg.dtype, name="wte")
        lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, name="lm_head")
        block_cls = nn.remat(GlmBlock) if cfg.remat else GlmBlock
        rope = rotary_tables(input_ids.shape[1], cfg.qk_rope_head_dim,
                             cfg.rope_theta)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        def head(h, ln_name):
            h = norm(ln_name)(h)
            with jax.named_scope("loss"):
                return lm_head(h).astype(jnp.float32)

        x = wte(input_ids)
        for i in range(cfg.num_layers):
            kind = "dense" if i < cfg.first_k_dense_replace else "expert"
            x = block_cls(cfg, kind, name=f"h_{i}")(x, rope)
        logits = head(x, "ln_f")
        if not cfg.num_nextn_predict_layers:
            return logits, None
        with jax.named_scope("mtp"):
            with jax.named_scope("input_embeddings"):
                nxt = wte(jnp.roll(input_ids, -1, axis=1))
                merged = jnp.concatenate(
                    [norm("ln_mtp_h")(x), norm("ln_mtp_e")(nxt)], axis=-1)
                h = nn.Dense(cfg.hidden_size, use_bias=False,
                             dtype=cfg.dtype, kernel_init=init,
                             name="mtp_eh_proj")(merged)
            h = block_cls(cfg, "expert", name="mtp_block")(h, rope)
            return logits, head(h, "ln_mtp_f")


def glm_moe_lm_loss(outputs, input_ids, *, mtp_loss_weight: float = 0.3):
    """``L_main + mtp_loss_weight * L_mtp`` of `GlmMoeLmHeadModel`'s
    ``(logits, mtp_logits)``: next-token cross-entropy, and the prediction
    module's against the token two ahead (the targets shifted, the logits
    never sliced: `models.losses.next_token_cross_entropy`)."""
    logits, mtp_logits = outputs
    with jax.named_scope("loss"):
        loss = next_token_cross_entropy(logits, input_ids)
    if mtp_logits is not None:
        with jax.named_scope("mtp"), jax.named_scope("loss"):
            loss = loss + mtp_loss_weight * next_token_cross_entropy(
                mtp_logits, input_ids, ahead=2)
    return loss


def expert_assignments(cfg: GlmMoeConfig, intermediates) -> jax.Array:
    """``[expert layers, experts held]`` assignments made to each held
    expert, from the ``intermediates`` collection of one
    ``model.apply(..., mutable=["intermediates"])``: the blocks in order,
    the prediction module's last."""
    names = [f"h_{i}" for i in range(cfg.first_k_dense_replace,
                                     cfg.num_layers)]
    if cfg.num_nextn_predict_layers:
        names.append("mtp_block")
    return jnp.stack([intermediates[n]["moe"]["assignments"][0]
                      for n in names]).astype(jnp.float32)
