"""Hybrid linear-attention sparse decoder (Ling-3.0 family, ``model_type:
bailing_hybrid``): pre-norm RMSNorm blocks whose mixer is Kimi Delta
Attention (``kda``) wherever ``(i + 1) % layer_group_size != 0`` and
multi-head latent attention (``mla``) at the other layers (five to one);
SwiGLU MLPs, dense in the leading ``first_k_dense_replace`` layers, then
group-limited top-k routed experts beside a shared expert; an untied head;
one multi-token-prediction module (DeepSeek-V3 report, eq. 21-25) whose block
is an MLA expert block sharing embedding and head. No projection has a bias.

    kda:  q = l2norm(silu(conv4(W_q y)))   k = l2norm(silu(conv4(W_k y)))
          v = silu(conv4(W_v y))           conv4: causal, depthwise, no bias
          beta_t = sigmoid(W_b y_t)                          one a head
          log alpha_t = lower_bound * sigmoid(exp(A_log) (W_f y_t + dt_bias))
          S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
          o_t = S_t^T q_t / sqrt(d)                          (`ops.kda`)
          Mixer = W_o [RMSNorm_head(o_t) * sigmoid(W_g y_t)]
    mla:  q = W_q y (no low rank); [c_kv | k_rope] = W_kv_a y;
          [k_nope | v] = W_kv_b RMSNorm(c_kv); a per-head RMSNorm of q and
          of k = [k_nope | k_rope] over their 192 lanes, then an interleaved
          rotary on the last 64 (lanes 2i, 2i + 1; one k_rope head shared);
          softmax(q k^T / sqrt(192)) v, each head's context times
          sigmoid(w_{g,h} . y), then W_o

Training form only (no recurrent state or latent cache: those are
serving's). One chip may hold a share of a deployment: `layer_types` names
the layers held here (from published layer ``first_layer``),
``experts_held`` of ``num_experts`` from ``expert_offset`` on
(`parallel.ep.RoutedExperts`), and a slice of the vocabulary.

The MLA core runs through `models.gpt.causal_attention` (the flash kernel
on a TPU), whose kernels take q, k and v of one width (`core_width`): q and
k are padded with zero lanes from 192 to 256 and v from 128 to 256, the
context sliced back. The kernel scales by 1/16; q carries the rest of
1/sqrt(192), applied in float32 with the norm and the rotary before q's one
rounding.

Named scopes (docs/OBSERVABILITY.md): ``kda/{in_proj, conv, gate, scan,
out_norm_gate, out_proj}`` (all six input projections under ``in_proj``,
`ops.kda.kda_chunked` under ``scan``); MLA's projections, norms and rotary
under ``query`` / ``key`` / ``value``, its output gate and ``W_o`` under
``output``, the core under the bare ``attention``; the MLPs under ``mlp``
(``mlp/moe/{route,dispatch,experts,combine}``, ``mlp/shared``); heads and
cross-entropies under ``loss``; the prediction module's pieces also under
``mtp``.

``A_log``, ``dt_bias``, the convolutions' taps and every norm weight are
float32 leaves used in float32 arithmetic whatever ``dtype`` is.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dear_pytorch_tpu.models.glm_moe import RMSNorm, _swiglu
from dear_pytorch_tpu.models.gpt import causal_attention
from dear_pytorch_tpu.models.granite_hybrid import _a_log_init, _dt_bias_init
from dear_pytorch_tpu.models.lfm2_moe import causal_depthwise_conv
from dear_pytorch_tpu.ops.kda import kda_chunked

#: the KDA scan's output, named for `_BLOCK_POLICY`
_SCAN_OUT = "kda_out"

#: KDA heads a pass of XLA's chunked scan (`ops.kda.kda_chunked`'s
#: ``heads_at_once``, off the TPU kernels' path): the op's backward working
#: set is this share of all 32. The benchmark cell's step on that form by
#: XLA's memory analysis for a v5e: 16.62 GB of the 16.9 the compiler allows
#: so, refused with all 32 at once (17.2 GB needed, 1.5 over). The kernels
#: work a few heads and one chunk at a time and ignore it.
_KDA_HEADS_AT_ONCE = 16


#: What a rematerialized block keeps for its backward pass: its weight
#: matmuls' outputs and the KDA scan's output (Granite's policy,
#: `models.granite_hybrid`); the convolutions, norms, gates, the SwiGLU's
#: product and the attention's forward kernel are recomputed.
_BLOCK_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(_SCAN_OUT))


def core_width(qk: int, v: int) -> int:
    """The one head width the attention core takes for q, k and v: the
    flash kernels read 64 or a multiple of 128 lanes, v as wide as q . k
    (256 for 192 / 128)."""
    widest = max(qk, v)
    return 64 if widest <= 64 else -(-widest // 128) * 128


def published_layer_types(layers: int = 42, group: int = 6) -> tuple:
    """The mixer of each published layer: MLA where ``(i + 1) % group ==
    0``, KDA elsewhere."""
    return tuple("mla" if (i + 1) % group == 0 else "kda"
                 for i in range(layers))


#: Ling-3.0-flash's SwiGLU clamps by published layer (0: none)
FLASH_EXPERT_SWIGLU_LIMITS = (0,) * 35 + (4,) * 7
FLASH_SHARED_SWIGLU_LIMITS = (0,) * 34 + (5,) * 6 + (7,) * 2


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    #: the mixer of each layer HELD here: ``"kda"`` or ``"mla"``
    layer_types: tuple = published_layer_types()
    #: the published index of the first layer held here
    first_layer: int = 0
    #: held layers that lead with the dense SwiGLU
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    #: KDA's key and value width a head
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk_size: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    #: experts the router scores; this chip holds ``experts_held`` of them
    #: (None: all), from ``expert_offset`` on
    num_experts: int = 512
    experts_held: Any = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    #: SwiGLU clamps by PUBLISHED layer (empty: none); a held layer's must
    #: be 0 (the grouped kernels' epilogue has no clamp)
    expert_swiglu_limits: tuple = FLASH_EXPERT_SWIGLU_LIMITS
    shared_swiglu_limits: tuple = FLASH_SHARED_SWIGLU_LIMITS
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    #: 0 or 1 multi-token-prediction modules after the last block
    num_nextn_predict_layers: int = 1
    #: weight of the prediction module's loss (the published
    #: ``mtp_loss_scaling_factor`` is the released stage's 0)
    mtp_loss_weight: float = 0.3
    #: rematerialize each block's elementwise work in the backward pass
    #: (`nn.remat` under `_BLOCK_POLICY`)
    remat: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"kda", "mla"}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)}; a layer "
                             "is 'kda' or 'mla'")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    def refuse_clamps(self) -> None:
        """Raise if a layer held here clamps its SwiGLU: the grouped
        kernels' epilogue has no clamp (the published model's layers 34-41
        have one)."""
        held = range(self.first_layer,
                     self.first_layer + len(self.layer_types))
        clamped = sorted({
            i for limits in (self.expert_swiglu_limits,
                             self.shared_swiglu_limits)
            for i, limit in enumerate(limits) if limit})
        if any(i in held for i in clamped):
            raise ValueError(
                f"published layers {clamped[0]}-{clamped[-1]} clamp their "
                "SwiGLUs, which the grouped kernels' epilogue does not; "
                f"layers {held.start}-{held.stop - 1} are held here")


#: Ling-3.0-flash as published (inclusionAI/Ling-3.0-flash config.json): 42
#: layers, 512 routed experts, 157,184 ids; ~125B parameters, no one chip's
LING_3_0_FLASH = LingHybridConfig()
#: the same blocks at test sizes: a dense KDA layer, a KDA expert layer and
#: an MLA expert layer; 16 experts in 4 groups, 4 a token from 2 groups
LING_HYBRID_TINY = LingHybridConfig(
    vocab_size=96, hidden_size=64, layer_types=("kda", "kda", "mla"),
    first_k_dense_replace=1, num_attention_heads=4, head_dim=16,
    kda_chunk_size=8, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=48,
    num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    expert_swiglu_limits=(), shared_swiglu_limits=())


def l2_norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last dimension, float32 (the
    Kimi Linear reference code's ``l2norm``)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def head_rms_norm(x, scale, eps: float):
    """RMSNorm of each head's last dimension, float32 in and out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def interleaved_rotary_tables(seq_len: int, dim: int, theta: float):
    """(cos, sin) ``[S, dim/2]`` f32: pair ``i`` turns at
    ``theta^(-2i/dim)``."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angles), jnp.sin(angles)


def apply_interleaved_rotary(x, rope):
    """Rotate ``x`` ``[B, S, heads, dim]`` f32: lane ``2i`` pairs with lane
    ``2i + 1`` (the source's ``rope_interleave``)."""
    cos, sin = (t[None, :, None, :] for t in rope)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


class KdaMixer(nn.Module):
    """``W_o [RMSNorm_head(KDA(...)) * sigmoid(W_g y)]``; the module
    docstring has the equations."""

    config: LingHybridConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, H = y.shape
        heads, d = cfg.num_attention_heads, cfg.head_dim
        width = heads * d
        f32 = jnp.float32
        init = nn.initializers.normal(cfg.initializer_range)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                            kernel_init=init, name=name)

        with jax.named_scope("in_proj"):
            q, k, v, f, g = (dense(width, f"{n}_proj")(y)
                             for n in ("q", "k", "v", "f", "g"))
            b = dense(heads, "b_proj")(y)
        taps = [self.param(f"{n}_conv", init,
                           (cfg.short_conv_kernel_size, width), f32)
                for n in ("q", "k", "v")]
        with jax.named_scope("conv"):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(x.astype(f32), w))
                       .reshape(B, S, heads, d)
                       for x, w in zip((q, k, v), taps))
            q, k = l2_norm(q), l2_norm(k)
            q, k, v = (x.astype(cfg.dtype) for x in (q, k, v))
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,))
        with jax.named_scope("gate"):
            beta = jax.nn.sigmoid(b.astype(f32))
            log_alpha = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None]
                * (f.astype(f32) + dt_bias).reshape(B, S, heads, d))
        with jax.named_scope("scan"):
            o = kda_chunked(q, k, v, log_alpha, beta,
                            min(cfg.kda_chunk_size, S),
                            lower_bound=cfg.kda_lower_bound,
                            heads_at_once=math.gcd(heads, _KDA_HEADS_AT_ONCE))
        o = checkpoint_name(o, _SCAN_OUT)
        scale = self.param("o_norm", nn.initializers.ones, (d,), f32)
        with jax.named_scope("out_norm_gate"):
            gated = (head_rms_norm(o, scale, cfg.rms_norm_eps)
                     * jax.nn.sigmoid(g.astype(f32)).reshape(B, S, heads, d))
            gated = gated.reshape(B, S, width).astype(cfg.dtype)
        return dense(H, "out_proj")(gated)


def latent_attention(cfg: LingHybridConfig, dense, norm, param, y, rope):
    """MLA's context projected back, ``W_o [ctx_h * sigmoid(w_{g,h} . y)]``
    (the module docstring's equations), in the calling block's scope:
    ``dense``, ``norm`` and ``param`` make its leaves."""
    B, S, _ = y.shape
    nh, nope, rot = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    qk, vd = cfg.qk_head_dim, cfg.v_head_dim
    width = core_width(qk, vd)
    f32 = jnp.float32

    def rotate(x):
        return jnp.concatenate(
            [x[..., :nope], apply_interleaved_rotary(x[..., nope:], rope)],
            axis=-1)

    def pad(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

    with jax.named_scope("query"):
        q = head_rms_norm(dense((nh, qk), "q_proj")(y),
                          param("q_ln", (qk,)), cfg.rms_norm_eps)
        # the core's 1 / sqrt(256) is a power of two: q carries the rest of
        # 1 / sqrt(192) in float32, before its one rounding
        q = pad((rotate(q) * math.sqrt(width / qk)).astype(cfg.dtype))
    with jax.named_scope("key"):
        kv = dense(cfg.kv_lora_rank + rot, "kv_a_proj")(y)
        c_kv = norm("kv_a_ln")(kv[..., :cfg.kv_lora_rank])
        # one rotary key head, shared by every head
        k = jnp.concatenate(
            [dense((nh, nope), "k_up")(c_kv),
             jnp.broadcast_to(kv[..., None, cfg.kv_lora_rank:],
                              (B, S, nh, rot))], axis=-1)
        k = pad(rotate(head_rms_norm(k, param("k_ln", (qk,)),
                                     cfg.rms_norm_eps)).astype(cfg.dtype))
    with jax.named_scope("value"):
        v = pad(dense((nh, vd), "v_up")(c_kv))
    # the flash kernel where `models.gpt.flash_core_applies`, else dense
    ctx = causal_attention(q, k, v, None, dtype=cfg.dtype)[..., :vd]
    with jax.named_scope("output"):
        gate = jax.nn.sigmoid(dense(nh, "gate_proj")(y).astype(f32))
        ctx = (ctx * gate[..., None]).astype(cfg.dtype)
        return dense(y.shape[-1], "output", axis=(-2, -1))(ctx)


class LingBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``, then ``x + FFN(RMSNorm(x))``; ``mixer`` is
    ``"kda"`` or ``"mla"``, ``kind`` ``"dense"`` (SwiGLU at
    ``intermediate_size``) or ``"expert"`` (the routed experts held here
    plus the shared expert)."""

    config: LingHybridConfig
    mixer: str
    kind: str

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
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        def param(name, shape):
            return self.param(name, nn.initializers.ones, shape, jnp.float32)

        y = norm("ln_1")(x)
        if self.mixer == "kda":
            x = x + KdaMixer(cfg, name="kda")(y)
        else:
            x = x + latent_attention(cfg, dense, norm, param, y, rope)

        y = norm("ln_2")(x)
        with jax.named_scope("mlp"):
            if self.kind == "dense":
                return x + _swiglu(dense, y, cfg.intermediate_size, "mlp")
            # lazy import: models<->parallel would otherwise cycle
            from dear_pytorch_tpu.parallel.ep import RoutedExperts

            routed = RoutedExperts(
                router_width=cfg.num_experts,
                experts_held=cfg.experts_held or cfg.num_experts,
                expert_offset=cfg.expert_offset,
                top_k=cfg.num_experts_per_tok,
                mlp_dim=cfg.moe_intermediate_size,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                dtype=cfg.dtype, kernel_init=init, bias_init=init,
                name="moe")(y.reshape(B * S, H)).reshape(B, S, H)
            with jax.named_scope("shared"):
                return x + routed + _swiglu(
                    dense, y, cfg.moe_shared_expert_intermediate_size,
                    "shared")


class LingHybridLmHeadModel(nn.Module):
    """``__call__(input_ids)`` -> ``(logits, mtp_logits)``, both ``[B, S,
    vocab]`` f32 over the vocabulary slice held here, as
    `models.glm_moe.GlmMoeLmHeadModel`'s (and trained by its loss,
    `models.glm_moe.glm_moe_lm_loss`): ``logits[:, i]`` predict token ``i +
    1``, ``mtp_logits[:, i]`` token ``i + 2``."""

    config: LingHybridConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        cfg.refuse_clamps()
        init = nn.initializers.normal(cfg.initializer_range)
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, embedding_init=init,
                       dtype=cfg.dtype, name="wte")
        lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                           kernel_init=init, name="lm_head")
        block_cls = LingBlock
        if cfg.remat:
            block_cls = nn.remat(LingBlock, policy=_BLOCK_POLICY)
        rope = interleaved_rotary_tables(input_ids.shape[1],
                                         cfg.qk_rope_head_dim, cfg.rope_theta)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        def head(h, ln_name):
            h = norm(ln_name)(h)
            with jax.named_scope("loss"):
                return lm_head(h).astype(jnp.float32)

        x = wte(input_ids)
        for i, mixer in enumerate(cfg.layer_types):
            kind = "dense" if i < cfg.first_k_dense_replace else "expert"
            x = block_cls(cfg, mixer, kind, name=f"h_{i}")(x, rope)
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
            h = block_cls(cfg, "mla", "expert", name="mtp_block")(h, rope)
            return logits, head(h, "ln_mtp_f")


def expert_assignments(cfg: LingHybridConfig, intermediates) -> jax.Array:
    """``[expert layers, experts held]`` assignments made to each held
    expert, from the ``intermediates`` collection of one
    ``model.apply(..., mutable=["intermediates"])``: the blocks in order,
    the prediction module's last."""
    names = [f"h_{i}" for i in range(cfg.first_k_dense_replace,
                                     cfg.num_hidden_layers)]
    if cfg.num_nextn_predict_layers:
        names.append("mtp_block")
    return jnp.stack([intermediates[n]["moe"]["assignments"][0]
                      for n in names]).astype(jnp.float32)
