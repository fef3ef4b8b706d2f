"""Dense Mamba-2 / attention hybrid decoder (Granite-4.0-H family,
``model_type: granitemoehybrid`` with ``num_local_experts: 0``): pre-norm
RMSNorm blocks whose mixer is chosen per layer from ``layer_types``, a
Mamba-2 state-space mixer (``mamba``) or grouped-query attention WITHOUT
positions (``attention``); a SwiGLU MLP in every block; a head tied to the
embedding; and the family's four multipliers:

    x0  = embedding_multiplier * Embed(ids)
    h   = x + residual_multiplier * Mixer(RMSNorm(x))
    out = h + residual_multiplier * SwiGLU(RMSNorm(h))
    logits = RMSNorm(x_L) Embed^T / logits_scaling

    mamba:      [z | xBC | dt] = W_in y
                xBC = silu(conv1d(xBC) + b)           depthwise, causal, 4 taps
                [x | B | C] = xBC;  dt = softplus(dt + dt_bias)
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  A = -exp(A_log)
                y_t = S_t C_t + D x_t                 (`ops.ssd`, by chunks)
                Mixer = W_out RMSNorm(y * silu(z))    the gate BEFORE the norm,
                                                      over all of its channels
    attention:  Mixer = W_o softmax_causal(attention_multiplier * q k^T) v
                no rotary, no bias, no norm of a head; the multiplier is
                the whole softmax scale (1/64 here, not 1/sqrt(64))

Training form only (no convolution or recurrent state, no KV cache: those
are serving's). One chip may hold a pipeline stage and a slice of the
vocabulary: `GraniteHybridConfig.layer_types` names the layers held here
(one ten-layer period of the published forty in the benchmark's cell) and
``vocab_size`` is then the slice; embedding, tied head and loss are over it.

Named scopes (docs/OBSERVABILITY.md): ``mamba/in_proj``, ``mamba/conv1d``
(taps, bias, silu), ``mamba/ssd`` (all of the scan: softplus, ``-exp(A_log)``,
`ops.ssd.ssd_chunked_scan` with its ``D`` skip), ``mamba/gate_norm``,
``mamba/out_proj``; the attention layer's projections under ``query`` /
``key`` / ``value`` / ``output``, its core under the bare ``attention``;
the MLP under ``mlp``; the tied head and the cross-entropy under ``loss``.

``A_log``, ``D``, ``dt_bias``, the convolution's taps and bias and every norm
weight are float32 leaves used in float32 arithmetic whatever ``dtype`` is.
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
from dear_pytorch_tpu.models.lfm2_moe import causal_depthwise_conv
from dear_pytorch_tpu.models.losses import next_token_cross_entropy
from dear_pytorch_tpu.ops.ssd import ssd_chunked_scan

#: Granite-4.0-H-Micro's mixers: attention at 5, 15, 25 and 35, Mamba-2
#: elsewhere (nine to one, a period of ten)
_MICRO_LAYERS = tuple("attention" if i % 10 == 5 else "mamba"
                      for i in range(40))

#: the scan's output, named for `_BLOCK_POLICY`
_SCAN_OUT = "ssd_out"

#: What a rematerialized block keeps for its backward pass: the outputs of
#: its weight matmuls (the projections and the MLP: `dots_with_no_batch_dims`)
#: and the scan's output (8 KB a token and layer). What is recomputed is the
#: elementwise work between them: the RMSNorms, the convolution and its silu,
#: softplus, the gated norm, the SwiGLU's product, the attention layer's
#: forward kernel; not the scan (its own `jax.checkpoint` recomputes its
#: chunk matrices once for its gradient either way). The benchmark cell's
#: step by XLA's memory analysis for a v5e (PR 38): 11.40 GB so, 9.46 GB
#: keeping nothing but the blocks' inputs, 13.82 GB without recomputation.
_BLOCK_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(_SCAN_OUT))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    #: the mixer of each layer HELD here: ``"mamba"`` or ``"attention"``
    layer_types: tuple = _MICRO_LAYERS
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    #: the SwiGLU of every block (``num_local_experts`` is 0: no routed part)
    shared_intermediate_size: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    #: the softmax scale itself
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    #: rematerialize each block's elementwise work in the backward pass
    #: (`nn.remat` under `_BLOCK_POLICY`)
    remat: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)}; a layer "
                             "is 'mamba' or 'attention'")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_inner:
            raise ValueError(
                f"{self.mamba_n_heads} Mamba heads of {self.mamba_d_head} "
                f"are not mamba_expand x hidden_size = {self.mamba_inner}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_groups} groups do not divide "
                             f"{self.mamba_n_heads} Mamba heads")
        if self.mamba_proj_bias or self.attention_bias:
            raise ValueError("the projections have no bias path")
        if not self.mamba_conv_bias:
            raise ValueError("the Mamba convolution has its bias")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        """Width of the scan's ``x`` and of the gate ``z``."""
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state


#: Granite-4.0-H-Micro as published (ibm-granite/granite-4.0-h-micro
#: config.json): 40 layers, 100,352 ids; 3.19B parameters
GRANITE_4_0_H_MICRO = GraniteHybridConfig()
#: the same blocks at test sizes: both kinds of layer, two chunks in S=16
GRANITE_HYBRID_TINY = GraniteHybridConfig(
    vocab_size=96, hidden_size=32, layer_types=("mamba", "attention", "mamba"),
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=8, mamba_n_groups=1,
    mamba_chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=48)


# (initializers are handed over as functions, not built by calls inside
# `__call__`: dearlint's name-keyed call graph would else tie every model's
# ``__call__`` to each function called ``log``)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of ``exp U(log 0.001, log 0.1)``: ``softplus(dt_bias)``
    starts log-uniform in [0.001, 0.1] (the Mamba-2 reference's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)``: ``A = -exp(A_log)`` starts uniform in -[1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def gated_rms_norm(y, z, scale, eps: float):
    """``RMSNorm(y * silu(z)) * scale`` over the whole last dimension: the
    gate first, then the norm (the source's ``RMSNormGated``). f32
    arithmetic, result in ``y``'s dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return (g * scale).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    """``W_out RMSNorm(SSD(...) * silu(z))``; the module docstring has the
    equations."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.config
        B, S, H = y.shape
        heads, width = cfg.mamba_n_heads, cfg.mamba_d_head
        groups, state = cfg.mamba_n_groups, cfg.mamba_d_state
        inner = cfg.mamba_inner
        init = nn.initializers.normal(cfg.initializer_range)
        proj = nn.Dense(inner + cfg.conv_dim + heads, use_bias=False,
                        dtype=cfg.dtype, kernel_init=init, name="in_proj")(y)
        z, xbc, dt = jnp.split(proj, [inner, inner + cfg.conv_dim], axis=-1)
        taps = self.param("conv_kernel", init, (cfg.mamba_d_conv,
                                                cfg.conv_dim), jnp.float32)
        conv_bias = self.param("conv_bias", init, (cfg.conv_dim,),
                               jnp.float32)
        with jax.named_scope("conv1d"):
            xbc = jax.nn.silu(
                causal_depthwise_conv(xbc.astype(jnp.float32), taps)
                + conv_bias).astype(xbc.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
        a_log = self.param("A_log", _a_log_init, (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        with jax.named_scope("ssd"):
            scanned = ssd_chunked_scan(
                x.reshape(B, S, heads, width),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                b.reshape(B, S, groups, state), c.reshape(B, S, groups, state),
                skip, min(cfg.mamba_chunk_size, S))
        scanned = checkpoint_name(scanned, _SCAN_OUT)
        scale = self.param("gate_norm", nn.initializers.ones, (inner,),
                           jnp.float32)
        with jax.named_scope("gate_norm"):
            gated = gated_rms_norm(scanned.reshape(B, S, inner), z, scale,
                                   cfg.rms_norm_eps)
        return nn.Dense(H, use_bias=False, dtype=cfg.dtype, kernel_init=init,
                        name="out_proj")(gated)


class GraniteHybridBlock(nn.Module):
    """``x + m * Mixer(RMSNorm(x))``, then ``x + m * SwiGLU(RMSNorm(x))``
    with ``m`` the residual multiplier; ``mixer`` is a ``layer_types``
    entry."""

    config: GraniteHybridConfig
    mixer: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        H = x.shape[-1]
        init = nn.initializers.normal(cfg.initializer_range)

        def dense(features, name, axis=-1):
            return nn.DenseGeneral(features, axis=axis, use_bias=False,
                                   dtype=cfg.dtype, kernel_init=init,
                                   name=name)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)

        def residual(x, update):
            return x + jnp.asarray(cfg.residual_multiplier,
                                   cfg.dtype) * update

        y = norm("ln_1")(x)
        if self.mixer == "mamba":
            x = residual(x, Mamba2Mixer(cfg, name="mamba")(y))
        else:
            nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
            # no rotary: the source's attention applies no positions. The
            # cores scale by 1/sqrt(d); q carries what is left of the
            # family's softmax scale (1/8 here: a power of two, exact)
            with jax.named_scope("query"):
                q = dense((nh, d), "q_proj")(y) * jnp.asarray(
                    cfg.attention_multiplier * math.sqrt(d), cfg.dtype)
            with jax.named_scope("key"):
                k = dense((nkv, d), "k_proj")(y)
            with jax.named_scope("value"):
                v = dense((nkv, d), "v_proj")(y)
            # the grouped flash kernel where `models.gpt.flash_core_applies`,
            # else the dense program (which repeats k and v)
            ctx = causal_attention(q, k, v, None, dtype=cfg.dtype)
            x = residual(x, dense(H, "output", axis=(-2, -1))(ctx))

        y = norm("ln_2")(x)
        with jax.named_scope("mlp"):
            return residual(x, _swiglu(dense, y,
                                       cfg.shared_intermediate_size, "mlp"))


class GraniteHybridLmHeadModel(nn.Module):
    """``__call__(input_ids)`` -> logits ``[B, S, vocab]`` f32 over the
    vocabulary slice held here; ``logits[:, i]`` predict token ``i + 1``."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       embedding_init=nn.initializers.normal(
                           cfg.initializer_range), name="wte")
        block_cls = GraniteHybridBlock
        if cfg.remat:
            block_cls = nn.remat(GraniteHybridBlock, policy=_BLOCK_POLICY)
        x = wte(input_ids) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        for i, mixer in enumerate(cfg.layer_types):
            x = block_cls(cfg, mixer, name=f"h_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="ln_f")(x)
        with jax.named_scope("loss"):
            # the head's input divided, not its [S, vocab] output: the same
            # logits (8 is a power of two: exact), and the matmul's one
            # ``cfg.dtype`` buffer stays the logits (models/losses.py)
            x = x * jnp.asarray(1.0 / cfg.logits_scaling, cfg.dtype)
            return wte.attend(x).astype(jnp.float32)


def granite_hybrid_lm_loss(logits, input_ids):
    """Next-token cross-entropy of `GraniteHybridLmHeadModel`'s logits (the
    targets shifted, the logits never sliced:
    `models.losses.next_token_cross_entropy`)."""
    with jax.named_scope("loss"):
        return next_token_cross_entropy(logits, input_ids)
