"""BERT for pre-training — TPU-native flax implementation.

Parity target: the reference benchmarks HuggingFace ``BertForPreTraining``
built from local JSON configs (reference dear/bert_benchmark.py:63-86;
bert_config.json = BERT-Large 1024h/24L/16heads, bert_base_config.json =
BERT-Base 768h/12L/12heads) with the vocab padded to a multiple of 8
(dear/bert_benchmark.py:72-78) and a custom ``BertPretrainingCriterion``
(masked-LM + next-sentence cross-entropy, dear/bert_benchmark.py:101-112).

TPU-first choices: compute dtype threading (bfloat16 on the MXU), static
shapes throughout, attention as one batched einsum per layer, MLM decoder
tied to the input embedding (``Embed.attend``), and an `attention_impl`
hook so the sequence-parallel engines (ring attention / Ulysses,
dear_pytorch_tpu.parallel) can replace the core attention without forking
the model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dear_pytorch_tpu.models.losses import token_cross_entropy


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    #: Decode-mode KV-cache ring length (None = the position budget; see
    #: models/gpt.py — same ring semantics via `serving.kvcache`).
    kv_cache_len: Optional[int] = None
    #: Route decode attention through the Pallas flash kernel (decode
    #: ticks only; chunked prefill uses the dense core — models/gpt.py).
    decode_use_flash: bool = False
    #: Storage dtype of the decode KV cache (None = ``dtype``; see
    #: models/gpt.py — the serving cache-memory knob).
    kv_cache_dtype: Any = None

    @property
    def padded_vocab_size(self) -> int:
        """Vocab padded to a multiple of 8 (reference
        dear/bert_benchmark.py:72-78 pads for tensor-core efficiency; the
        MXU likes multiples of 8 just the same)."""
        return ((self.vocab_size + 7) // 8) * 8


#: Reference config files, reproduced (dear/bert_config.json,
#: dear/bert_base_config.json).
BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(
    hidden_size=1024, num_hidden_layers=24, num_attention_heads=16,
    intermediate_size=4096,
)


class ProjDense(nn.Module):
    """Dense / DenseGeneral twin with an injectable matmul impl — the
    projection-path analog of the ``attention_impl`` hook.

    Creates the SAME params as the flax module it replaces (``kernel`` of
    shape ``(in,) + features``, ``bias`` of shape ``features``; same
    names, same init, fp32 param dtype), so fusion plans, checkpoints,
    and the TP rule regexes are unchanged. The impl receives the matmul
    FLATTENED to 2-D — ``impl(x2d [M, in], kernel2d [in, out_flat],
    bias1d [out_flat], dtype) -> y2d`` — which is the contract
    `ops.collective_matmul.make_ring_projection_impl` implements (the
    ring collective-matmul that starts on the local weight shard while
    remote shards stream in). ``impl`` must apply the dtype promotion
    itself (the ring impl mirrors flax's ``promote_dtype``).

    Only instantiated when a hook is active; with ``projection_impl=None``
    the models keep their original ``nn.Dense`` / ``nn.DenseGeneral``
    modules so default-path numerics cannot drift.
    """

    features: Any            # int or tuple (e.g. (heads, head_dim))
    impl: Callable
    dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        feats = (self.features if isinstance(self.features, tuple)
                 else (self.features,))
        in_dim = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (in_dim,) + feats)
        bias = self.param("bias", nn.initializers.zeros, feats)
        out_flat = 1
        for f in feats:
            out_flat *= f
        lead = x.shape[:-1]
        y = self.impl(
            x.reshape(-1, in_dim),
            kernel.reshape(in_dim, out_flat),
            bias.reshape(out_flat),
            self.dtype,
        )
        return y.reshape(lead + feats)


def dot_product_attention(q, k, v, mask, *, dropout_rng=None,
                          dropout_rate=0.0, dtype=jnp.float32):
    """Default attention core: one softmax(QK^T)V per layer, batched over
    (batch, heads). Shapes: q/k/v [B, S, H, D]; mask [B, 1, 1, S] additive."""
    depth = q.shape[-1]
    with jax.named_scope("attention/scores"):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                  / jnp.sqrt(depth).astype(dtype))
    with jax.named_scope("attention/softmax"):  # the mask included
        if mask is not None:
            scores = scores + mask
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(dtype)
    if dropout_rng is not None and dropout_rate > 0.0:
        with jax.named_scope("attention/dropout"):
            keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                        probs.shape)
            probs = probs * keep / (1.0 - dropout_rate)
    with jax.named_scope("attention/context"):
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
              dtype=jnp.float32):
    """The default attention core of `BertSelfAttention`: the Pallas flash
    kernel where `models.gpt.flash_core_applies` says so (one predicate for
    both families: a TPU, no mask or the key-padding mask, S a multiple of
    128 from the measured minimum up; the probabilities are dropped inside
    the kernel), else `dot_product_attention`, unchanged. Same calling
    convention as both."""
    from dear_pytorch_tpu.models import gpt   # gpt imports this module

    if gpt.flash_core_applies(q, k, mask, dropout_rng, dropout_rate,
                              causal=False):
        return gpt.flash_core(q, k, v, mask, dropout_rng, dropout_rate,
                              False)
    return dot_product_attention(q, k, v, mask, dropout_rng=dropout_rng,
                                 dropout_rate=dropout_rate, dtype=dtype)


class BertSelfAttention(nn.Module):
    config: BertConfig
    attention_impl: Optional[Callable] = None
    #: QKV projection hook (`ProjDense` contract) — the fused
    #: collective-matmul path (`ops.collective_matmul`); None = nn.DenseGeneral
    projection_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask, train: bool = True, decode: bool = False,
                 decode_positions=None, prefill_lengths=None):
        cfg = self.config
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        d = h // nh
        kinit = nn.initializers.normal(cfg.initializer_range)
        if self.projection_impl is not None:
            dense = lambda name: ProjDense(  # noqa: E731
                (nh, d), impl=self.projection_impl, dtype=cfg.dtype,
                kernel_init=kinit, name=name)
        else:
            dense = lambda name: nn.DenseGeneral(  # noqa: E731
                (nh, d), dtype=cfg.dtype, name=name, kernel_init=kinit)
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        if decode:
            ctx = self._decode_attend(q, k, v, decode_positions,
                                      prefill_lengths)
        else:
            dropout_rng = None
            if train and cfg.attention_probs_dropout_prob > 0.0:
                dropout_rng = self.make_rng("dropout")
            impl = self.attention_impl or attention
            if self.attention_impl is None and self.is_initializing():
                # `init` keeps the parameters and discards this output: no
                # kernel is traced and lowered for it (as `GptBlock`)
                impl = dot_product_attention
            ctx = impl(q, k, v, mask, dropout_rng=dropout_rng,
                       dropout_rate=(cfg.attention_probs_dropout_prob
                                     if train else 0.0),
                       dtype=cfg.dtype)
        out = nn.DenseGeneral(
            h, axis=(-2, -1), dtype=cfg.dtype, name="output",
            kernel_init=nn.initializers.normal(cfg.initializer_range))(ctx)
        return out

    def _decode_attend(self, q, k, v, positions, prefill_lengths=None):
        """Attention against the ring-buffer KV cache — the serving
        decode path, identical ring semantics to models/gpt.py
        (`serving.kvcache` owns the math; S > 1 with ``prefill_lengths``
        is a chunked prefill tick — see GptBlock._decode_attend).
        Incremental decode is left-to-right by construction, so its
        logits reproduce the full forward run with ``causal=True``
        (pinned by tests/test_serving.py), not the bidirectional
        training forward."""
        from dear_pytorch_tpu.serving import kvcache as KV

        cfg = self.config
        B, S, nh, d = q.shape
        L = cfg.kv_cache_len or cfg.max_position_embeddings
        if S > 1 and prefill_lengths is None:
            raise ValueError(
                f"decode with S={S} > 1 is a chunked prefill and needs "
                "per-row prefill_lengths"
            )
        if S > L:
            raise ValueError(
                f"prefill chunk ({S}) exceeds the KV ring length ({L}); "
                "a chunk must not overwrite its own window"
            )
        kv_dtype = cfg.kv_cache_dtype or cfg.dtype
        initialized = self.has_variable("cache", "k")
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((B, L, nh, d), kv_dtype))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((B, L, nh, d), kv_dtype))
        if not initialized:
            return jnp.zeros_like(q)
        if S > 1:
            ctx = KV.chunk_attend(q, ck.value, cv.value, k, v, positions,
                                  prefill_lengths, dtype=cfg.dtype)
            ck.value, cv.value = KV.ring_write_chunk(
                ck.value, cv.value, positions, k.astype(kv_dtype),
                v.astype(kv_dtype), prefill_lengths)
            return ctx
        ck.value, cv.value = KV.ring_write(
            ck.value, cv.value, positions, k.astype(kv_dtype),
            v.astype(kv_dtype))
        valid = KV.ring_validity(positions, L)
        return KV.cache_attend(q, ck.value, cv.value, valid,
                               dtype=cfg.dtype,
                               use_flash=cfg.decode_use_flash)


class BertLayer(nn.Module):
    config: BertConfig
    attention_impl: Optional[Callable] = None
    projection_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask, train: bool = True, decode: bool = False,
                 decode_positions=None, prefill_lengths=None):
        cfg = self.config
        attn = BertSelfAttention(cfg, attention_impl=self.attention_impl,
                                 projection_impl=self.projection_impl,
                                 name="attention")(x, mask, train, decode,
                                                   decode_positions,
                                                   prefill_lengths)
        attn = nn.Dropout(cfg.hidden_dropout_prob,
                          deterministic=not train)(attn)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="attention_ln")(x + attn)
        kinit = nn.initializers.normal(cfg.initializer_range)
        with jax.named_scope("mlp"):
            if self.projection_impl is not None:
                y = ProjDense(cfg.intermediate_size,
                              impl=self.projection_impl, dtype=cfg.dtype,
                              kernel_init=kinit, name="intermediate")(x)
            else:
                y = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                             kernel_init=kinit, name="intermediate")(x)
            y = nn.gelu(y, approximate=True)
            y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, kernel_init=kinit,
                         name="output")(y)
        y = nn.Dropout(cfg.hidden_dropout_prob, deterministic=not train)(y)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                            name="output_ln")(x + y)


class BertForPreTraining(nn.Module):
    """Embeddings + encoder + MLM head (tied decoder) + NSP head.

    ``__call__(input_ids, token_type_ids, attention_mask)`` returns
    ``(prediction_logits [B,S,V_padded], seq_relationship_logits [B,2])`` —
    the same pair the reference criterion consumes
    (dear/bert_benchmark.py:104-112).
    """

    config: BertConfig
    attention_impl: Optional[Callable] = None
    #: QKV + MLP-intermediate projection hook (see `ProjDense`) — wires
    #: the ring collective-matmul into the transformer hot path
    projection_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = True, position_offset=0, pool_fn=None,
                 causal: bool = False, decode: bool = False,
                 prefill_lengths=None):
        """``position_offset`` shifts position ids (a sequence-parallel shard
        at global offset r*S_local passes that offset; in decode mode it may
        be a per-row ``[B]`` array — see models/gpt.py); ``pool_fn(x)``
        overrides the default ``x[:, 0]`` CLS pooling (under sequence
        parallelism the CLS token lives on shard 0 only — see
        parallel.sp.sp_cls_pool).

        ``causal=True`` adds the causal triangle to the attention mask —
        the left-to-right serving forward whose logits the incremental
        ``decode=True`` path (one token per call, ring KV cache in the
        'cache' collection, apply with ``mutable=['cache']``) reproduces
        exactly. The default bidirectional forward is untouched."""
        cfg = self.config
        B, S = input_ids.shape
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)

        embed_init = nn.initializers.normal(cfg.initializer_range)
        word_emb = nn.Embed(cfg.padded_vocab_size, cfg.hidden_size,
                            embedding_init=embed_init, dtype=cfg.dtype,
                            name="word_embeddings")
        x = word_emb(input_ids)
        offset = jnp.asarray(position_offset, jnp.int32)
        if offset.ndim == 1:
            # per-row [B] offsets (the serving engine's mixed batch)
            pos_ids = offset[:, None] + jnp.arange(S)[None, :]
        else:
            # scalar or broadcastable offset array — legacy semantics
            pos_ids = offset + jnp.arange(S)[None, :]
        if decode:
            # a partial final prefill chunk's padding rows must not index
            # past the position table (see models/gpt.py)
            pos_ids = jnp.minimum(pos_ids, cfg.max_position_embeddings - 1)
        x = x + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                         embedding_init=embed_init, dtype=cfg.dtype,
                         name="position_embeddings")(pos_ids)
        x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                         embedding_init=embed_init, dtype=cfg.dtype,
                         name="token_type_embeddings")(token_type_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="embeddings_ln")(x)
        x = nn.Dropout(cfg.hidden_dropout_prob, deterministic=not train)(x)

        # additive mask [B, 1, 1, S]
        mask = (1.0 - attention_mask[:, None, None, :].astype(cfg.dtype))
        mask = mask * jnp.asarray(-1e9, dtype=cfg.dtype)
        if causal:
            if self.attention_impl is not None:
                raise ValueError(
                    "causal=True builds a [B, 1, S, S] mask the default "
                    "attention core broadcasts; custom attention_impl "
                    "hooks expect [B, 1, 1, S] key-padding masks"
                )
            tri = jnp.tril(jnp.ones((S, S), jnp.bool_))
            mask = mask + jnp.where(tri, 0.0, -1e9).astype(
                cfg.dtype)[None, None]

        decode_positions = None
        if decode:
            if offset.ndim == 0:
                decode_positions = jnp.broadcast_to(offset[None], (B,))
            elif offset.ndim == 1:
                decode_positions = offset
            else:
                raise ValueError(
                    "decode mode needs a scalar or per-row [B] "
                    f"position_offset, got shape {offset.shape}"
                )
        for i in range(cfg.num_hidden_layers):
            x = BertLayer(cfg, attention_impl=self.attention_impl,
                          projection_impl=self.projection_impl,
                          name=f"layer_{i}")(x, mask, train, decode,
                                             decode_positions,
                                             prefill_lengths)

        # --- MLM head: transform + tied decoder + bias -----------------------
        y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     kernel_init=embed_init, name="mlm_transform")(x)
        y = nn.gelu(y, approximate=True)
        y = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="mlm_ln")(y)
        logits = word_emb.attend(y)
        logits = logits + self.param(
            "mlm_bias", nn.initializers.zeros, (cfg.padded_vocab_size,))
        # --- NSP head: pooled [CLS] -> 2 classes -----------------------------
        pooled_in = pool_fn(x) if pool_fn is not None else x[:, 0]
        pooled = nn.tanh(nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                                  kernel_init=embed_init, name="pooler")(
            pooled_in))
        nsp = nn.Dense(2, dtype=jnp.float32, kernel_init=embed_init,
                       name="nsp_classifier")(pooled)
        return logits.astype(jnp.float32), nsp.astype(jnp.float32)


def bert_pretraining_loss(logits, nsp_logits, masked_lm_labels,
                          next_sentence_labels, ignore_index: int = -1):
    """Masked-LM + next-sentence cross-entropy (reference
    ``BertPretrainingCriterion``, dear/bert_benchmark.py:101-112:
    CrossEntropyLoss(ignore_index=-1) on flattened logits, summed).
    """
    with jax.named_scope("loss"):
        valid = masked_lm_labels != ignore_index
        nll, count = token_cross_entropy(
            logits, jnp.where(valid, masked_lm_labels, 0), valid)
        mlm_loss = nll / jnp.maximum(count, 1)
        nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nsp_logp,
                                next_sentence_labels.reshape(-1, 1),
                                axis=-1))
        return mlm_loss + nsp_loss
