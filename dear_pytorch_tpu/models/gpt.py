"""Decoder-only causal LM (GPT-2 family) — a model family BEYOND the
reference (its zoo stops at torchvision CNNs + BERT, reference
dear/imagenet_benchmark.py:88-95, dear/bert_benchmark.py:63-86), added
because autoregressive pretraining is the dominant large-scale workload the
decoupled schedule should also serve.

TPU-first choices mirror models/bert.py: compute-dtype threading (bf16 on
the MXU), static shapes, attention as batched einsums, the LM head tied to
the token embedding, vocab padded to a multiple of 8, and an
``attention_impl`` hook so the Pallas causal flash kernel
(`ops.flash_attention`) or the sequence-parallel engines can replace the
core attention without forking the model. Pre-LN residual blocks (GPT-2),
gelu(tanh) MLPs.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from dear_pytorch_tpu.models.bert import dot_product_attention
from dear_pytorch_tpu.models.losses import next_token_cross_entropy

# by module name: `dear_pytorch_tpu.ops` re-exports a `flash_attention`
# FUNCTION that shadows the module attribute
_flash = importlib.import_module("dear_pytorch_tpu.ops.flash_attention")

#: Shortest sequence a default core hands to the flash kernel, by (causal,
#: attention dropout live): the first S at which the kernel beat XLA's dense
#: program on a v5e, bf16 forward + backward of one layer, dense / kernel ms
#: (`scripts/flash_ab.py`; docs/KERNELS.md has the whole table).
FLASH_MIN_SEQ = {
    # `--causal`, 12 heads of 64 (PR 30): S=512 (batch 16) 0.742 / 0.725, a
    # tie; S=768 (8) 1.567 / 0.623; S=1024 (16) 5.857 / 1.959; S=2048 (4)
    # 5.420 / 1.573; S=4096 (2) 10.272 / 2.557. XLA's dense program is at
    # its best at 512 (26% of the matmul floor; 13-15% from 768 up)
    (True, False): 768,
    # `--causal --dropout 0.1`, batch 16, 12 heads of 64 (PR 32): S=256
    # 0.719 / 0.506; S=512 2.840 / 0.844; S=1024 refused (24.4 GB) / 2.284:
    # the dense program pays two threefry masks of S^2 elements
    (True, True): 256,
    # `--kv-mask`, batch 16, 16 heads of 64 (PR 32): S=128 0.490 / 0.501 and
    # S=256 0.507 / 0.500, ties; S=512 1.886 / 1.096
    (False, False): 512,
    # `--kv-mask --dropout 0.1`, the same shapes (PR 32): S=128 0.549 /
    # 0.508; S=256 0.931 / 0.738; S=512 4.335 / 1.363
    (False, True): 128,
}


@dataclasses.dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    embd_dropout_prob: float = 0.1
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: Any = jnp.float32
    #: > 0 replaces every block's dense MLP with a top-1 (switch) routed
    #: mixture of experts (`parallel.ep.MoeMlp`); train with
    #: `parallel.tp.make_tp_train_step(rules=EP_RULES, tp_axis='ep')` to
    #: shard the experts over an 'ep' mesh axis.
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    #: rematerialize each block in the backward pass (jax.checkpoint over
    #: GptBlock): activations are recomputed instead of stored, trading
    #: ~1/3 extra FLOPs in the blocks for O(layers) less live memory —
    #: the standard TPU recipe for raising batch size (HBM, not MXU, is
    #: the binding constraint at small batch).
    remat: bool = False

    #: pad the vocab (and thus the tied LM-head matmul's N dimension) to a
    #: multiple of this. Default 8 = reference parity (reference
    #: dear/bert_benchmark.py:72-78) and HF-familiar logits width; 128 (the
    #: TPU lane width) was A/B-measured on-chip and is a NULL result —
    #: 88.1k vs 88.6k tok/s, within run noise
    #: (perf/onchip_r05/gpt_sweep/gpt_sweep_v128.json) — XLA already tiles
    #: the unaligned N=50264 well, so the default stays interop-friendly.
    #: Padded ids are dead in the loss and in sampling either way.
    vocab_pad_multiple: int = 8

    #: Decode-mode KV-cache ring length (None = ``max_position_embeddings``,
    #: which never wraps inside the position budget — the legacy linear
    #: cache). A smaller ring bounds serving memory per slot; once a
    #: sequence outgrows it, attention becomes a sliding window over the
    #: last ``kv_cache_len`` tokens (`serving.kvcache`).
    kv_cache_len: Optional[int] = None
    #: Route decode-mode attention through the Pallas flash kernel
    #: (1-token query over the cache, validity mask as its ``kv_mask``)
    #: instead of the dense core. Same logits at dtype tolerance
    #: (tests/test_serving.py). Chunked prefill (S > 1 decode calls)
    #: always uses the dense core — its per-(query, key) window mask is
    #: outside the kernel's per-row ``kv_mask`` contract.
    decode_use_flash: bool = False
    #: Storage dtype of the decode KV cache (None = ``dtype``). bf16
    #: halves serving cache memory per slot; decode logits then match the
    #: full forward at bf16 tolerance (a `ServeSpace` axis, docs/TUNING.md).
    kv_cache_dtype: Any = None

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m


GPT2_SMALL = GptConfig()
GPT2_MEDIUM = GptConfig(hidden_size=1024, num_hidden_layers=24,
                        num_attention_heads=16, intermediate_size=4096)
GPT2_LARGE = GptConfig(hidden_size=1280, num_hidden_layers=36,
                       num_attention_heads=20, intermediate_size=5120)


def causal_dot_product_attention(q, k, v, mask, *, dropout_rng=None,
                                 dropout_rate=0.0, dtype=jnp.float32):
    """Dense causal attention core (same calling convention as
    models.bert.dot_product_attention; ``mask`` is the additive key-padding
    mask [B,1,1,S] or None — the causal triangle is applied here). ``k`` and
    ``v`` may hold fewer heads than ``q`` (grouped-query attention: K/V head
    ``j`` serves Q heads ``j * H/H_kv`` on); they are repeated here, which
    the flash kernels never do."""
    depth = q.shape[-1]
    S = q.shape[1]
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    with jax.named_scope("attention/scores"):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                  / jnp.sqrt(depth).astype(dtype))
    with jax.named_scope("attention/softmax"):  # the masks included
        tri = jnp.tril(jnp.ones((S, S), jnp.bool_))
        scores = jnp.where(tri[None, None], scores,
                           jnp.asarray(-1e9, scores.dtype))
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


def flash_core_applies(q, k, mask, dropout_rng, dropout_rate,
                       causal: bool = True) -> bool:
    """Whether a default attention core (`causal_attention` here,
    `models.bert.attention` with ``causal=False``) runs the Pallas flash
    kernel: a function of what the call can observe (backend, dropout,
    mask, shape), in this one place for both families. The kernel takes no
    mask or the additive key-padding mask ``[B, 1, 1, S]`` (as its
    ``kv_mask``; BERT's ``[B, 1, S, S]`` serving mask stays dense), drops
    probabilities itself, pays off from `FLASH_MIN_SEQ`'s entry for
    (causal, dropout live) up, tiles sequences in multiples of 128, and off
    the TPU would run in Pallas' interpreter (`_interpret` is the one
    predicate: an ahead-of-time compile for a described TPU from a CPU
    host patches that). ``k`` with fewer heads than ``q`` (grouped-query
    attention) needs heads that tile the kernels' lane blocks
    (`ops.flash_attention.grouped_heads_tile`)."""
    seq = q.shape[1]
    live = dropout_rng is not None and dropout_rate > 0.0
    return (not _flash._interpret()
            and _flash.grouped_heads_tile(q.shape[2], k.shape[2], q.shape[3])
            and (mask is None or mask.shape == (q.shape[0], 1, 1, seq))
            and k.shape[1] == seq
            and seq >= FLASH_MIN_SEQ[causal, live] and seq % 128 == 0)


def flash_core(q, k, v, mask, dropout_rng, dropout_rate, causal: bool):
    """The kernel as a default core calls it where `flash_core_applies`:
    under a bare ``attention`` scope (what `attention_core_ms` and the
    kernel counters read), the additive mask as key validity."""
    with jax.named_scope("attention"):
        return _flash.flash_attention(
            q, k, v, causal=causal,
            kv_mask=None if mask is None else _flash.key_validity(mask),
            dropout_rng=dropout_rng, dropout_rate=dropout_rate)


def causal_attention(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
                     dtype=jnp.float32):
    """The default causal attention core of `GptBlock`: the flash kernel
    where `flash_core_applies` (scores, mask, softmax, dropout and context
    stay in VMEM; bf16 operands, f32 accumulation and softmax), else the
    dense program `causal_dot_product_attention`, unchanged. Same calling
    convention as both."""
    if flash_core_applies(q, k, mask, dropout_rng, dropout_rate):
        return flash_core(q, k, v, mask, dropout_rng, dropout_rate, True)
    return causal_dot_product_attention(
        q, k, v, mask, dropout_rng=dropout_rng, dropout_rate=dropout_rate,
        dtype=dtype)


def checkpointed_causal_attention_impl():
    """Dense causal attention with the probs tensor RECOMPUTED in the
    backward pass (jax.checkpoint over the core) — the flash kernel's
    memory idea expressed in pure XLA, so it runs (and is measurable)
    everywhere. Per layer at [B=16, H=12, S=1024]: the bf16 probs cost
    ~0.4 GB of residency and a write+read HBM round trip when stored;
    checkpointing trades that for one extra attention forward (~7% of
    model FLOPs at S=1024). No dropout path (the mask would have to be
    replayed); use for dropout-free configs."""

    def impl(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
             dtype=jnp.float32):
        if dropout_rng is not None and dropout_rate > 0.0:
            raise ValueError(
                "checkpointed attention has no dropout path; set "
                "attention_probs_dropout_prob=0"
            )

        core = jax.checkpoint(
            lambda q_, k_, v_: causal_dot_product_attention(
                q_, k_, v_, mask, dtype=dtype
            )
        )
        with jax.named_scope("attention"):
            return core(q, k, v)

    return impl


def flash_causal_attention_impl():
    """Causal attention via the Pallas flash kernel, whatever
    `flash_core_applies` says (attention dropout included: the kernel's
    own mask, `ops.flash_attention.dropout_keep_mask`)."""
    def impl(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
             dtype=jnp.float32):
        del mask  # full sequences in the causal LM path
        return flash_core(q, k, v, None, dropout_rng, dropout_rate, True)

    return impl


class GptBlock(nn.Module):
    config: GptConfig
    attention_impl: Optional[Callable] = None
    #: QKV + MLP-up projection hook (models/bert.py `ProjDense` contract)
    #: — the ring collective-matmul path (`ops.collective_matmul`)
    projection_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, train: bool = True, decode: bool = False,
                 decode_positions=None, prefill_lengths=None):
        cfg = self.config
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        d = h // nh
        init = nn.initializers.normal(cfg.initializer_range)

        y = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_1")(x)
        if self.projection_impl is not None:
            from dear_pytorch_tpu.models.bert import ProjDense

            dense = lambda name: ProjDense(  # noqa: E731
                (nh, d), impl=self.projection_impl, dtype=cfg.dtype,
                kernel_init=init, name=name)
        else:
            dense = lambda name: nn.DenseGeneral(  # noqa: E731
                (nh, d), dtype=cfg.dtype, kernel_init=init, name=name)
        q, k, v = dense("query")(y), dense("key")(y), dense("value")(y)
        dropout_rng = None
        if train and cfg.attention_probs_dropout_prob > 0.0:
            dropout_rng = self.make_rng("dropout")
        if decode:
            ctx = self._decode_attend(q, k, v, decode_positions,
                                      prefill_lengths)
        else:
            impl = self.attention_impl or causal_attention
            if self.attention_impl is None and self.is_initializing():
                # `init` keeps the parameters and discards this output: it
                # takes the dense program, whose trace costs less set-up
                # time than lowering a kernel for a result nobody reads
                impl = causal_dot_product_attention
            ctx = impl(q, k, v, None, dropout_rng=dropout_rng,
                       dropout_rate=(cfg.attention_probs_dropout_prob
                                     if train else 0.0),
                       dtype=cfg.dtype)
        attn = nn.DenseGeneral(h, axis=(-2, -1), dtype=cfg.dtype,
                               kernel_init=init, name="output")(ctx)
        attn = nn.Dropout(cfg.hidden_dropout_prob,
                          deterministic=not train)(attn)
        x = x + attn

        y = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_2")(x)
        with jax.named_scope("mlp"):
            if cfg.num_experts > 0:
                # lazy import: models<->parallel would otherwise cycle
                # (parallel.sp imports this module)
                from dear_pytorch_tpu.parallel.ep import MoeMlp

                B_, S_, H_ = y.shape
                # Decode flattens only B tokens, which would collapse the
                # expert capacity (C = max(int(cf*B/E), 1)) and silently
                # zero colliding tokens' MLP outputs — use a drop-free
                # factor there. Note capacity DROPS are not replayed
                # incrementally: decode logits match training-time logits
                # exactly iff training was drop-free too
                # (expert_capacity_factor >= num_experts).
                cf = (float(cfg.num_experts) if decode
                      else cfg.expert_capacity_factor)
                y = MoeMlp(
                    num_experts=cfg.num_experts,
                    mlp_dim=cfg.intermediate_size,
                    capacity_factor=cf,
                    dtype=cfg.dtype, name="moe",
                )(y.reshape(B_ * S_, H_)).reshape(B_, S_, H_)
            elif self.projection_impl is not None:
                from dear_pytorch_tpu.models.bert import ProjDense

                y = ProjDense(cfg.intermediate_size,
                              impl=self.projection_impl, dtype=cfg.dtype,
                              kernel_init=init, name="mlp_in")(y)
                y = nn.gelu(y, approximate=True)
                y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                             kernel_init=init, name="mlp_out")(y)
            else:
                y = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                             kernel_init=init, name="mlp_in")(y)
                y = nn.gelu(y, approximate=True)
                y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                             kernel_init=init, name="mlp_out")(y)
        y = nn.Dropout(cfg.hidden_dropout_prob, deterministic=not train)(y)
        return x + y

    def _decode_attend(self, q, k, v, positions, prefill_lengths=None):
        """Attention against the ring-buffer KV cache (autoregressive
        decoding; `serving.kvcache` owns the ring math). ``positions`` is
        the per-row global token position ``[B]`` — the write slot is
        ``pos % L`` and validity derives from the position alone, so the
        cache carries NO write-index state: resetting a row to position 0
        (continuous-batching slot reuse) invalidates every stale entry
        for free. Shapes are static — the ring length is
        ``config.kv_cache_len`` (default: the position budget).

        ``S == 1``: the single-token decode tick. ``S > 1``: a chunked
        prefill tick — ``prefill_lengths`` (``[B]``) gives each row's
        valid prefix of the chunk (0 freezes the row: no write, output
        garbage the engine ignores); queries attend the pre-chunk cache
        plus the chunk's own K/V under exact per-query window masking
        (`serving.kvcache.chunk_attend`), so chunk logits match the
        token-at-a-time path at every position, wrap boundary included."""
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
        # flax's standard decode-cache pattern: during model.init the
        # variables are being CREATED (has_variable is False) and the call
        # must not execute a cache write — otherwise the returned cache
        # template already carries a phantom entry in slot 0
        initialized = self.has_variable("cache", "k")
        ck = self.variable("cache", "k",
                           lambda: jnp.zeros((B, L, nh, d), kv_dtype))
        cv = self.variable("cache", "v",
                           lambda: jnp.zeros((B, L, nh, d), kv_dtype))
        if not initialized:
            return jnp.zeros_like(q)
        if S > 1:
            # attend BEFORE the write: the chunk's tail may overwrite ring
            # slots its own head is still entitled to see (see chunk_attend)
            ctx = KV.chunk_attend(q, ck.value, cv.value, k, v, positions,
                                  prefill_lengths, dtype=cfg.dtype)
            ck.value, cv.value = KV.ring_write_chunk(
                ck.value, cv.value, positions, k.astype(kv_dtype),
                v.astype(kv_dtype), prefill_lengths)
            return ctx
        ck.value, cv.value = KV.ring_write(
            ck.value, cv.value, positions, k.astype(kv_dtype),
            v.astype(kv_dtype))
        # causality is carried by the slot-validity mask (only positions
        # already written — the current token included — are attendable)
        valid = KV.ring_validity(positions, L)
        return KV.cache_attend(q, ck.value, cv.value, valid,
                               dtype=cfg.dtype,
                               use_flash=cfg.decode_use_flash)


class GptLmHeadModel(nn.Module):
    """Token + position embeddings, pre-LN blocks, final LN, tied LM head.

    ``__call__(input_ids, train=...)`` -> next-token logits
    ``[B, S, padded_vocab]``.
    """

    config: GptConfig
    attention_impl: Optional[Callable] = None
    #: QKV + MLP-up projection hook (see models/bert.py `ProjDense`)
    projection_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, train: bool = True, position_offset=0,
                 decode: bool = False, prefill_lengths=None):
        """``decode=True``: autoregressive mode — ``input_ids`` is one
        token per sequence ``[B, 1]``, attention reads/writes the 'cache'
        collection (apply with ``mutable=['cache']``), and
        ``position_offset`` is the token's global position — a scalar, or
        a per-row ``[B]`` array (a continuous-batching engine serves rows
        at independent positions: some prefilling, some decoding, in ONE
        jitted step — `serving.engine`).

        ``decode=True`` with ``input_ids`` of shape ``[B, C]`` (C > 1) is
        a CHUNKED PREFILL tick: each row consumes its valid prefix
        (``prefill_lengths`` ``[B]``, required; 0 freezes a row) of C
        prompt tokens into the ring cache in one step — ceil(P/C) ticks
        per P-token prompt instead of P. Logits at in-chunk position j
        equal the token-at-a-time logits at global position
        ``position_offset + j`` (tests/test_serving.py)."""
        cfg = self.config
        B, S = input_ids.shape
        init = nn.initializers.normal(cfg.initializer_range)
        wte = nn.Embed(cfg.padded_vocab_size, cfg.hidden_size,
                       embedding_init=init, dtype=cfg.dtype, name="wte")
        x = wte(input_ids)
        offset = jnp.asarray(position_offset, jnp.int32)
        if offset.ndim == 1:
            # per-row [B] offsets (the serving engine's mixed batch)
            pos = offset[:, None] + jnp.arange(S)[None, :]
        else:
            # scalar, or a [..., S]-broadcastable per-token offset array
            # (the zigzag sequence-parallel layout) — legacy semantics
            pos = offset + jnp.arange(S)[None, :]
        if decode:
            # a partial final prefill chunk's PADDING rows can index past
            # the position table (their outputs are masked/ignored, but
            # the embedding gather must stay in bounds by construction,
            # not by XLA's clamping being merciful)
            pos = jnp.minimum(pos, cfg.max_position_embeddings - 1)
        x = x + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                         embedding_init=init, dtype=cfg.dtype,
                         name="wpe")(pos)
        x = nn.Dropout(cfg.embd_dropout_prob, deterministic=not train)(x)
        block_cls = GptBlock
        if cfg.remat and not decode:
            # static_argnums counts the bound module as arg 0: (self, x,
            # train, decode) -> the two bools are 2 and 3
            block_cls = nn.remat(GptBlock, static_argnums=(2, 3))
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
            x = block_cls(cfg, attention_impl=self.attention_impl,
                          projection_impl=self.projection_impl,
                          name=f"h_{i}")(x, train, decode, decode_positions,
                                         prefill_lengths)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_f")(x)
        return wte.attend(x).astype(jnp.float32)


def _top_p_filter(logits: jax.Array, top_p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest set of tokens whose cumulative
    probability reaches ``top_p`` (the most-probable token always stays);
    everything else is masked to -inf."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # cutoff = lowest logit still inside the nucleus: first index where the
    # cumulative mass (EXCLUSIVE of the current token) is already >= top_p
    inside = (cum - probs) < top_p
    cutoff = jnp.min(
        jnp.where(inside, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits >= cutoff, logits, -jnp.inf)


def generate(
    model: GptLmHeadModel,
    params,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_p: float = 1.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive decoding with a KV cache, as one jittable program.

    The prompt prefills the cache one token per scan tick (same decode path
    as sampling — one code path, exactly consistent with training-time
    logits, pinned by tests/test_gpt.py), then ``max_new_tokens`` tokens
    are sampled greedily (``temperature=0``) or from the
    temperature-scaled categorical, optionally nucleus-filtered
    (``top_p < 1``). Returns ``[B, prompt + new]`` token ids. Padded vocab
    ids are masked out of the sampling support.
    """
    cfg = model.config
    B, P = prompt_ids.shape
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    total = P + max_new_tokens
    if total > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt + new tokens ({total}) exceeds the cache budget "
            f"(max_position_embeddings={cfg.max_position_embeddings})"
        )

    # cache template from shapes only — a real model.init here would
    # materialize (and discard) a full random parameter tree per call
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(
            lambda: model.init(
                {"params": jax.random.PRNGKey(0)},
                jnp.zeros((B, 1), prompt_ids.dtype), train=False,
                decode=True,
            )["cache"]
        ),
    )
    pad_mask = jnp.where(
        jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size, 0.0, -1e9
    )
    # right-padded token buffer; scan index t reads (prompt) or writes
    # (sampled) position t
    tokens0 = jnp.concatenate(
        [prompt_ids, jnp.zeros((B, max_new_tokens), prompt_ids.dtype)],
        axis=1,
    )

    def tick(carry, t):
        tokens, cache, key = carry
        tok = lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        logits, vars_out = model.apply(
            {"params": params, "cache": cache}, tok, train=False,
            decode=True, position_offset=t, mutable=["cache"],
        )
        logits = logits[:, 0] + pad_mask[None, :]
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            logits = logits / temperature
            if top_p < 1.0:
                logits = _top_p_filter(logits, top_p)
            nxt = jax.random.categorical(sub, logits, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        nxt = nxt.astype(tokens.dtype)
        # during prefill (t + 1 < P) the next token is the prompt's, not
        # the model's; afterwards write the sample at t + 1 (t runs to
        # total - 2, so the write never leaves the buffer)
        write_at = t + 1
        keep = lax.dynamic_slice_in_dim(tokens, write_at, 1, axis=1)[:, 0]
        chosen = jnp.where(t + 1 < P, keep, nxt)
        tokens = lax.dynamic_update_slice_in_dim(
            tokens, chosen[:, None], write_at, axis=1
        )
        return (tokens, vars_out["cache"], key), None

    (tokens, _, _), _ = lax.scan(
        tick, (tokens0, cache, rng), jnp.arange(total - 1)
    )
    return tokens


def gpt_lm_loss(logits, input_ids, *, vocab_size: Optional[int] = None):
    """Next-token cross-entropy: logits[:, t] predict input_ids[:, t+1].
    Padded vocab ids (>= ``vocab_size``) are excluded from the softmax
    support, so the loss matches an unpadded model's.

    Streamed formulation: ``nll = logsumexp(valid logits) - logit[target]``
    — the identical function to masking + log_softmax + gather (log_softmax
    IS x - logsumexp(x)). Nothing of the logits' size is sliced: the
    *targets* are shifted, the last position takes weight 0 (and so an
    exactly zero gradient), and the padded tail is masked by column index
    (`models.losses` says why: slicing ``[:, :-1]`` and
    ``[..., :vocab_size]`` cost the v5e three logits-sized buffers and
    23 ms a step at GPT-2 scale, PERF.md PR 34). Same-value +
    same-gradient property is pinned by
    tests/test_gpt.py::test_gpt_lm_loss_streamed_equivalence."""
    with jax.named_scope("loss"):
        return next_token_cross_entropy(logits, input_ids,
                                        valid_vocab=vocab_size)
