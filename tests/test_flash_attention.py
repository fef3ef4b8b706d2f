"""Pallas flash-attention kernels vs the dense reference — forward and
backward, causal and padded, f32 and bf16. Runs the EXACT kernel code via
interpret mode on the CPU test mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention_impl,
)
from dear_pytorch_tpu.parallel.ring_attention import full_attention

B, S, H, D = 2, 64, 4, 16


def _qkv(key, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(
        jax.random.normal(k, (B, S, H, D), dtype) for k in ks
    )


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    got = flash_attention(q, k, v, causal=causal)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_with_padding_mask():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    kv_mask = jnp.arange(S)[None, :] < jnp.array([[40], [64]])  # per-batch
    got = flash_attention(q, k, v, kv_mask=kv_mask)
    # dense reference with additive mask
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
    s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(2))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_gradients_with_padding_mask():
    q, k, v = _qkv(jax.random.PRNGKey(3))
    kv_mask = jnp.arange(S)[None, :] < jnp.array([[48], [16]])

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_mask=kv_mask) ** 2)

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return jnp.sum(out ** 2)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name}",
        )


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(4), jnp.bfloat16)
    got = flash_attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_bert_impl_contract_and_dropout_refusal():
    """The attention_impl adapter matches the dense model path exactly at
    dropout 0 and REFUSES a live dropout rate (the kernel has no dropout
    path; a silent dense fall-through would be timed under its name)."""
    from dear_pytorch_tpu.models.bert import dot_product_attention

    impl = make_flash_attention_impl()
    q, k, v = _qkv(jax.random.PRNGKey(5))
    additive = jnp.where(
        jnp.arange(S)[None, None, None, :] < 50, 0.0, _big := -1e9
    ) * jnp.ones((B, 1, 1, 1))
    got = impl(q, k, v, additive)
    want = dot_product_attention(q, k, v, additive)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="no attention-dropout path"):
        impl(q, k, v, additive, dropout_rng=jax.random.PRNGKey(9),
             dropout_rate=0.5)


def test_bert_end_to_end_with_flash_impl():
    """A BERT built with the flash impl produces the same logits as the
    default dense-attention BERT (dropout off)."""
    from dear_pytorch_tpu.models import data as mdata
    from dear_pytorch_tpu.models.bert import BertConfig, BertForPreTraining

    cfg = BertConfig(
        num_hidden_layers=2, hidden_size=32, num_attention_heads=4,
        intermediate_size=64, vocab_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    )
    batch = mdata.synthetic_bert_batch(
        jax.random.PRNGKey(2), 2, seq_len=32, vocab_size=64
    )
    dense = BertForPreTraining(cfg)
    flash = BertForPreTraining(cfg, attention_impl=make_flash_attention_impl())
    params = dense.init(
        {"params": jax.random.PRNGKey(0)}, batch["input_ids"], train=False
    )["params"]
    out_d, nsp_d = dense.apply(
        {"params": params}, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"], train=False,
    )
    out_f, nsp_f = flash.apply(
        {"params": params}, batch["input_ids"], batch["token_type_ids"],
        batch["attention_mask"], train=False,
    )
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(nsp_f), np.asarray(nsp_d),
                               rtol=2e-4, atol=2e-4)


def test_mosaic_block_rule():
    """Every BlockSpec the wrappers emit must satisfy Mosaic's real-TPU
    block rule (trailing dims (8k, 128k) or equal to the array's): the CPU
    interpret path never checks it, so this pins the rule host-side. The
    (1, S) rank-2 vector specs that passed the whole CPU suite but died on
    first chip contact (2026-07-31) are the regression under test."""
    from dear_pytorch_tpu.ops.flash_attention import check_mosaic_block

    # legal: full-dim blocks, 8/128-multiples, trailing singletons
    check_mosaic_block((1, 128, 64), (384, 128, 64))
    check_mosaic_block((1, 128, 1), (384, 128, 1))
    check_mosaic_block((1, 64, 64), (384, 192, 64))
    # the round-4 on-chip failure shape: rank-2 (1, S) over [BH, S]
    with pytest.raises(ValueError, match="Mosaic-illegal"):
        check_mosaic_block((1, 128), (384, 128))
    # sublane block neither 8-multiple nor full
    with pytest.raises(ValueError, match="second-to-last"):
        check_mosaic_block((1, 4, 64), (384, 192, 64))
    # lane block neither 128-multiple nor full
    with pytest.raises(ValueError, match="last block dim"):
        check_mosaic_block((1, 128, 32), (384, 128, 64))
    # dtype-aware sublane rule: 8 rows is legal for f32 but BELOW the
    # native (16, 128) tile for bf16 — must be rejected for 16-bit
    check_mosaic_block((1, 8, 128), (4, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="sublane tile 16"):
        check_mosaic_block((1, 8, 128), (4, 256, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="sublane tile 32"):
        check_mosaic_block((1, 16, 128), (4, 256, 128), jnp.int8)


def test_wrappers_reject_mosaic_illegal_blocks():
    """An odd sequence length that forces a tiny sub-tile query block must
    be rejected at trace time on every backend, not at Mosaic lowering on
    the chip."""
    rng = jax.random.PRNGKey(0)
    # S=132 -> largest halving divisor is 4 (132 = 4*33): below every
    # dtype's sublane tile
    q = jax.random.normal(rng, (2, 132, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="sublane tile"):
        flash_attention(q, q, q)
    # the ADVICE.md round-4 scenario: S=136 = 8*17 tiles to 8-row blocks,
    # which PASSES the naive %8 rule but mis-tiles bf16 on real chips
    qb = jax.random.normal(rng, (2, 136, 2, 8)).astype(jnp.bfloat16)
    with pytest.raises(ValueError, match="sublane tile"):
        flash_attention(qb, qb, qb)
