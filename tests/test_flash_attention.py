"""Pallas flash-attention kernels vs the dense reference — forward and
backward, causal and padded, f32 and bf16. Runs the EXACT kernel code via
interpret mode on the CPU test mesh."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops.flash_attention import (
    flash_attention,
    make_flash_attention_impl,
)
from dear_pytorch_tpu.parallel.ring_attention import full_attention

# by module name: `dear_pytorch_tpu.ops` re-exports a `flash_attention`
# FUNCTION that shadows the module attribute
FA = sys.modules["dear_pytorch_tpu.ops.flash_attention"]

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


def test_bert_impl_contract_with_dropout():
    """The attention_impl adapter matches the dense model path at dropout
    0, and with a live rate drops the probabilities inside the kernel: the
    dense path under `dropout_keep_mask`'s mask for the same key."""
    from dear_pytorch_tpu.models.bert import dot_product_attention

    impl = make_flash_attention_impl()
    q, k, v = _qkv(jax.random.PRNGKey(5))
    additive = jnp.where(
        jnp.arange(S)[None, None, None, :] < 50, 0.0, -1e9
    ) * jnp.ones((B, 1, 1, 1))
    got = impl(q, k, v, additive)
    want = dot_product_attention(q, k, v, additive)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    rng = jax.random.PRNGKey(9)
    got = impl(q, k, v, additive, dropout_rng=rng, dropout_rate=0.5)
    keep = FA.dropout_keep_mask(rng, B, H, S, S, 0.5)
    want = _dense(q, k, v, False, additive[:, 0, 0] > -1.0, keep, 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.allclose(np.asarray(got),
                           np.asarray(impl(q, k, v, additive)), atol=1e-3)


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
    """A sequence longer than one block whose only divisors are tiny
    sub-tile blocks must be rejected at trace time on every backend, not
    at Mosaic lowering on the chip."""
    rng = jax.random.PRNGKey(0)
    # S=1028 -> largest halving divisor is 4 (1028 = 4*257): below every
    # dtype's sublane tile
    q = jax.random.normal(rng, (1, 1028, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="sublane tile"):
        flash_attention(q, q, q)
    # S=1040 = 16*65 tiles to 16-row blocks: a whole bf16 sublane tile, but
    # no multiple of the 128 lanes the row statistics travel along
    qb = jax.random.normal(rng, (1, 1040, 2, 8)).astype(jnp.bfloat16)
    with pytest.raises(ValueError, match="sublane tile"):
        flash_attention(qb, qb, qb)
    # at most one block of rows is always legal (S=132 = 4*33 included)
    q = jax.random.normal(rng, (1, 132, 2, 8), jnp.float32)
    assert flash_attention(q, q, q).shape == q.shape


def test_causal_needs_square_tiles():
    q = jnp.zeros((1, 64, 2, 8))
    k = jnp.zeros((1, 32, 2, 8))
    with pytest.raises(ValueError, match="equal query and key lengths"):
        flash_attention(q, k, k, causal=True)


# ---------------------------------------------------------------------------
# the tiled grid: blocks, head groups, masks, dtypes
# ---------------------------------------------------------------------------

#: docs/KERNELS.md: bf16 agrees with dense f32 to 5e-2 absolute (the chip
#: read 7.8e-3 .. 3.1e-2); f32 to summation order
TOL = {jnp.float32: dict(out=dict(rtol=2e-5, atol=2e-5),
                         grad=dict(rtol=5e-4, atol=5e-5)),
       jnp.bfloat16: dict(out=dict(rtol=0, atol=5e-2),
                          grad=dict(rtol=0, atol=5e-2))}


@pytest.fixture
def blocks_of_128(monkeypatch):
    """128-row blocks, so that a 256- or 384-row sequence runs the kernels'
    whole grid at a size the interpreter finishes: tiles under the
    diagonal (unmasked), on it (masked) and past it (skipped), the scratch
    carried from block to block. The jitted calls are keyed on shapes, not
    on the block, so the caches go before and after."""
    monkeypatch.setattr(FA, "_BLOCK", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _dense(q, k, v, causal, kv_mask, keep=None, rate=0.0):
    """Dense attention; ``keep`` ``[B, H, Sq, Sk]`` drops probabilities."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        tri = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(tri[None, None], s, -jnp.inf)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if keep is not None:
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _check_against_dense(shape, mode, dtype, key=0, rate=0.0, kv_heads=None):
    """``rate`` > 0: the kernel drops probabilities with a key, the dense
    program with `dropout_keep_mask`'s mask for that key. ``kv_heads``
    fewer than the shape's heads: k and v hold that many, and the dense
    program sees them repeated for their groups."""
    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    rng = jax.random.PRNGKey(key + 100) if rate else None
    keep = FA.dropout_keep_mask(rng, b, h, s, s, rate) if rate else None
    group = h // (kv_heads or h)
    q, k, v = (jax.random.normal(kk, shp, jnp.float32).astype(dtype)
               for kk, shp in zip(ks[:3], (shape, (b, s, h // group, d),
                                           (b, s, h // group, d))))
    w = jax.random.normal(ks[3], shape, jnp.float32)   # a generic cotangent
    causal = "causal" in mode
    kv_mask = None
    if "kv_mask" in mode:   # the first key stays valid: no empty causal row
        kv_mask = (jnp.arange(s)[None, :]
                   < jnp.array([[s - 37], [s // 2 + 5]][:b])) | (
                       jnp.arange(s)[None, :] == 0)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, kv_mask=kv_mask, dropout_rng=rng,
        dropout_rate=rate)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    dense = lambda q, k, v: _dense(  # noqa: E731
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        causal, kv_mask, keep, rate)
    got = flash(q, k, v)
    assert got.dtype == dtype
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(dense(*f32)), **tol["out"])
    got_g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(dense), argnums=(0, 1, 2))(*f32)
    for g, wnt, name in zip(got_g, want_g, "qkv"):
        assert g.dtype == dtype and g.shape == wnt.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(wnt), err_msg=f"d{name}",
                                   **tol["grad"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["causal", "kv_mask", "causal+kv_mask"])
@pytest.mark.parametrize("shape", [
    (1, 256, 2, 64),     # GPT-2's layout: two 64-wide heads a 128-lane block
    (2, 256, 1, 128),    # one head a block
    (1, 256, 4, 16),     # H·D = 64 < 128 lanes: all four heads in one block
], ids=["2x64", "1x128", "4x16"])
def test_tiled_grid_matches_dense(blocks_of_128, shape, mode, dtype):
    _check_against_dense(shape, mode, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_head_a_block_at_256_lanes(blocks_of_128, dtype):
    """D = 256 (the latent-attention decoder's head: 192 + 64 for q and k,
    256 for v) is the ``D % 128 == 0`` branch: one head a block, two lane
    rows wide, no head mask; three blocks a sequence, so dK/dV accumulate
    over the query blocks."""
    _check_against_dense((1, 384, 2, 256), "causal", dtype, key=3)


def test_one_head_a_block_at_the_default_block():
    """S=512, D=256 at the default block: one diagonal tile in strips."""
    _check_against_dense((1, 512, 2, 256), "causal", jnp.float32, key=4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["causal", "causal+kv_mask"])
def test_diagonal_tile_in_strips(mode, dtype):
    """S=512 at the default block is one diagonal tile of two 256-row
    strips: the first sees 256 keys, the second 512 (and in the dkv kernel
    the first key strip 512 queries, the second 256)."""
    _check_against_dense((1, 512, 2, 64), mode, dtype, key=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["causal", "kv_mask"])
def test_sequence_below_one_block(mode, dtype):
    """S=40 is one 40-row block, whatever the preferred block."""
    _check_against_dense((2, 40, 2, 64), mode, dtype, key=1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_single_query_row_over_a_masked_cache(dtype):
    """The decode tick: one query row over a key cache with a validity
    mask (`serving.kvcache.cache_attend`, ``use_flash``)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (3, 1, 4, 32), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (3, 96, 4, 32), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (3, 96, 4, 32), jnp.float32).astype(dtype)
    valid = jnp.arange(96)[None, :] < jnp.array([[1], [50], [96]])
    got = flash_attention(q, k, v, kv_mask=valid)
    want = _dense(*(x.astype(jnp.float32) for x in (q, k, v)), False, valid)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               **TOL[dtype]["out"])


def test_block_choice():
    """Blocks follow from (S, dtype) alone: 1024 rows where S allows, the
    whole sequence below that, 128-multiples by halving in between; a tile
    is worked through in 256-row strips, each over the columns it sees."""
    pick = FA._pick_block
    assert [pick(s) for s in (1, 40, 512, 1024, 2048, 1536, 1152, 384)] == [
        1, 40, 512, 1024, 1024, 512, 128, 384]
    assert FA._strips(1024, 1024, False) == [
        (0, 256, 0, 1024), (256, 512, 0, 1024), (512, 768, 0, 1024),
        (768, 1024, 0, 1024)]
    assert FA._strips(512, 512, True) == [(0, 256, 0, 256),
                                          (256, 512, 0, 512)]
    assert FA._strips(512, 512, True, transposed=True) == [
        (0, 256, 0, 512), (256, 512, 256, 512)]
    assert FA._strips(384, 384, True) == [(0, 384, 0, 384)]
    assert FA._group_width(12, 64) == 128      # two heads a block
    assert FA._group_width(8, 128) == 128      # one
    assert FA._group_width(4, 16) == 64        # H·D < 128: one block
    assert FA._group_width(3, 64) == 192       # 128 does not divide H·D
    assert FA._group_width(1, 64) == 64        # the folded (ring) API


def test_pair_kernels_agree_with_the_fused_backward():
    """Ring attention's entry points (`flash_pair_fwd/dq/dkv`: separate dq
    and transposed dkv kernels over folded ``[BH, S, D]``) give the
    gradients `flash_attention`'s fused backward kernel gives, on a
    diagonal tile of two strips with a key mask."""
    bh, s, d = 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, do = (jax.random.normal(kk, (bh, s, d), jnp.float32)
                   for kk in ks)
    mask = (jnp.arange(s)[None, :] < jnp.array([[s], [s - 100]]))
    scale = d ** -0.5
    o, lse = FA.flash_pair_fwd(q, k, v, mask, scale, True)
    delta = jnp.sum(do * o, axis=-1)
    dq = FA.flash_pair_dq(q, k, v, mask, do, lse, delta, scale, True)
    dk, dv = FA.flash_pair_dkv(q, k, v, mask, do, lse, delta, scale, True)

    def fused(q, k, v):   # [BH,S,D] as batch BH of one head
        out = flash_attention(q[:, :, None], k[:, :, None], v[:, :, None],
                              causal=True, kv_mask=mask)
        return jnp.sum(out[:, :, 0] * do)

    want = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    for got, w, name in zip((dq, dk, dv), want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# grouped-query attention: K/V heads fewer than Q heads
# ---------------------------------------------------------------------------

# (H, H_kv, D): LFM2's ratio, two K/V heads a 128-lane block serving eight Q
# heads (half of them reach their K/V head's lanes by a rotation); one K/V
# head of 128 lanes a block serving four; equal heads (the old kernels)
GROUPED = [(8, 2, 64), (4, 1, 128), (4, 4, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["causal", "full", "causal+kv_mask"])
@pytest.mark.parametrize("heads,kv_heads,d", GROUPED)
def test_grouped_heads_match_dense_with_repeated_kv(blocks_of_128, heads,
                                                    kv_heads, d, mode, dtype):
    """Forward and all three gradients over a 2 x 2 grid of blocks (dK/dV
    accumulate over the query blocks and over a group's Q heads inside the
    kernel) against the dense program with K and V repeated."""
    _check_against_dense((2, 256, heads, d), mode, dtype, key=21,
                         kv_heads=kv_heads)


@pytest.mark.parametrize("heads,kv_heads,d", GROUPED[:2])
def test_grouped_heads_drop_probabilities_by_the_q_heads_mask(
        blocks_of_128, heads, kv_heads, d):
    """Dropout with grouped heads: the mask of a score is its Q head's
    (`dropout_keep_mask` over all H heads)."""
    _check_against_dense((2, 256, heads, d), "causal+kv_mask", jnp.float32,
                         key=23, rate=0.1, kv_heads=kv_heads)


def test_grouped_heads_on_a_diagonal_tile_in_strips():
    _check_against_dense((1, 512, 8, 64), "causal", jnp.float32, key=22,
                         kv_heads=2)


def test_grouped_kernels_take_kv_as_it_is_and_return_it_summed(monkeypatch):
    """No repeat of K and V stands before the kernels: the K/V operands of
    both `pallas_call`s are ``[B, S, H_kv * D]``, and dK/dV leave the
    backward kernel at that width."""
    seen = []
    real = FA.pl.pallas_call

    def recording(kernel, **kw):
        call = real(kernel, **kw)

        def run(*operands):
            seen.append(([o.shape for o in operands],
                         [o.shape for o in kw["out_shape"]]))
            return call(*operands)
        return run

    monkeypatch.setattr(FA.pl, "pallas_call", recording)
    jax.clear_caches()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 8, 64))
    k, v = (jax.random.normal(kk, (1, 128, 2, 64)) for kk in ks[1:])
    grads = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    jax.clear_caches()
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    (fwd_in, fwd_out), (bwd_in, bwd_out) = seen
    assert fwd_in == [(1, 128, 512), (1, 128, 128), (1, 128, 128)]
    # lse by Q head: [B, 128-lane blocks of q, Q heads a block, S]
    assert fwd_out == [(1, 128, 512), (1, 4, 2, 128)]
    assert bwd_in[:3] == fwd_in
    assert bwd_out == [(1, 128, 512), (1, 128, 128), (1, 128, 128)]


def test_what_grouped_heads_do_not_fit():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 64))
    k, v = (jax.random.normal(kk, (2, 128, 2, 64)) for kk in ks[1:])
    # 2 K/V heads of 16 are a quarter of a lane row: no block of whole rows
    with pytest.raises(ValueError, match="do not tile"):
        flash_attention(q[..., :16], k[..., :16], v[..., :16])
    assert FA.grouped_heads_tile(32, 8, 64) and FA.grouped_heads_tile(4, 1, 128)
    assert FA.grouped_heads_tile(16, 4, 32) and FA.grouped_heads_tile(8, 8, 256)
    assert FA.grouped_heads_tile(3, 3, 20)           # equal heads always
    assert not FA.grouped_heads_tile(8, 3, 64)       # no whole groups
    assert not FA.grouped_heads_tile(6, 3, 64)       # 192 lanes of K/V
    assert not FA.grouped_heads_tile(4, 2, 96)
    # the ring's pair kernels (folded [BH, S, D]) need equal heads
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, 128, 64)  # noqa: E731
    fq, fk, fv = fold(q), fold(k), fold(v)
    lse = jnp.zeros(fq.shape[:2])
    for call in (lambda: FA.flash_pair_fwd(fq, fk, fv, None, 0.125, True),
                 lambda: FA.flash_pair_dq(fq, fk, fv, None, fq, lse, lse,
                                          0.125, True),
                 lambda: FA.flash_pair_dkv(fq, fk, fv, None, fq, lse, lse,
                                           0.125, True)):
        with pytest.raises(ValueError, match="H_kv == H"):
            call()


def test_the_default_core_takes_grouped_heads_where_the_kernels_do(
        monkeypatch):
    from dear_pytorch_tpu.models import gpt

    monkeypatch.setattr(FA, "_interpret", lambda: False)   # "on a TPU"
    q = jax.ShapeDtypeStruct((1, 1024, 8, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    assert gpt.flash_core_applies(q, k, None, None, 0.0)
    assert gpt.flash_core_applies(q, q, None, None, 0.0)
    mask = jnp.zeros((1, 1, 1, 1024))
    assert gpt.flash_core_applies(q, k, mask, jax.random.PRNGKey(0), 0.1)
    small = jax.ShapeDtypeStruct((1, 1024, 2, 16), jnp.bfloat16)
    assert not gpt.flash_core_applies(
        jax.ShapeDtypeStruct((1, 1024, 8, 16), jnp.bfloat16), small, None,
        None, 0.0)
    # off the TPU the dense program repeats K and V
    monkeypatch.setattr(FA, "_interpret", lambda: True)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    qa = jax.random.normal(ks[0], (1, 128, 8, 64))
    ka, va = (jax.random.normal(kk, (1, 128, 2, 64)) for kk in ks[1:])
    got = gpt.causal_attention(qa, ka, va, None)
    want = flash_attention(qa, ka, va, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# attention-probabilities dropout inside the kernels
# ---------------------------------------------------------------------------


@pytest.fixture
def small_tiles(monkeypatch):
    """128-row blocks worked through in 64-row strips: a 256-row sequence
    is two blocks each way and two strips a tile, so a mask drawn from
    tile-relative coordinates would repeat."""
    monkeypatch.setattr(FA, "_BLOCK", 128)
    monkeypatch.setattr(FA, "_STRIP", 64)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["full", "kv_mask", "causal",
                                  "causal+kv_mask"])
@pytest.mark.parametrize("shape", [
    (2, 256, 2, 64),     # two 64-wide heads a 128-lane block
    (1, 256, 2, 128),    # one head a block, two head groups
], ids=["2x64", "1x128"])
def test_dropout_kernel_matches_dense_under_the_same_mask(small_tiles, shape,
                                                          mode, dtype):
    """Output, dq, dk, dv of the kernels with dropout live against the
    dense f32 program that drops with `dropout_keep_mask`'s mask."""
    _check_against_dense(shape, mode, dtype, key=5, rate=0.1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_dropout_kernel_at_the_default_tiles(dtype):
    """S=512 at the default block: one tile of two 256-row strips, the
    shape of BERT-Large's call (a key mask, not causal)."""
    _check_against_dense((1, 512, 2, 64), "kv_mask", dtype, key=6, rate=0.1)


def _kernel_mask(shape, rng, rate):
    """The mask the forward kernel applies, read off its output: with q = 0
    every probability is 1/S, and with v's row j the j-th unit vector
    (D >= S) output row i reads keep[i, :] / ((1 - rate) S)."""
    b, s, h, d = shape
    zeros = jnp.zeros(shape, jnp.float32)
    v = jnp.broadcast_to(jnp.eye(s, d, dtype=jnp.float32)[None, :, None, :],
                         shape)
    out = flash_attention(zeros, zeros, v, dropout_rng=rng,
                          dropout_rate=rate)
    return np.asarray(out[..., :s] * ((1.0 - rate) * s) > 0.5).transpose(
        0, 2, 1, 3)


def test_the_mask_does_not_depend_on_the_tiling(monkeypatch):
    """Absolute coordinates: the kernel's mask is `dropout_keep_mask`'s
    at the default block and strip and at smaller ones."""
    shape, rate = (2, 256, 2, 256), 0.1
    rng = jax.random.PRNGKey(3)
    want = np.asarray(FA.dropout_keep_mask(rng, 2, 2, 256, 256, rate))
    np.testing.assert_array_equal(_kernel_mask(shape, rng, rate), want)
    for block, strip in ((128, 64), (128, 128)):
        monkeypatch.setattr(FA, "_BLOCK", block)
        monkeypatch.setattr(FA, "_STRIP", strip)
        jax.clear_caches()
        np.testing.assert_array_equal(_kernel_mask(shape, rng, rate), want)
    jax.clear_caches()


@pytest.mark.parametrize("how", ["rate 0", "no key"])
def test_no_live_dropout_is_the_undropped_kernel(how):
    """Rate 0 or no key: the same kernels as without the arguments (no seed
    operand, no hash, no select in the traced program) and so the same
    bits; a live rate adds them."""
    q, k, v = _qkv(jax.random.PRNGKey(8))
    kw = (dict(dropout_rng=jax.random.PRNGKey(1), dropout_rate=0.0)
          if how == "rate 0" else dict(dropout_rng=None, dropout_rate=0.1))

    def program(**kw):
        return str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            **kw).sum(),
            argnums=(0, 1, 2)))(q, k, v))

    plain = program()
    assert program(**kw) == plain
    assert "shift_right_logical" not in plain and "_dropout" not in plain
    live = program(dropout_rng=jax.random.PRNGKey(1), dropout_rate=0.1)
    assert "shift_right_logical" in live
    assert "flash_fwd_dropout" in live and "flash_bwd_dropout" in live
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, causal=True, **kw)),
        np.asarray(flash_attention(q, k, v, causal=True)))


def test_keep_mask_statistics():
    """An i.i.d. Bernoulli(0.9) mask, as far as a test can tell: the share
    within 4 sigma of 0.9, neighbours along rows and columns uncorrelated,
    and masks of other seed words, batch rows and heads unrelated."""
    rate, shape = 0.1, (2, 4, 512, 512)
    n = float(np.prod(shape))
    seed = jnp.array([0x1234ABCD, 0x0BADCAFE], jnp.uint32)
    keep = np.asarray(FA.dropout_keep_mask(seed, *shape, rate))
    assert keep.shape == shape and keep.dtype == np.bool_
    sigma = (rate * (1 - rate) / n) ** 0.5
    assert abs(keep.mean() - (1 - rate)) < 4 * sigma

    def correlation(a, b):
        return np.corrcoef(a.ravel().astype(np.float64),
                           b.ravel().astype(np.float64))[0, 1]

    pairs = {
        "column neighbours": (keep[..., :-1], keep[..., 1:]),
        "row neighbours": (keep[..., :-1, :], keep[..., 1:, :]),
        "diagonal neighbours": (keep[..., :-1, :-1], keep[..., 1:, 1:]),
        "batch rows": (keep[0], keep[1]),
        "heads 0, 1": (keep[:, 0], keep[:, 1]),
        "heads 1, 3": (keep[:, 1], keep[:, 3]),
    }
    for word in (0, 1):
        other = seed.at[word].add(1)
        pairs[f"seed word {word} + 1"] = (
            keep, np.asarray(FA.dropout_keep_mask(other, *shape, rate)))
    for what, (a, b) in pairs.items():
        assert abs(correlation(a, b)) < 4 / a.size ** 0.5, what
    # every row and every column is dropped from: no axis shares a decision
    assert (~keep).any(axis=-1).all() and (~keep).any(axis=-2).all()


@pytest.mark.parametrize("rate", [0.1, 0.5, 0.25])
def test_keep_share_follows_the_rate(rate):
    keep = np.asarray(FA.dropout_keep_mask(jax.random.PRNGKey(4), 1, 2, 512,
                                           512, rate))
    sigma = (rate * (1 - rate) / keep.size) ** 0.5
    assert abs(keep.mean() - (1 - rate)) < 4 * sigma


def test_seed_words_of_typed_and_raw_keys():
    """A typed key and its raw ``uint32[2]`` data seed the same mask; a
    wider key's words are folded into two."""
    raw = jax.random.PRNGKey(11)
    typed = jax.random.wrap_key_data(raw)
    np.testing.assert_array_equal(np.asarray(FA.dropout_seed_words(raw)),
                                  np.asarray(raw))
    np.testing.assert_array_equal(np.asarray(FA.dropout_seed_words(typed)),
                                  np.asarray(raw))
    wide = jnp.arange(4, dtype=jnp.uint32) + 7
    np.testing.assert_array_equal(np.asarray(FA.dropout_seed_words(wide)),
                                  np.asarray(wide[:2] ^ wide[2:]))


def test_dropout_rate_must_be_below_one():
    q = jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="dropout_rate"):
        flash_attention(q, q, q, dropout_rng=jax.random.PRNGKey(0),
                        dropout_rate=1.0)
