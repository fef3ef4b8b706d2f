"""`models.losses.token_cross_entropy` through the three heads that call it,
against the naive form (slice or flatten, mask, ``log_softmax``, gather):
value, gradient with respect to the logits, and the rows whose gradient
must be exactly zero (the positions with no target, the ignored labels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.models.bert import bert_pretraining_loss
from dear_pytorch_tpu.models.glm_moe import glm_moe_lm_loss
from dear_pytorch_tpu.models.gpt import gpt_lm_loss
from dear_pytorch_tpu.models.losses import token_cross_entropy

B, S, V = 2, 9, 64


def _naive_nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _next_token(ahead, vocab_size=None):
    def naive(logits, ids):
        logits = logits[:, :-ahead]
        if vocab_size is not None:
            pad = jnp.arange(logits.shape[-1]) >= vocab_size
            logits = jnp.where(pad, -1e9, logits)
        return jnp.mean(_naive_nll(logits, ids[:, ahead:]))
    return naive


def _gpt(vocab_size):
    hi = vocab_size or V
    return dict(
        system=lambda lg, ids: gpt_lm_loss(lg, ids, vocab_size=vocab_size),
        naive=_next_token(1, vocab_size),
        labels=lambda rng: rng.randint(0, hi, (B, S)),
        zero_rows=lambda ids: np.arange(S) == S - 1)


def _glm(ahead):
    # one term of the sum at a time: the main head alone, or the prediction
    # module's term as what it adds to a main term held constant
    def system(lg, ids):
        if ahead == 1:
            return glm_moe_lm_loss((lg, None), ids)
        main = jax.lax.stop_gradient(lg)
        return (glm_moe_lm_loss((main, lg), ids, mtp_loss_weight=1.0)
                - glm_moe_lm_loss((main, None), ids))
    return dict(system=system, naive=_next_token(ahead),
                labels=lambda rng: rng.randint(0, V, (B, S)),
                zero_rows=lambda ids: np.arange(S) >= S - ahead)


def _bert(ignored):
    nsp = jnp.zeros((B, 2), jnp.float32)
    nsp_labels = jnp.zeros((B,), jnp.int32)

    def system(lg, labels):
        return bert_pretraining_loss(lg, nsp, labels, nsp_labels)

    def naive(lg, labels):
        flat, lab = lg.reshape(-1, V), labels.reshape(-1)
        valid = lab != -1
        nll = _naive_nll(flat, jnp.where(valid, lab, 0))
        mlm = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
        return mlm + jnp.log(2.0)           # the NSP term of zero logits

    def labels(rng):
        lab = rng.randint(0, V, (B, S))
        drop = {"some": rng.rand(B, S) < 0.6, "all": np.ones((B, S), bool),
                "none": np.zeros((B, S), bool)}[ignored]
        return np.where(drop, -1, lab)

    return dict(system=system, naive=naive, labels=labels,
                zero_rows=lambda lab: np.asarray(lab) == -1)


CASES = {
    "gpt-padded-vocab": _gpt(61),
    "gpt-whole-vocab": _gpt(None),
    "glm-ahead-1": _glm(1),
    "glm-ahead-2": _glm(2),
    "bert-some-ignored": _bert("some"),
    "bert-all-ignored": _bert("all"),
    "bert-none-ignored": _bert("none"),
}


@pytest.mark.parametrize("widened_from", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16-widened"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_heads_equal_mask_log_softmax_gather(case, widened_from):
    """Value and gradient; f32 logits, and f32 logits that are widened bf16
    values (what every model's head returns: nothing may be rounded
    again)."""
    c = CASES[case]
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(B, S, V).astype(np.float32)) * 3.0
    logits = logits.astype(widened_from).astype(jnp.float32)
    labels = jnp.asarray(c["labels"](rng))

    v_s, g_s = jax.value_and_grad(c["system"])(logits, labels)
    v_n, g_n = jax.value_and_grad(c["naive"])(logits, labels)
    assert g_s.dtype == jnp.float32
    np.testing.assert_allclose(float(v_s), float(v_n), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_n),
                               rtol=1e-5, atol=1e-7)
    zero = np.broadcast_to(c["zero_rows"](labels), (B, S))
    assert zero.any() or case == "bert-none-ignored"
    assert np.all(np.asarray(g_s)[zero] == 0.0)
    assert zero.all() or np.any(np.asarray(g_s)[~zero] != 0.0)


def test_padded_columns_take_no_probability_and_no_gradient():
    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(B, S, V).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, 61, (B, S)))
    ones = jnp.ones((B, S))

    def nll(lg):
        return token_cross_entropy(lg, targets, ones, valid_vocab=61)[0]

    # the tail's logits, however large, move nothing
    loud = logits.at[..., 61:].set(50.0)
    assert float(nll(logits)) == float(nll(loud))
    assert np.all(np.asarray(jax.grad(nll)(loud))[..., 61:] == 0.0)


def test_weights_sum_is_returned_and_a_zero_weight_target_is_free():
    rng = np.random.RandomState(7)
    logits = jnp.asarray(rng.randn(B, S, V).astype(np.float32))
    targets = jnp.asarray(rng.randint(0, V, (B, S)))
    weights = jnp.asarray(rng.rand(B, S) < 0.5)
    total, count = token_cross_entropy(logits, targets, weights)
    assert float(count) == float(weights.sum())
    np.testing.assert_allclose(
        float(total), float(jnp.sum(_naive_nll(logits, targets) * weights)),
        rtol=1e-6)
    # whatever stands under a weight of 0 changes nothing
    other = jnp.where(weights, targets, (targets + 1) % V)
    assert float(token_cross_entropy(logits, other, weights)[0]) == float(total)
