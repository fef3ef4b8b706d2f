"""The default attention core of `GptBlock` chooses between the Pallas flash
kernel and the dense program from what the call can observe — backend,
dropout, mask, shape — in one function (`models.gpt.flash_core_applies`);
no option of the model or the program selects it."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import gpt

FA = sys.modules["dear_pytorch_tpu.ops.flash_attention"]


def _qk(seq, kv_seq=None, dtype=jnp.bfloat16):
    q = jax.ShapeDtypeStruct((2, seq, 12, 64), dtype)
    return q, jax.ShapeDtypeStruct((2, kv_seq or seq, 12, 64), dtype)


@pytest.fixture
def on_tpu(monkeypatch):
    """The one predicate an ahead-of-time compile for a described TPU
    patches too (tests/test_chip_compile.py)."""
    monkeypatch.setattr(FA, "_interpret", lambda: False)


def test_cpu_stays_dense():
    assert jax.default_backend() == "cpu"
    assert not gpt.flash_core_applies(*_qk(1024), None, None, 0.0)


@pytest.mark.parametrize("seq", [768, 1024, 2048, 1152])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_tpu_selects_the_kernel(on_tpu, seq, dtype):
    assert gpt.flash_core_applies(*_qk(seq, dtype=dtype), None, None, 0.0)
    # a dropout key with rate 0 (eval, or a dropout-free config) is not live
    assert gpt.flash_core_applies(*_qk(seq), None, jax.random.PRNGKey(0),
                                  0.0)


@pytest.mark.parametrize("why,args", [
    ("live dropout", (*_qk(1024), None, jax.random.PRNGKey(0), 0.1)),
    ("additive mask", (*_qk(1024), jnp.zeros((2, 1, 1, 1024)), None, 0.0)),
    ("S does not tile", (*_qk(1000), None, None, 0.0)),
    ("S below the crossover", (*_qk(512), None, None, 0.0)),
    ("keys of another length", (*_qk(1024, 2048), None, None, 0.0)),
])
def test_tpu_stays_dense(on_tpu, why, args):
    assert not gpt.flash_core_applies(*args), why


def test_core_dispatches_on_the_rule(on_tpu, monkeypatch):
    """`causal_attention` hands q, k, v to the kernel exactly when the rule
    holds, and otherwise calls the dense program with its arguments."""
    calls = []
    monkeypatch.setattr(
        FA, "flash_attention",
        lambda q, k, v, **kw: calls.append(kw) or jnp.zeros_like(q))
    q = jnp.ones((1, 768, 2, 64), jnp.bfloat16)
    out = gpt.causal_attention(q, q, q, None, dtype=jnp.bfloat16)
    assert calls == [{"causal": True}] and not out.any()
    short = q[:, :256]
    want = gpt.causal_dot_product_attention(short, short, short, None,
                                            dtype=jnp.bfloat16)
    got = gpt.causal_attention(short, short, short, None, dtype=jnp.bfloat16)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_default_block_is_the_dense_program_off_tpu():
    """On the CPU the default `GptBlock` lowers to the text the explicit
    dense core lowers to: the rule leaves the dense path as it was."""
    cfg = models.GptConfig(
        vocab_size=61, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=1024, embd_dropout_prob=0.0,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    ids = jnp.zeros((1, 1024), jnp.int32)

    def lowered(impl):
        model = models.GptLmHeadModel(cfg, attention_impl=impl)
        params = jax.eval_shape(
            lambda: model.init({"params": jax.random.PRNGKey(0)}, ids,
                               train=False)["params"])
        return jax.jit(lambda p, x: model.apply({"params": p}, x, train=True)
                       ).lower(params, ids).as_text()

    assert lowered(None) == lowered(gpt.causal_dot_product_attention)


def test_an_explicit_impl_still_wins(on_tpu):
    seen = []

    def impl(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
             dtype=jnp.float32):
        seen.append(q.shape)
        return jnp.zeros_like(q)

    cfg = models.GptConfig(
        vocab_size=61, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=1024, embd_dropout_prob=0.0,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = models.GptLmHeadModel(cfg, attention_impl=impl)
    ids = jnp.zeros((1, 1024), jnp.int32)
    jax.eval_shape(lambda: model.init({"params": jax.random.PRNGKey(0)}, ids,
                                      train=False))
    assert seen == [(1, 1024, 2, 64)]


def test_init_takes_the_dense_core(on_tpu, monkeypatch):
    """`init` keeps the parameters and discards the core's output: it does
    not pay a kernel's trace and lowering (set-up time in every run), and
    its program is what it was before the kernel existed."""
    def boom(*a, **kw):
        raise AssertionError("init reached the flash kernel")

    monkeypatch.setattr(FA, "flash_attention", boom)
    cfg = models.GptConfig(
        vocab_size=61, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=1024, embd_dropout_prob=0.0,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = models.GptLmHeadModel(cfg)
    ids = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, ids, train=False)["params"])
    with pytest.raises(AssertionError, match="reached the flash kernel"):
        jax.eval_shape(lambda p: model.apply({"params": p}, ids, train=True),
                       params)
