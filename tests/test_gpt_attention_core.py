"""The default attention cores of `GptBlock` and `BertSelfAttention` choose
between the Pallas flash kernel and the dense program from what the call
can observe — backend, dropout, mask, shape — in one function for both
families (`models.gpt.flash_core_applies`); no option of the model or the
program selects it."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import bert, gpt

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


KEY = jax.random.PRNGKey(0)
#: (causal, attention dropout live) -> the shortest S the kernel takes
MINIMUM = gpt.FLASH_MIN_SEQ


def _padding(seq, batch=2):
    return jnp.zeros((batch, 1, 1, seq))


@pytest.mark.parametrize("why,args,causal", [
    ("live dropout", (*_qk(1024), None, KEY, 0.1), True),
    ("live dropout, not causal", (*_qk(512), None, KEY, 0.1), False),
    ("key-padding mask", (*_qk(1024), _padding(1024), None, 0.0), True),
    ("key-padding mask and dropout: BERT's call",
     (*_qk(512), _padding(512), KEY, 0.1), False),
    ("no mask, no dropout, not causal", (*_qk(512), None, None, 0.0), False),
])
def test_tpu_selects_the_kernel_with_dropout_and_key_masks(on_tpu, why, args,
                                                           causal):
    assert gpt.flash_core_applies(*args, causal=causal), why


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("live", [True, False], ids=["dropout", "none"])
def test_each_minimum_is_the_first_length_taken(on_tpu, causal, live):
    """One minimum a (causal, dropout live): the kernel from there up, the
    dense program one 128-row step below."""
    least = MINIMUM[causal, live]
    assert least % 128 == 0
    rng, rate = (KEY, 0.1) if live else (None, 0.0)
    assert gpt.flash_core_applies(*_qk(least), None, rng, rate,
                                  causal=causal)
    if least > 128:
        assert not gpt.flash_core_applies(*_qk(least - 128), None, rng, rate,
                                          causal=causal)
    # dropout makes the dense program dearer, never the kernel's case worse
    assert MINIMUM[causal, True] <= MINIMUM[causal, False]


def test_the_gpt_cells_choice_is_unchanged():
    assert MINIMUM[True, False] == 768


@pytest.mark.parametrize("why,args,causal", [
    ("a [B,1,S,S] mask (BERT's causal serving forward)",
     (*_qk(1024), jnp.zeros((2, 1, 1024, 1024)), None, 0.0), False),
    ("a mask of another batch", (*_qk(1024), _padding(1024, 1), None, 0.0),
     True),
    ("S does not tile", (*_qk(1000), None, None, 0.0), True),
    ("S does not tile, dropout live", (*_qk(1000), None, KEY, 0.1), False),
    ("S below the causal crossover", (*_qk(512), None, None, 0.0), True),
    ("S below every minimum", (*_qk(64), None, KEY, 0.1), False),
    ("keys of another length", (*_qk(1024, 2048), None, None, 0.0), True),
    ("keys of another length, not causal",
     (*_qk(512, 1024), None, KEY, 0.1), False),
])
def test_tpu_stays_dense(on_tpu, why, args, causal):
    assert not gpt.flash_core_applies(*args, causal=causal), why


def test_core_dispatches_on_the_rule(on_tpu, monkeypatch):
    """`causal_attention` hands q, k, v to the kernel exactly when the rule
    holds, and otherwise calls the dense program with its arguments."""
    calls = []
    monkeypatch.setattr(
        FA, "flash_attention",
        lambda q, k, v, **kw: calls.append(kw) or jnp.zeros_like(q))
    q = jnp.ones((1, 768, 2, 64), jnp.bfloat16)
    out = gpt.causal_attention(q, q, q, None, dtype=jnp.bfloat16)
    assert calls == [{"causal": True, "kv_mask": None, "dropout_rng": None,
                      "dropout_rate": 0.0}] and not out.any()
    short = q[:, :256]
    want = gpt.causal_dot_product_attention(short, short, short, None,
                                            dtype=jnp.bfloat16)
    got = gpt.causal_attention(short, short, short, None, dtype=jnp.bfloat16)
    assert len(calls) == 1
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_bert_core_dispatches_on_the_rule(on_tpu, monkeypatch):
    """`models.bert.attention` hands q, k, v, the key mask as validity and
    the dropout key to the kernel exactly when the rule holds, and
    otherwise calls `dot_product_attention` with its arguments."""
    calls = []
    monkeypatch.setattr(
        FA, "flash_attention",
        lambda q, k, v, **kw: calls.append(kw) or jnp.zeros_like(q))
    q = jnp.ones((2, 512, 2, 64), jnp.bfloat16)
    padding = jnp.zeros((2, 1, 1, 512)).at[1, 0, 0, 500:].set(-1e9)
    out = bert.attention(q, q, q, padding, dropout_rng=KEY, dropout_rate=0.1,
                         dtype=jnp.bfloat16)
    assert len(calls) == 1 and not out.any()
    seen = calls[0]
    assert seen["causal"] is False and seen["dropout_rate"] == 0.1
    assert seen["dropout_rng"] is KEY
    np.testing.assert_array_equal(np.asarray(seen["kv_mask"]),
                                  np.asarray(padding[:, 0, 0] == 0))
    # the [B,1,S,S] mask of the causal serving forward, and a short S
    square = jnp.zeros((2, 1, 512, 512))
    for args in ((q, square), (q[:, :64], padding[..., :64])):
        x, mask = args
        want = bert.dot_product_attention(x, x, x, mask, dtype=jnp.bfloat16)
        got = bert.attention(x, x, x, mask, dtype=jnp.bfloat16)
        assert len(calls) == 1
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def _tiny_bert(**kw):
    return models.BertConfig(
        vocab_size=61, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=512, **kw)


def test_default_bert_layer_is_the_dense_program_off_tpu():
    """On the CPU the default `BertForPreTraining` lowers to the text the
    explicit dense core lowers to, dropout live."""
    cfg = _tiny_bert()
    ids = jnp.zeros((1, 512), jnp.int32)

    def lowered(impl):
        model = models.BertForPreTraining(cfg, attention_impl=impl)
        params = jax.eval_shape(
            lambda: model.init({"params": KEY}, ids, train=False)["params"])
        return jax.jit(lambda p, x, rng: model.apply(
            {"params": p}, x, train=True, rngs={"dropout": rng})
        ).lower(params, ids, KEY).as_text()

    assert lowered(None) == lowered(bert.dot_product_attention)


def test_an_explicit_bert_impl_still_wins(on_tpu):
    seen = []

    def impl(q, k, v, mask, *, dropout_rng=None, dropout_rate=0.0,
             dtype=jnp.float32):
        seen.append((q.shape, dropout_rate))
        return jnp.zeros_like(q)

    model = models.BertForPreTraining(_tiny_bert(), attention_impl=impl)
    ids = jnp.zeros((1, 512), jnp.int32)
    params = jax.eval_shape(lambda: model.init({"params": KEY}, ids,
                                               train=False)["params"])
    jax.eval_shape(lambda p: model.apply({"params": p}, ids, train=True,
                                         rngs={"dropout": KEY}), params)
    assert seen == [((1, 512, 2, 64), 0.0), ((1, 512, 2, 64), 0.1)]


def test_bert_init_takes_the_dense_core(on_tpu, monkeypatch):
    """As `GptBlock`: `init` pays no kernel's trace and lowering, `apply`
    on a TPU reaches the kernel with the layer's own dropout key."""
    reached = []
    monkeypatch.setattr(
        FA, "flash_attention",
        lambda q, k, v, **kw: reached.append(kw) or jnp.zeros_like(q))
    model = models.BertForPreTraining(_tiny_bert())
    ids = jnp.zeros((1, 512), jnp.int32)
    params = jax.eval_shape(lambda: model.init({"params": KEY}, ids,
                                               train=False)["params"])
    assert not reached
    jax.eval_shape(lambda p: model.apply({"params": p}, ids, train=True,
                                         rngs={"dropout": KEY}), params)
    assert len(reached) == 1 and reached[0]["dropout_rate"] == 0.1
    assert reached[0]["dropout_rng"] is not None


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
