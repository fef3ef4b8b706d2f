"""BERT family: the program's model and pre-training loss (MLM + NSP), the
batch from the seed, the model-FLOPs function, and a plain reference of the
same mathematics.

Only the program's public API is used (`models.BertForPreTraining`,
`models.bert_pretraining_loss`, `models.BertConfig`); `reference_loss` uses
none of it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain

#: share of positions that carry an MLM label (BERT paper, section 3.1)
MASKED_FRACTION = 0.15


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `BertConfig` from the published keys of the config file."""
    from dear_pytorch_tpu import models

    drop = (lambda k: model[k]) if dropout else (lambda k: 0.0)
    return models.BertConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_hidden_layers=num_layers or model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        intermediate_size=model["intermediate_size"],
        max_position_embeddings=model["max_position_embeddings"],
        type_vocab_size=model["type_vocab_size"],
        hidden_dropout_prob=drop("hidden_dropout_prob"),
        attention_probs_dropout_prob=drop("attention_probs_dropout_prob"),
        layer_norm_eps=model["layer_norm_eps"],
        initializer_range=model["initializer_range"],
        dtype=dtype,
    )


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model: train-mode forward
    (dropout from the per-step key the train step hands in) and the MLM + NSP
    criterion, as `bench.py:bench_bert`."""
    from dear_pytorch_tpu import models

    model = models.BertForPreTraining(cfg)

    def init_fn(key, seq_len: int):
        ids = jnp.zeros((1, seq_len), jnp.int32)
        return model.init({"params": key}, ids, train=False)["params"]

    def loss_fn(params, batch, rng=None):
        rngs = {"dropout": rng} if rng is not None else None
        logits, nsp = model.apply(
            {"params": params}, batch["input_ids"], batch["token_type_ids"],
            batch["attention_mask"], train=True, rngs=rngs)
        return models.bert_pretraining_loss(
            logits.astype(jnp.float32), nsp.astype(jnp.float32),
            batch["masked_lm_labels"], batch["next_sentence_labels"])

    if with_rng:
        return init_fn, loss_fn
    return init_fn, lambda params, batch: loss_fn(params, batch)


def make_batch(model: dict, key, batch_size: int, seq_len: int) -> dict:
    """Random ids, two segments split at a random point, a full attention
    mask (sequences are packed to S, as in phase-2 pre-training), random MLM
    labels on 15% of positions (-1 elsewhere) and random NSP labels."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    shape = (batch_size, seq_len)
    vocab = model["vocab_size"]
    split = jax.random.randint(k5, (batch_size, 1), seq_len // 4,
                               3 * seq_len // 4)
    masked = jax.random.uniform(k2, shape) < MASKED_FRACTION
    return {
        "input_ids": jax.random.randint(k1, shape, 0, vocab, jnp.int32),
        "token_type_ids": (jnp.arange(seq_len)[None] >= split).astype(
            jnp.int32),
        "attention_mask": jnp.ones(shape, jnp.int32),
        "masked_lm_labels": jnp.where(
            masked, jax.random.randint(k3, shape, 0, vocab, jnp.int32), -1),
        "next_sentence_labels": jax.random.randint(
            k4, (batch_size,), 0, 2, jnp.int32),
    }


def batch_shapes(model: dict, batch_size: int, seq_len: int) -> dict:
    shape = ((batch_size, seq_len), jnp.int32)
    return {"input_ids": shape, "token_type_ids": shape,
            "attention_mask": shape, "masked_lm_labels": shape,
            "next_sentence_labels": ((batch_size,), jnp.int32)}


def tokens_per_step(batch_size: int, seq_len: int) -> int:
    """Every position of every sequence: the encoder and the MLM head run on
    all of them, whichever carry a label."""
    return batch_size * seq_len


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per position, forward + backward, no recompute: 6 per
    matmul parameter of the encoder (4H^2 + 2HI per layer), 12*L*S*H for
    QK^T and AV, and the MLM head at every position (6*H^2 transform +
    6*V*H tied decoder). The pooler and NSP head run on one position per
    sequence and are left out. BERT-Large at S=512: 2.157 GFLOP/token."""
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    per_layer = 4 * h * h + 2 * h * model["intermediate_size"]
    return float(6 * layers * per_layer + 12 * layers * seq_len * h
                 + 6 * h * h + 6 * model["vocab_size"] * h)


def initial_loss(model: dict) -> float:
    """MLM near ln(vocab) plus NSP near ln 2."""
    return math.log(model["vocab_size"]) + math.log(2.0)


# -- plain reference ---------------------------------------------------------

def reference_loss(model: dict, num_layers: int):
    """``loss(params, batch)``: BERT's forward pass (post-LN encoder, three
    summed embeddings, MLM transform + tied decoder + bias, tanh pooler + NSP
    classifier) and the MLM + NSP cross-entropy, float32, no dropout,
    straightforward `jax.numpy`. Departures from the source, both the
    program's and listed in the config file under `assumed`: tanh-approximate
    gelu, and the 8-padded vocabulary rows stay inside the MLM softmax."""
    eps = model["layer_norm_eps"]

    def loss(params, batch):
        ids = batch["input_ids"]
        seq = ids.shape[1]
        word = params["word_embeddings"]["embedding"]
        x = (word[ids]
             + params["position_embeddings"]["embedding"][jnp.arange(seq)]
             + params["token_type_embeddings"]["embedding"][
                 batch["token_type_ids"]])
        x = plain.layer_norm(x, params["embeddings_ln"], eps)
        bias = (1.0 - batch["attention_mask"][:, None, None, :]) * -1e9
        for i in range(num_layers):
            p = params[f"layer_{i}"]
            a = p["attention"]
            q, k, v = (jnp.einsum("bsh,hnd->bsnd", x, a[n]["kernel"])
                       + a[n]["bias"] for n in ("query", "key", "value"))
            ctx = plain.attention(q, k, v, bias)
            attn = jnp.einsum("bqnd,ndh->bqh", ctx,
                              a["output"]["kernel"]) + a["output"]["bias"]
            x = plain.layer_norm(x + attn, p["attention_ln"], eps)
            y = plain.gelu_tanh(x @ p["intermediate"]["kernel"]
                                + p["intermediate"]["bias"])
            y = y @ p["output"]["kernel"] + p["output"]["bias"]
            x = plain.layer_norm(x + y, p["output_ln"], eps)
        y = plain.gelu_tanh(x @ params["mlm_transform"]["kernel"]
                            + params["mlm_transform"]["bias"])
        y = plain.layer_norm(y, params["mlm_ln"], eps)
        logits = y @ word.T + params["mlm_bias"]
        labels = batch["masked_lm_labels"]
        valid = labels != -1
        mlm = jnp.sum(plain.cross_entropy(logits, jnp.where(valid, labels, 0))
                      * valid) / jnp.maximum(jnp.sum(valid), 1)
        pooled = jnp.tanh(x[:, 0] @ params["pooler"]["kernel"]
                          + params["pooler"]["bias"])
        nsp_logits = (pooled @ params["nsp_classifier"]["kernel"]
                      + params["nsp_classifier"]["bias"])
        nsp = jnp.mean(plain.cross_entropy(
            nsp_logits, batch["next_sentence_labels"]))
        return mlm + nsp

    return loss
