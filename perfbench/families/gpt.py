"""GPT-2 family: the program's model and loss, the batch from the seed, the
model-FLOPs function, and a plain reference of the same mathematics.

Only the program's public API is used (`models.GptLmHeadModel`,
`models.gpt_lm_loss`, `models.GptConfig`); `reference_loss` uses none of
it: plain `jax.numpy` over the parameter tree the model initialises.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `GptConfig` from the published keys of the config file."""
    from dear_pytorch_tpu import models

    drop = (lambda k: model[k]) if dropout else (lambda k: 0.0)
    return models.GptConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["n_embd"],
        num_hidden_layers=num_layers or model["n_layer"],
        num_attention_heads=model["n_head"],
        intermediate_size=model["n_inner"],
        max_position_embeddings=model["n_positions"],
        embd_dropout_prob=drop("embd_pdrop"),
        hidden_dropout_prob=drop("resid_pdrop"),
        attention_probs_dropout_prob=drop("attn_pdrop"),
        layer_norm_eps=model["layer_norm_epsilon"],
        initializer_range=model["initializer_range"],
        dtype=dtype,
    )


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model. ``loss_fn(params,
    batch[, rng])`` is the causal-LM loss of `benchmarks/gpt.py`."""
    from dear_pytorch_tpu import models

    model = models.GptLmHeadModel(cfg)

    def init_fn(key, seq_len: int):
        ids = jnp.zeros((1, seq_len), jnp.int32)
        return model.init({"params": key}, ids, train=False)["params"]

    def loss_fn(params, batch, rng=None):
        rngs = {"dropout": rng} if rng is not None else None
        logits = model.apply({"params": params}, batch["input_ids"],
                             train=True, rngs=rngs)
        return models.gpt_lm_loss(logits, batch["input_ids"],
                                  vocab_size=cfg.vocab_size)

    if with_rng:
        return init_fn, loss_fn
    return init_fn, lambda params, batch: loss_fn(params, batch)


def make_batch(model: dict, key, batch_size: int, seq_len: int) -> dict:
    """Uniform random token ids; next-token targets come from shifting."""
    return {"input_ids": jax.random.randint(
        key, (batch_size, seq_len), 0, model["vocab_size"], jnp.int32)}


def batch_shapes(model: dict, batch_size: int, seq_len: int) -> dict:
    return {"input_ids": ((batch_size, seq_len), jnp.int32)}


def tokens_per_step(batch_size: int, seq_len: int) -> int:
    return batch_size * seq_len


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward, no recompute:
    6 per matmul parameter outside the embeddings (4H^2 attention + 2HI MLP
    per layer), 12*L*S*H for QK^T and AV (the full square, the usual MFU
    convention; the causal half is not discounted), and 6*V*H for the tied
    head. GPT-2 124M at S=1024: 0.854 GFLOP/token."""
    h, layers = model["n_embd"], model["n_layer"]
    per_layer = 4 * h * h + 2 * h * model["n_inner"]
    return float(6 * layers * per_layer + 12 * layers * seq_len * h
                 + 6 * model["vocab_size"] * h)


def initial_loss(model: dict) -> float:
    """Loss of a freshly initialised model: close to ln(vocab)."""
    return math.log(model["vocab_size"])


# -- plain reference ---------------------------------------------------------

def reference_loss(model: dict, num_layers: int):
    """``loss(params, batch)``: GPT-2's forward pass and next-token
    cross-entropy as published (pre-LN blocks, learned positions, gelu_new,
    tied head, no dropout), float32, straightforward `jax.numpy`. Departure
    from the source: none in the mathematics; the parameter tree has 8-padded
    embedding rows, which are sliced off before the softmax."""
    eps, vocab = model["layer_norm_epsilon"], model["vocab_size"]

    def loss(params, batch):
        ids = batch["input_ids"]
        seq = ids.shape[1]
        wte = params["wte"]["embedding"]
        x = wte[ids] + params["wpe"]["embedding"][jnp.arange(seq)][None]
        causal = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), 0.0,
                           -jnp.inf)[None, None]
        for i in range(num_layers):
            p = params[f"h_{i}"]
            y = plain.layer_norm(x, p["ln_1"], eps)
            q, k, v = (jnp.einsum("bsh,hnd->bsnd", y, p[n]["kernel"])
                       + p[n]["bias"] for n in ("query", "key", "value"))
            ctx = plain.attention(q, k, v, causal)
            x = x + jnp.einsum("bqnd,ndh->bqh", ctx,
                               p["output"]["kernel"]) + p["output"]["bias"]
            y = plain.layer_norm(x, p["ln_2"], eps)
            y = plain.gelu_tanh(y @ p["mlp_in"]["kernel"]
                                + p["mlp_in"]["bias"])
            x = x + y @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]
        x = plain.layer_norm(x, params["ln_f"], eps)
        logits = (x @ wte[:vocab].T)[:, :-1]
        return jnp.mean(plain.cross_entropy(logits, ids[:, 1:]))

    return loss
