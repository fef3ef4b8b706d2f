"""Plain `jax.numpy` pieces the families' reference models share: nothing of
the program's, nothing of flax."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    """The tanh approximation of gelu (GPT-2's ``gelu_new``)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def cross_entropy(logits, labels):
    """Per-position negative log-likelihood of integer ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def attention(q, k, v, bias):
    """Softmax attention on ``[batch, seq, heads, dim]`` tensors with an
    additive ``bias`` broadcast over ``[batch, heads, query, key]``."""
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1])
    return jnp.einsum("bnqk,bknd->bqnd",
                      jax.nn.softmax(scores + bias, axis=-1), v)
