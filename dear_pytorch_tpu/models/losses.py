"""The token cross-entropy every language-model head of the zoo shares.

Written so that nothing of the logits' size is sliced, reshaped or
materialised: the caller shifts the *targets* and weights the positions,
and vocabulary columns are told apart by index. A slice of the logits that
is not tile-aligned (``[:, :-1]``, ``[..., :vocab_size]``) costs XLA:TPU a
copy of the logits going forward and a pad coming back; in this form the
head's matmul keeps its one ``cfg.dtype`` output buffer with the row
maximum in its epilogue, one pass over that buffer gives the sum of
exponentials and the target's logit, and the softmax gradient is an operand
of the two backward matmuls (PERF.md, PR 34; ``tests/test_chip_compile.py``
pins the compiled form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def token_cross_entropy(logits, targets, weights, *, valid_vocab=None):
    """``(sum_i w_i * nll_i, sum_i w_i)`` with ``nll_i = logsumexp(logits_i)
    - logits_i[targets_i]``, over every leading position ``i``.

    ``logits [..., V]``; ``targets`` and ``weights`` ``[...]``. A position
    of weight 0 adds nothing to the sum and takes an exactly zero gradient,
    whatever its target (which must still index a column). Columns from
    ``valid_vocab`` on (a padded vocabulary's tail) are outside the
    softmax. The same function as ``log_softmax`` + gather, in f32."""
    logits = logits.astype(jnp.float32)
    col = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    support = logits
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        support = jnp.where(col < valid_vocab, logits, -jnp.inf)
    lse = jax.scipy.special.logsumexp(support, axis=-1)
    tgt = jnp.sum(jnp.where(col == targets[..., None], logits, 0.0), axis=-1)
    weights = weights.astype(jnp.float32)
    return jnp.sum((lse - tgt) * weights), jnp.sum(weights)


def next_token_cross_entropy(logits, input_ids, *, ahead: int = 1,
                             valid_vocab=None):
    """Mean cross-entropy of ``logits[:, i]`` against token ``i + ahead``
    over the ``B * (S - ahead)`` positions that have one. The targets are
    rolled, and the last ``ahead`` positions (whose rolled targets wrap
    around) take weight 0: the gradient of their logits is exactly zero,
    as the pad of a slice's transpose would make it."""
    B, S = input_ids.shape
    total, _ = token_cross_entropy(
        logits, jnp.roll(input_ids, -ahead, axis=1),
        jnp.broadcast_to(jnp.arange(S) < S - ahead, (B, S)),
        valid_vocab=valid_vocab)
    return total / (B * (S - ahead))
