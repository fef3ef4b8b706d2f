"""LFM2-MoE family (``lfm2_moe``): the program's model and loss, the batch
from the seed, the model-FLOPs functions, the routing counter, and a plain
reference of the same mathematics.

Only the program's public API is used (`models.Lfm2MoeLmHeadModel`,
`models.lfm2_moe_lm_loss`, `models.Lfm2MoeConfig`,
`models.expert_assignments`); the reference uses none of it: plain
`jax.numpy` over the parameter tree the model initialises, written from the
source's description (`transformers`' ``Lfm2Moe*`` classes): the convolution
an explicit sum over its shifts, K and V repeated for their groups, a loop
over the held experts with a dense mask each; no sort, no grouped matmul,
no kernel.

The ``model`` section of the configuration file keeps the published key
names. This chip's share (the file's ``reduced``): ``num_layers`` blocks,
the ``layer_types`` listed there, of the published ``num_hidden_layers``;
``num_dense_layers`` of them dense; ``num_experts`` experts HELD of the
``num_experts_published`` the router scores; ``vocab_size`` ids of
``vocab_size_published``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `Lfm2MoeConfig` from the keys of the config file. The
    family has no dropout; ``dropout`` is accepted for the harness's call."""
    from dear_pytorch_tpu import models

    del dropout
    kinds = tuple(model["layer_types"][:num_layers or model["num_layers"]])
    return models.Lfm2MoeConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_hidden_layers=len(kinds),
        layer_types=kinds,
        num_dense_layers=model["num_dense_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"],
        routed_scaling_factor=model["routed_scaling_factor"],
        use_expert_bias=model["use_expert_bias"],
        conv_L_cache=model["conv_L_cache"],
        conv_bias=model["conv_bias"],
        norm_eps=model["norm_eps"],
        rope_theta=model["rope_theta"],
        max_position_embeddings=model["max_position_embeddings"],
        initializer_range=model["initializer_range"],
        remat=model["remat"],
        dtype=dtype,
    )


def _model_of(cfg) -> dict:
    """The keys the reference reads, back from the program's config."""
    return {
        "layer_types": list(cfg.layer_types),
        "num_layers": cfg.num_hidden_layers,
        "num_dense_layers": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_experts_published": cfg.num_experts,
        "num_experts": cfg.experts_held or cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "conv_L_cache": cfg.conv_L_cache, "norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size,
    }


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model; ``loss_fn(params,
    batch)`` is the loss of `benchmarks/glm.py` for this family. The
    weights are the model's own initialisation from the key with one repair:
    every expert layer's ``expert_bias`` is balanced (`balanced_expert_bias`)
    on a calibration sequence drawn from the same key."""
    from dear_pytorch_tpu import models

    if with_rng:
        raise ValueError("the family has no dropout: dropout_seed is null")
    model = models.Lfm2MoeLmHeadModel(cfg)

    def init_fn(key, seq_len: int):
        # no parameter's shape depends on the sequence (rotary positions)
        params = model.init({"params": key},
                            jnp.zeros((1, 8), jnp.int32))["params"]
        ids = jax.random.randint(jax.random.fold_in(key, 1), (1, seq_len), 0,
                                 cfg.vocab_size, jnp.int32)
        return balanced_expert_bias(_model_of(cfg), params, ids)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"])
        return models.lfm2_moe_lm_loss(logits, batch["input_ids"])

    return init_fn, loss_fn


def expert_assignments(cfg, params, batch):
    """``[expert layers, experts held]``: assignments the program's router
    made to each held expert on this batch (its `intermediates` counter)."""
    from dear_pytorch_tpu import models

    _, collections = models.Lfm2MoeLmHeadModel(cfg).apply(
        {"params": params}, batch["input_ids"], mutable=["intermediates"])
    return models.expert_assignments(cfg, collections["intermediates"])


def make_batch(model: dict, key, batch_size: int, seq_len: int) -> dict:
    """Uniform random token ids drawn from the vocabulary slice held here;
    the targets come from shifting."""
    return {"input_ids": jax.random.randint(
        key, (batch_size, seq_len), 0, model["vocab_size"], jnp.int32)}


def batch_shapes(model: dict, batch_size: int, seq_len: int) -> dict:
    return {"input_ids": ((batch_size, seq_len), jnp.int32)}


def tokens_per_step(batch_size: int, seq_len: int) -> int:
    return batch_size * seq_len


def _kinds(model: dict, num_layers: int | None = None) -> list:
    """[(mixer, ffn)] of the blocks run here."""
    kinds = model["layer_types"][:num_layers or model["num_layers"]]
    return [(mixer, "dense" if i < model["num_dense_layers"] else "expert")
            for i, mixer in enumerate(kinds)]


def matmul_params_per_token(model: dict) -> dict:
    """Matmul parameters one token passes through, by part (the routed
    experts at their expected ``top_k * held / router width`` a token; the
    convolution's taps are no matmul and count nothing)."""
    h = model["hidden_size"]
    q_width = model["num_attention_heads"] * model["head_dim"]
    kv_width = model["num_key_value_heads"] * model["head_dim"]
    expert = 3 * h * model["moe_intermediate_size"]
    routed_per_token = (model["num_experts_per_tok"] * model["num_experts"]
                        / model["num_experts_published"])
    return {
        "conv": 3 * h * h + h * h,
        "attention": 2 * h * q_width + 2 * h * kv_width,
        "dense_mlp": 3 * h * model["intermediate_size"],
        "router": h * model["num_experts_published"],
        "routed": routed_per_token * expert,
        "head": model["vocab_size"] * h,
    }


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward, no recompute:
    6 per active matmul parameter (`matmul_params_per_token`) and ``12 * S *
    heads * head_dim`` an attention layer for QK^T and AV (the full square,
    the usual MFU convention). This configuration at S=8192: 1.40
    GFLOP/token."""
    p = matmul_params_per_token(model)
    kinds = _kinds(model)
    attention = sum(mixer == "full_attention" for mixer, _ in kinds)
    experts = sum(ffn == "expert" for _, ffn in kinds)
    params = ((len(kinds) - attention) * p["conv"]
              + attention * p["attention"]
              + (len(kinds) - experts) * p["dense_mlp"]
              + experts * (p["router"] + p["routed"]) + p["head"])
    square = (12 * seq_len * model["num_attention_heads"]
              * model["head_dim"])
    return float(6 * params + attention * square)


def expert_matmul_flops(model: dict, assignments: float) -> float:
    """FLOPs of the routed experts' matmuls, forward + backward, for
    ``assignments`` (token, expert) pairs: 6 per parameter of one expert."""
    return 6.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"] \
        * assignments


def attention_core_flops(model: dict, batch: int, seq: int) -> float:
    """FLOPs a step of the attention layers' cores over the CAUSAL TRIANGLE,
    forward + backward: QK^T and PV going forward (``4 * S^2 * D`` a head on
    the square), twice that coming back, half of it under the mask: ``6 * B
    * H * S^2 * D`` a layer. The backward kernel's recomputation of the
    scores is time and no FLOPs here."""
    layers = sum(m == "full_attention" for m, _ in _kinds(model))
    return (6.0 * layers * batch * model["num_attention_heads"]
            * seq * seq * model["head_dim"])


def initial_loss(model: dict) -> float:
    """Loss of a freshly initialised model. The tied head's logits are the
    unit-RMS output of an RMSNorm against ``hidden_size`` embedding weights
    of N(0, initializer_range^2): N(0, var) over the vocabulary slice, whose
    expected cross-entropy is ``ln(vocab) + var / 2``: 9.704 + 0.410 = 10.11
    here."""
    var = model["hidden_size"] * model["initializer_range"] ** 2
    return math.log(model["vocab_size"]) + var / 2


# -- plain reference ---------------------------------------------------------

def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _rotary(x, theta):
    """Rotary embedding of ``x`` ``[B, S, heads, dim]`` at positions
    ``0..S-1`` over all ``dim`` lanes; lane ``i`` pairs with lane
    ``i + dim/2``."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, block: int = 512):
    """``softmax(q k^T / sqrt(d)) v`` under the causal mask, one block of
    query rows at a time (a `lax.map` over the blocks, each recomputed in
    the backward pass): the f32 ``[heads, S, S]`` scores never exist whole
    (at S=8192 they are 8.6 GB). The one way this reference differs in form
    from a textbook forward pass."""
    seq, scale = q.shape[1], 1.0 / math.sqrt(q.shape[-1])
    block = math.gcd(seq, block)
    keys = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        visible = keys <= start + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))   # [blocks, B, ...]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def reference_short_conv(model: dict, y, p):
    """``W_out (C * conv(B * x~))`` with ``[B ; C ; x~] = W_in y``: the
    depthwise causal convolution as an explicit sum over its ``conv_L_cache``
    shifts, ``c_t = sum_j w[j] * u_{t-(L-1)+j}``, zeros before the sequence.
    No activation function."""
    taps, seq = model["conv_L_cache"], y.shape[1]
    gate_b, gate_c, x = jnp.split(y @ p["in_proj"]["kernel"], 3, axis=-1)
    u = gate_b * x
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j      # tap j reads u this many positions back
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:, :back]), u[:, :seq - back]], axis=1)
        conv = conv + p["filter"][j] * shifted
    return (gate_c * conv) @ p["out_proj"]["kernel"]


def reference_attention(model: dict, y, p):
    """Grouped-query attention from the normed block input ``y``: a
    per-head RMSNorm (one ``[head_dim]`` weight for the Q heads, one for the
    K heads) BEFORE the rotary embedding, K/V head ``j`` repeated for Q
    heads ``j * group .. (j + 1) * group - 1``."""
    eps, theta = model["norm_eps"], model["rope_theta"]
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    q = jnp.einsum("bsh,hnd->bsnd", y, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", y, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", y, p["v_proj"]["kernel"])
    q = _rotary(_rms_norm(q, p["q_ln"], eps), theta)
    k = _rotary(_rms_norm(k, p["k_ln"], eps), theta)
    ctx = _causal_attention(q, jnp.repeat(k, group, axis=2),
                            jnp.repeat(v, group, axis=2))
    return jnp.einsum("bsnd,ndh->bsh", ctx, p["output"]["kernel"])


def _swiglu(y, p, name):
    gate = y @ p[f"{name}_gate"]["kernel"]
    return (jax.nn.silu(gate) * (y @ p[f"{name}_up"]["kernel"])) \
        @ p[f"{name}_down"]["kernel"]


def reference_routing(model: dict, y, moe):
    """(idx ``[T, k]`` over all the router's experts, weights ``[T, k]``):
    sigmoid scores, the top-k taken on score + ``expert_bias``, the weights
    the scores at the chosen indices over (their sum + 1e-6), scaled."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(y @ moe["router"])
    idx = jnp.argsort(-(scores + moe["router_bias"]), axis=-1)[:, :k]
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return idx, model["routed_scaling_factor"] * weights


def reference_routed(model: dict, y, moe):
    """The held experts' terms of ``sum_k w_k Expert_{idx_k}(y)`` for
    ``y`` ``[T, H]``: a loop over the held experts (a `lax.scan`, so the
    compiler sees one body; each expert recomputed in the backward pass, so
    the f32 activations of one expert are live at a time), every one
    computed on every token and weighted by a dense mask of the tokens
    routed to it. Terms of absent experts are left out (the chip's share);
    there is no shared expert."""
    width = model["moe_intermediate_size"]
    idx, weights = reference_routing(model, y, moe)

    @jax.checkpoint
    def add_expert(out, expert):
        e, wi, wo = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        gate_up = y @ wi
        act = jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]
        return out + w_e[:, None] * (act @ wo), None

    held = model["expert_offset"] + jnp.arange(model["num_experts"])
    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (held, moe["wi"], moe["wo"]))[0]


def reference_mixer(model: dict, x, p, mixer: str):
    """``h = x + Operator(RMSNorm(x))``: the first half of a block."""
    y = _rms_norm(x, p["ln_1"], model["norm_eps"])
    if mixer == "conv":
        return x + reference_short_conv(model, y, p["conv"])
    return x + reference_attention(model, y, p)


def reference_block(model: dict, x, p, mixer: str, ffn: str):
    """``h = x + Operator(RMSNorm(x))``; ``h + FFN(RMSNorm(h))``."""
    x = reference_mixer(model, x, p, mixer)
    y = _rms_norm(x, p["ln_2"], model["norm_eps"])
    if ffn == "dense":
        return x + _swiglu(y, p, "mlp")
    routed = reference_routed(model, y.reshape(-1, y.shape[-1]), p["moe"])
    return x + routed.reshape(y.shape)


#: Steps and rate of the bias's balancing at initialisation: the rule is the
#: family's (auxiliary-loss-free balancing: after a step, an expert's bias
#: moves by the rate against the sign of its load's excess over the mean),
#: the rate DeepSeek-V3's 0.001; neither is in config.json (`assumed`).
BALANCE_STEPS, BALANCE_RATE = 256, 1e-3


def balanced_expert_bias(model: dict, params, ids):
    """``params`` with every expert layer's ``router_bias`` moved from its
    initial value by `BALANCE_STEPS` steps of the balancing rule on the
    sequence ``ids``, layer after layer (a layer sees its input as the
    balanced layers before it give it), then held fixed: what a trained
    router is, every expert near ``T * k / experts`` tokens. The initial
    N(0, 0.02^2) bias alone is a tenth of the scores' spread: it makes some
    experts a third more popular than others, and the held experts' share
    of a step's rows, hence the step time, moves with the seed (3.3% over
    eight seeds on the chip, 0.8% balanced; what is left is the run's own
    sequence, whose running mean of values the attention layer puts into
    every state, and the draw)."""
    k, width = model["num_experts_per_tok"], model["num_experts_published"]
    params = dict(params)
    x = params["wte"]["embedding"][ids]
    for i, (mixer, ffn) in enumerate(_kinds(model)):
        p = params[f"h_{i}"]
        if ffn == "expert":
            h = reference_mixer(model, x, p, mixer)
            y = _rms_norm(h, p["ln_2"], model["norm_eps"])
            scores = jax.nn.sigmoid(y.reshape(-1, y.shape[-1])
                                    @ p["moe"]["router"])

            def step(_, bias):
                _, idx = jax.lax.top_k(scores + bias, k)
                load = jnp.sum(idx[..., None] == jnp.arange(width), (0, 1))
                return bias + BALANCE_RATE * jnp.sign(jnp.mean(load) - load)

            bias = jax.lax.fori_loop(0, BALANCE_STEPS, step,
                                     p["moe"]["router_bias"])
            p = {**p, "moe": {**p["moe"], "router_bias": bias}}
            params[f"h_{i}"] = p
        x = reference_block(model, x, p, mixer, ffn)
    return params


def reference_logits(model: dict, num_layers: int):
    """``(params, ids) -> logits [B, S, V]``: the blocks, the final norm
    (the source's ``embedding_norm``) and the head tied to the embedding,
    over the vocabulary slice."""

    def logits(params, ids):
        wte = params["wte"]["embedding"]
        x = wte[ids]
        for i, (mixer, ffn) in enumerate(_kinds(model, num_layers)):
            x = reference_block(model, x, params[f"h_{i}"], mixer, ffn)
        return _rms_norm(x, params["ln_f"], model["norm_eps"]) @ wte.T

    return logits


def reference_loss(model: dict, num_layers: int):
    """``loss(params, batch)``: next-token cross-entropy, float32,
    straightforward `jax.numpy`. Departures from the source: the attention's
    query blocks (`_causal_attention`); ``wi`` holds an expert's gate and up
    matrices side by side and the convolution's taps lie ``[tap, channel]``;
    what absent experts would add is left out."""
    logits_of = reference_logits(model, num_layers)

    def loss(params, batch):
        ids = batch["input_ids"]
        return jnp.mean(plain.cross_entropy(logits_of(params, ids)[:, :-1],
                                            ids[:, 1:]))

    return loss
