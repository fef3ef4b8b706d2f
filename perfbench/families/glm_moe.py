"""GLM-4.x / DeepSeek-V3 family (``glm4_moe_lite``): the program's model and
loss, the batch from the seed, the model-FLOPs function, the routing counter,
and a plain reference of the same mathematics.

Only the program's public API is used (`models.GlmMoeLmHeadModel`,
`models.glm_moe_lm_loss`, `models.GlmMoeConfig`,
`models.expert_assignments`); `reference_loss` uses none of it: plain
`jax.numpy` over the parameter tree the model initialises, a loop over the
held experts with a dense mask each, no sort, no grouped matmul, no kernel.

The ``model`` section of the configuration file keeps the published key
names. Three of them carry this chip's share (the file's ``reduced``):
``num_layers`` blocks of the published ``num_hidden_layers``,
``n_routed_experts`` experts HELD of the ``n_routed_experts_published`` the
router scores, ``vocab_size`` ids of ``vocab_size_published``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `GlmMoeConfig` from the keys of the config file. The
    family has no dropout; ``dropout`` is accepted for the harness's call."""
    from dear_pytorch_tpu import models

    del dropout
    return models.GlmMoeConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        num_layers=num_layers or model["num_layers"],
        first_k_dense_replace=model["first_k_dense_replace"],
        num_attention_heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        n_routed_experts=model["n_routed_experts_published"],
        experts_held=model["n_routed_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        rope_theta=model["rope_theta"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        num_nextn_predict_layers=model["num_nextn_predict_layers"],
        mtp_loss_weight=model["mtp_loss_weight"],
        remat=model["remat"],
        dtype=dtype,
    )


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model; ``loss_fn(params,
    batch)`` is the loss of `benchmarks/glm.py`."""
    from dear_pytorch_tpu import models

    if with_rng:
        raise ValueError("the family has no dropout: dropout_seed is null")
    model = models.GlmMoeLmHeadModel(cfg)

    def init_fn(key, seq_len: int):
        # no parameter's shape depends on the sequence (rotary positions)
        del seq_len
        return model.init({"params": key},
                          jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(params, batch):
        outputs = model.apply({"params": params}, batch["input_ids"])
        return models.glm_moe_lm_loss(outputs, batch["input_ids"],
                                      mtp_loss_weight=cfg.mtp_loss_weight)

    return init_fn, loss_fn


def expert_assignments(cfg, params, batch):
    """``[expert layers, experts held]``: assignments the program's router
    made to each held expert on this batch (its `intermediates` counter)."""
    from dear_pytorch_tpu import models

    _, collections = models.GlmMoeLmHeadModel(cfg).apply(
        {"params": params}, batch["input_ids"], mutable=["intermediates"])
    return models.expert_assignments(cfg, collections["intermediates"])


def make_batch(model: dict, key, batch_size: int, seq_len: int) -> dict:
    """Uniform random token ids drawn from the vocabulary slice held here;
    the targets one and two ahead come from shifting."""
    return {"input_ids": jax.random.randint(
        key, (batch_size, seq_len), 0, model["vocab_size"], jnp.int32)}


def batch_shapes(model: dict, batch_size: int, seq_len: int) -> dict:
    return {"input_ids": ((batch_size, seq_len), jnp.int32)}


def tokens_per_step(batch_size: int, seq_len: int) -> int:
    """The prediction module's second targets are not extra tokens."""
    return batch_size * seq_len


def matmul_params_per_token(model: dict) -> dict:
    """Matmul parameters one token passes through, by part (the routed
    experts at their expected ``top_k * held / router width`` a token)."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    r_q, r_kv = model["q_lora_rank"], model["kv_lora_rank"]
    expert = 3 * h * model["moe_intermediate_size"]
    routed_per_token = (model["num_experts_per_tok"] * model["n_routed_experts"]
                        / model["n_routed_experts_published"])
    return {
        "attention": (h * r_q + r_q * nh * qk
                      + h * (r_kv + model["qk_rope_head_dim"])
                      + r_kv * nh * (model["qk_nope_head_dim"]
                                     + model["v_head_dim"])
                      + nh * model["v_head_dim"] * h),
        "dense_mlp": 3 * h * model["intermediate_size"],
        "shared": model["n_shared_experts"] * expert,
        "router": h * model["n_routed_experts_published"],
        "routed": routed_per_token * expert,
        "mtp_merge": 2 * h * h,
        "head": model["vocab_size"] * h,
    }


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward, no recompute:
    6 per active matmul parameter (`matmul_params_per_token`; the head
    twice, one matrix under two predictions), and ``12 * S * heads *
    v_head_dim`` a block for QK^T and AV (the full square, the usual MFU
    convention). This configuration at S=4096: 3.62 GFLOP/token."""
    p = matmul_params_per_token(model)
    dense = model["first_k_dense_replace"]
    mtp = model["num_nextn_predict_layers"]
    blocks = model["num_layers"] + mtp
    experts = blocks - dense
    params = (blocks * p["attention"] + dense * p["dense_mlp"]
              + experts * (p["shared"] + p["router"] + p["routed"])
              + mtp * p["mtp_merge"] + (1 + mtp) * p["head"])
    square = 12 * seq_len * model["num_attention_heads"] * model["v_head_dim"]
    return float(6 * params + blocks * square)


def expert_matmul_flops(model: dict, assignments: float) -> float:
    """FLOPs of the routed experts' matmuls, forward + backward, for
    ``assignments`` (token, expert) pairs: 6 per parameter of one expert."""
    return 6.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"] \
        * assignments


def initial_loss(model: dict) -> float:
    """Loss of a freshly initialised model. Each head's logits are the
    unit-RMS output of an RMSNorm against ``hidden_size`` weights of
    N(0, initializer_range^2): N(0, var) over the vocabulary, whose expected
    cross-entropy is ``ln(vocab) + var / 2``; the prediction module's weighs
    ``mtp_loss_weight``. 1.3 * (9.871 + 0.410) = 13.36 here."""
    var = model["hidden_size"] * model["initializer_range"] ** 2
    heads = 1 + model["num_nextn_predict_layers"] * model["mtp_loss_weight"]
    return heads * (math.log(model["vocab_size"]) + var / 2)


# -- plain reference ---------------------------------------------------------

def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _rotary(x, theta):
    """Rotary embedding of ``x`` ``[B, S, heads, dim]`` at positions
    ``0..S-1``; lane ``i`` pairs with lane ``i + dim/2``."""
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
    (at S=4096 they are 1.3 GB a layer). The one way this reference differs
    in form from a textbook forward pass."""
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
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[:3] + v.shape[3:])


def _swiglu(y, p, name):
    gate = y @ p[f"{name}_gate"]["kernel"]
    return (jax.nn.silu(gate) * (y @ p[f"{name}_up"]["kernel"])) \
        @ p[f"{name}_down"]["kernel"]


def reference_routing(model: dict, y, moe):
    """(idx ``[T, k]`` over all the router's experts, weights ``[T, k]``):
    ``noaux_tc``: sigmoid scores, the top-k taken on score + bias, the
    weights on the score alone, normalised and scaled."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(y @ moe["router"])
    idx = jnp.argsort(-(scores + moe["router_bias"]), axis=-1)[:, :k]
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, model["routed_scaling_factor"] * weights


def reference_routed(model: dict, y, moe):
    """The held experts' terms of ``sum_k w_k Expert_{idx_k}(y)`` for
    ``y`` ``[T, H]``: a loop over the held experts (a `lax.scan`, so the
    compiler sees one body; each expert recomputed in the backward pass, so
    the f32 activations of one expert are live at a time), every one
    computed on every token and weighted by a dense mask of the tokens
    routed to it. Terms of absent experts are left out (the chip's share)."""
    width = model["moe_intermediate_size"]
    idx, weights = reference_routing(model, y, moe)

    @jax.checkpoint
    def add_expert(out, expert):
        e, wi, wo = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        gate_up = y @ wi
        act = jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]
        return out + w_e[:, None] * (act @ wo), None

    held = model["expert_offset"] + jnp.arange(model["n_routed_experts"])
    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (held, moe["wi"], moe["wo"]))[0]


def reference_attention_inputs(model: dict, y, p):
    """(q, k, v) ``[B, S, heads, 256]`` of multi-head latent attention from
    the normed block input ``y``: low-rank query and key/value paths, rotary
    on the last ``qk_rope_head_dim`` lanes of q and on the one key head all
    heads share."""
    nope, theta = model["qk_nope_head_dim"], model["rope_theta"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    c_q = _rms_norm(y @ p["q_down"]["kernel"], p["q_ln"], eps)
    q = jnp.einsum("bsr,rnd->bsnd", c_q, p["q_up"]["kernel"])
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], -1)
    kv = y @ p["kv_down"]["kernel"]
    c_kv = _rms_norm(kv[..., :rank], p["kv_ln"], eps)
    k_nope = jnp.einsum("bsr,rnd->bsnd", c_kv, p["k_up"]["kernel"])
    k_rope = _rotary(kv[..., None, rank:], theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + k_rope.shape[3:])],
        axis=-1)
    v = jnp.einsum("bsr,rnd->bsnd", c_kv, p["v_up"]["kernel"])
    return q, k, v


def reference_attention(model: dict, x, p):
    """``x + MLA(RMSNorm(x))``: the first half of a block."""
    q, k, v = reference_attention_inputs(
        model, _rms_norm(x, p["ln_1"], model["rms_norm_eps"]), p)
    return x + jnp.einsum("bsnd,ndh->bsh", _causal_attention(q, k, v),
                          p["output"]["kernel"])


def reference_block(model: dict, x, p, kind: str):
    x = reference_attention(model, x, p)
    y = _rms_norm(x, p["ln_2"], model["rms_norm_eps"])
    if kind == "dense":
        return x + _swiglu(y, p, "mlp")
    routed = reference_routed(model, y.reshape(-1, y.shape[-1]), p["moe"])
    return x + routed.reshape(y.shape) + _swiglu(y, p, "shared")


def reference_logits(model: dict, num_layers: int):
    """``(params, ids) -> (logits [B, S, V], mtp_logits [B, S-1, V] or
    None)``: the blocks, the head, and the prediction module (DeepSeek-V3
    report, eq. 21-25) on positions ``0..S-2``: the last block's state at
    ``i`` beside the embedding of token ``i + 1``, one more expert block,
    its own final norm, the shared embedding and head."""
    eps = model["rms_norm_eps"]

    def logits(params, ids):
        wte, head = params["wte"]["embedding"], params["lm_head"]["kernel"]
        x = wte[ids]
        for i in range(num_layers):
            kind = ("dense" if i < model["first_k_dense_replace"]
                    else "expert")
            x = reference_block(model, x, params[f"h_{i}"], kind)
        main = _rms_norm(x, params["ln_f"], eps) @ head
        if not model["num_nextn_predict_layers"]:
            return main, None
        merged = jnp.concatenate(
            [_rms_norm(x[:, :-1], params["ln_mtp_h"], eps),
             _rms_norm(wte[ids[:, 1:]], params["ln_mtp_e"], eps)], axis=-1)
        h = reference_block(model, merged @ params["mtp_eh_proj"]["kernel"],
                            params["mtp_block"], "expert")
        return main, _rms_norm(h, params["ln_mtp_f"], eps) @ head

    return logits


def reference_loss(model: dict, num_layers: int):
    """``loss(params, batch)``: ``L_main + mtp_loss_weight * L_mtp``, float32,
    straightforward `jax.numpy`. Departures from the source: the attention's
    query blocks (`_causal_attention`); ``wi`` holds an expert's gate and up
    matrices side by side; what absent experts would add is left out."""
    logits_of = reference_logits(model, num_layers)

    def loss(params, batch):
        ids = batch["input_ids"]
        main, mtp = logits_of(params, ids)
        total = jnp.mean(plain.cross_entropy(main[:, :-1], ids[:, 1:]))
        if mtp is not None:
            total = total + model["mtp_loss_weight"] * jnp.mean(
                plain.cross_entropy(mtp[:, :-1], ids[:, 2:]))
        return total

    return loss
