"""Ling-3.0 family (``bailing_hybrid``): the program's model and loss, the
batch from the seed, the model-FLOPs functions, the KDA scan's work, the
routing counter, and a plain reference of the same mathematics.

Only the program's public API is used (`models.LingHybridLmHeadModel`,
`models.glm_moe_lm_loss`, `models.LingHybridConfig`,
`models.expert_assignments`); the reference uses none of it: plain
`jax.numpy` over the parameter tree the model initialises. Its delta rule is
NOT computed by chunks but by the recurrence itself, one position at a time
(a `lax.scan` whose blocks of 64 positions sit under `jax.checkpoint`, so
its backward pass fits at S=8192): an independent check of the chunked
algebra of `ops.kda`. Its latent attention is unpadded, at the head widths
192 / 128; its routing is the group-limited selection written out; the
convolutions are explicit sums over their shifts; no kernel.

The ``model`` section of the configuration file keeps the published key
names. This chip's share (the file's ``reduced``): the ``layer_types`` listed
there (``num_hidden_layers`` of ``num_hidden_layers_published``, from
published layer ``first_layer``), ``first_k_dense_replace`` of them dense,
``num_experts`` experts HELD of the ``num_experts_published`` the router
scores, ``vocab_size`` ids of ``vocab_size_published``. The reference
check's shallower model is the same with the keys of ``reference`` over it
(`at_depth`).

Departures of the reference from the source, all of form and none of value:
the delta rule's blocks of positions, the attention's blocks of query rows,
the cross-entropy's blocks of positions, the KDA heads' groups and the
blocks themselves (all recomputed in the backward pass); ``kv_b_proj`` is two leaves,
``k_up`` and ``v_up`` (the same matrix cut by its output columns), and an
expert's gate and up matrices are stored side by side (``wi``); the
convolutions' taps lie ``[tap, channel]``; the SwiGLU clamps are absent,
being 0 in every layer held (the configuration's lists are checked); what
absent experts would add is left out.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain


def _kinds(model: dict) -> list:
    """[(mixer, ffn)] of the blocks run: ``layer_types``, the first
    ``first_k_dense_replace`` dense."""
    dense = model["first_k_dense_replace"]
    return [(mixer, "dense" if i < dense else "expert")
            for i, mixer in enumerate(model["layer_types"])]


def at_depth(model: dict, num_layers: int | None = None) -> dict:
    """The model run: this chip's, or, for a shallower reference model of
    ``num_layers`` blocks, the same with the keys of ``reference`` over it
    (its ``layer_types``, ``first_layer``, ``first_k_dense_replace`` and
    ``num_nextn_predict_layers``)."""
    if num_layers is None or num_layers == len(model["layer_types"]):
        return model
    shallow = {**model, **model["reference"]}
    if len(shallow["layer_types"]) != num_layers:
        raise ValueError(
            f"the reference model has {len(shallow['layer_types'])} layers, "
            f"the reference check asks for {num_layers}")
    return shallow


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `LingHybridConfig` from the keys of the config file.
    The family has no dropout; ``dropout`` is accepted for the harness's
    call."""
    from dear_pytorch_tpu import models

    del dropout
    model = at_depth(model, num_layers)
    return models.LingHybridConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        layer_types=tuple(model["layer_types"]),
        first_layer=model["first_layer"],
        first_k_dense_replace=model["first_k_dense_replace"],
        num_attention_heads=model["num_attention_heads"],
        head_dim=model["head_dim"],
        short_conv_kernel_size=model["short_conv_kernel_size"],
        kda_lower_bound=model["kda_lower_bound"],
        kda_chunk_size=model["kda_chunk_size"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_theta=model["rope_theta"],
        intermediate_size=model["intermediate_size"],
        moe_intermediate_size=model["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=model[
            "moe_shared_expert_intermediate_size"],
        num_experts=model["num_experts_published"],
        experts_held=model["num_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"],
        expert_swiglu_limits=tuple(model["expert_swiglu_limit_list"]),
        shared_swiglu_limits=tuple(model["share_expert_swiglu_limit_list"]),
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        num_nextn_predict_layers=model["num_nextn_predict_layers"],
        mtp_loss_weight=model["mtp_loss_weight"],
        remat=model["remat"],
        dtype=dtype,
    )


def _model_of(cfg) -> dict:
    """The keys the reference reads, back from the program's config."""
    return {
        "layer_types": list(cfg.layer_types),
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "num_attention_heads": cfg.num_attention_heads,
        "head_dim": cfg.head_dim,
        "short_conv_kernel_size": cfg.short_conv_kernel_size,
        "kda_lower_bound": cfg.kda_lower_bound,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_experts_published": cfg.num_experts,
        "num_experts": cfg.experts_held or cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_nextn_predict_layers": cfg.num_nextn_predict_layers,
    }


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model; ``loss_fn(params,
    batch)`` is the loss of `benchmarks/glm.py` for this family. The
    weights are the model's own initialisation from the key with one repair:
    every expert layer's ``router_bias`` is balanced (`balanced_expert_bias`)
    on a calibration sequence drawn from the same key."""
    from dear_pytorch_tpu import models

    if with_rng:
        raise ValueError("the family has no dropout: dropout_seed is null")
    model = models.LingHybridLmHeadModel(cfg)

    def init_fn(key, seq_len: int):
        # no parameter's shape depends on the sequence (rotary positions)
        params = model.init({"params": key},
                            jnp.zeros((1, 8), jnp.int32))["params"]
        ids = jax.random.randint(jax.random.fold_in(key, 1), (1, seq_len), 0,
                                 cfg.vocab_size, jnp.int32)
        return balanced_expert_bias(_model_of(cfg), params, ids)

    def loss_fn(params, batch):
        outputs = model.apply({"params": params}, batch["input_ids"])
        return models.glm_moe_lm_loss(outputs, batch["input_ids"],
                                      mtp_loss_weight=cfg.mtp_loss_weight)

    return init_fn, loss_fn


def expert_assignments(cfg, params, batch):
    """``[expert layers, experts held]``: assignments the program's router
    made to each held expert on this batch (its `intermediates` counter)."""
    from dear_pytorch_tpu import models

    _, collections = models.LingHybridLmHeadModel(cfg).apply(
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


# -- work --------------------------------------------------------------------

def matmul_params_per_token(model: dict) -> dict:
    """Matmul parameters one token passes through, by part (the routed
    experts at their expected ``top_k * held / router width`` a token; taps,
    gates' biases, ``A_log`` and norms are no matmul and count nothing)."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    width = nh * model["head_dim"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    rank, vd = model["kv_lora_rank"], model["v_head_dim"]
    expert = 3 * h * model["moe_intermediate_size"]
    routed_per_token = (model["num_experts_per_tok"] * model["num_experts"]
                        / model["num_experts_published"])
    return {
        # q, k, v, f, g and W_o at the heads' width, beta one a head
        "kda": 6 * h * width + h * nh,
        "mla": (h * nh * qk + h * (rank + model["qk_rope_head_dim"])
                + rank * nh * (model["qk_nope_head_dim"] + vd) + h * nh
                + nh * vd * h),
        "dense_mlp": 3 * h * model["intermediate_size"],
        "shared": 3 * h * model["moe_shared_expert_intermediate_size"],
        "router": h * model["num_experts_published"],
        "routed": routed_per_token * expert,
        "mtp_merge": 2 * h * h,
        "head": model["vocab_size"] * h,
    }


def _scan_flops_per_token_forward(model: dict) -> int:
    """Matmul FLOPs a token and head of one KDA layer's scan going forward,
    by the chunked algorithm at ``kda_chunk_size`` ``C`` (keys ``K``, values
    ``V``, both ``head_dim``): the factorised ``A`` and ``P`` (``2 C K``
    each), the triangular solve (``C (K + V)``), the chunk's correction and
    addition to the state (``2 K K``, ``2 K V``), the state's carry over
    chunks (``2 K K V / C``), and the reads of the entering state by ``W``
    and ``Q`` (``2 K V`` each) and of ``U`` by ``P`` (``2 C V``). Whole
    ``[C, C]`` tiles count; decays, gates and masks are elementwise and
    count nothing."""
    c, k = model["kda_chunk_size"], model["head_dim"]
    v = k
    return (4 * c * k + c * (k + v) + 2 * k * k + 2 * k * v
            + 2 * k * k * v // c + 4 * k * v + 2 * c * v)


def kda_scan_flops(model: dict, tokens: int) -> float:
    """FLOPs a step of the KDA layers' scans, forward + backward (every
    matmul once going forward and twice coming back; recomputation is time
    and no FLOPs here), whatever implements the scan: a function of the
    model and the token count only. 262,144 a token and head at chunk 64
    and width 128."""
    layers = sum(m == "kda" for m, _ in _kinds(model))
    return (3.0 * layers * tokens * model["num_attention_heads"]
            * _scan_flops_per_token_forward(model))


def kda_scan_bytes(model: dict, tokens: int) -> float:
    """HBM bytes a step the scans cannot avoid, forward + backward: ``q``,
    ``k``, ``v`` (two bytes an element, the compute dtype), the log-decay
    and ``beta`` (four) read and ``o`` written going forward; those and
    ``do`` read and ``dq``, ``dk``, ``dv``, the log-decay's and ``beta``'s
    gradients written coming back."""
    k = v = model["head_dim"]
    inputs = 2 * (2 * k + v) + 4 * k + 4
    forward = inputs + 2 * v
    backward = inputs + 2 * v + inputs
    layers = sum(m == "kda" for m, _ in _kinds(model))
    return float(layers * tokens * model["num_attention_heads"]
                 * (forward + backward))


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward, no recompute: 6
    per active matmul parameter (`matmul_params_per_token`; the head twice,
    one matrix under two predictions), ``6 * S * heads * (192 + 128)`` an
    MLA block for QK^T and AV at their own widths (the full square, the
    convention of the other families and of `kernel_flops_util_pct`; the
    core's zero lanes are no model FLOPs), and the KDA scans' matmuls
    (`kda_scan_flops`)."""
    p = matmul_params_per_token(model)
    kinds = _kinds(model)
    mtp = model["num_nextn_predict_layers"]
    mla = sum(m == "mla" for m, _ in kinds) + mtp
    dense = sum(f == "dense" for _, f in kinds)
    experts = len(kinds) - dense + mtp
    params = (sum(p[m] for m, _ in kinds) + mtp * p["mla"]
              + dense * p["dense_mlp"]
              + experts * (p["shared"] + p["router"] + p["routed"])
              + mtp * p["mtp_merge"] + (1 + mtp) * p["head"])
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    square = 6 * seq_len * model["num_attention_heads"] * (
        qk + model["v_head_dim"])
    return float(6 * params + mla * square + kda_scan_flops(model, 1))


def expert_matmul_flops(model: dict, assignments: float) -> float:
    """FLOPs of the routed experts' matmuls, forward + backward, for
    ``assignments`` (token, expert) pairs: 6 per parameter of one expert."""
    return 6.0 * 3 * model["hidden_size"] * model["moe_intermediate_size"] \
        * assignments


def initial_loss(model: dict) -> float:
    """Loss of a freshly initialised model. Each head's logits are the
    unit-RMS output of an RMSNorm against ``hidden_size`` weights of
    N(0, initializer_range^2): N(0, var) over the vocabulary slice, whose
    expected cross-entropy is ``ln(vocab) + var / 2``; the prediction
    module's weighs ``mtp_loss_weight``. 1.3 * (9.886 + 0.512) = 13.52
    here."""
    var = model["hidden_size"] * model["initializer_range"] ** 2
    heads = 1 + model["num_nextn_predict_layers"] * model["mtp_loss_weight"]
    return heads * (math.log(model["vocab_size"]) + var / 2)


# -- plain reference ---------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _swiglu(y, p, name):
    gate = y @ p[f"{name}_gate"]["kernel"]
    return (jax.nn.silu(gate) * (y @ p[f"{name}_up"]["kernel"])) \
        @ p[f"{name}_down"]["kernel"]


def _causal_conv(x, taps):
    """``c_t = sum_j taps[j] x_{t - (L - 1) + j}``, zeros before the
    sequence: an explicit sum over the shifts."""
    lag, seq = taps.shape[0] - 1, x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(lag + 1):
        back = lag - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :seq - back]], axis=1)
        out = out + taps[j] * shifted
    return out


def reference_delta_rule(q, k, v, log_alpha, beta, block: int = 64):
    """``o_t = S_t^T q_t / sqrt(K)`` of ``S_t = (I - beta_t k_t k_t^T)
    Diag(exp(log_alpha_t)) S_{t-1} + beta_t k_t v_t^T``, one position at a
    time: a `lax.scan` over blocks of ``block`` positions, each block a
    `lax.scan` over its positions under `jax.checkpoint` (the backward pass
    keeps one state a block and recomputes the block's). ``q``, ``k``,
    ``log_alpha`` ``[B, S, h, K]``, ``v`` ``[B, S, h, V]``, ``beta`` ``[B, S,
    h]``. No chunk, no triangular system."""
    batch, seq, heads, width = q.shape
    block = math.gcd(seq, block)

    def position(state, t):
        qt, kt, vt, gt, bt = t
        state = jnp.exp(gt)[..., None] * state
        delta = vt - jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] * delta[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    @jax.checkpoint
    def run_block(state, xs):
        return jax.lax.scan(position, state, xs)

    xs = tuple(jnp.moveaxis(t, 1, 0).reshape(seq // block, block,
                                             *t.shape[:1], *t.shape[2:])
               for t in (q, k, v, log_alpha, beta))
    state = jnp.zeros((batch, heads, width, v.shape[-1]), q.dtype)
    _, out = jax.lax.scan(run_block, state, xs)
    out = out.reshape(seq, batch, heads, v.shape[-1])
    return jnp.moveaxis(out, 0, 1) / math.sqrt(width)


def reference_kda(model: dict, y, p, heads_at_once: int = 8):
    """The KDA mixer from the normed block input ``y`` ``[B, S, H]``: the
    projections; each of q, k, v through its causal depthwise convolution
    and a silu, q and k l2-normed a head (eps 1e-6); ``beta = sigmoid(W_b
    y)``; ``log alpha = lower_bound * sigmoid(exp(A_log) (W_f y +
    dt_bias))``; the delta rule (`reference_delta_rule`); a head's RMSNorm of
    ``o`` times ``sigmoid(W_g y)``; ``W_o``. The heads run ``heads_at_once``
    at a time (a `lax.scan` over groups summing their parts of ``W_o``'s
    product, each recomputed in the backward pass): the float32 ``[S,
    heads x 128]`` intermediates of all heads never exist together."""
    batch, seq, _ = y.shape
    heads, d = model["num_attention_heads"], model["head_dim"]
    group = math.gcd(heads, heads_at_once)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def add_group(out, first):    # ``first``: the group's first head
        def cols(w, per=d):       # this group's columns of ``w``
            return jax.lax.dynamic_slice_in_dim(w, first * per, group * per,
                                                axis=w.ndim - 1)

        def heads_of(x):
            return x.reshape(batch, seq, group, -1)

        q, k, v = (heads_of(jax.nn.silu(_causal_conv(
            y @ cols(p[f"{n}_proj"]["kernel"]), cols(p[f"{n}_conv"]))))
            for n in "qkv")
        beta = jax.nn.sigmoid(y @ cols(p["b_proj"]["kernel"], 1))
        log_alpha = model["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(cols(p["A_log"], 1))[:, None]
            * heads_of(y @ cols(p["f_proj"]["kernel"]) + cols(p["dt_bias"])))
        o = reference_delta_rule(l2(q), l2(k), v, log_alpha, beta)
        gated = (_rms_norm(o, p["o_norm"], model["rms_norm_eps"])
                 * jax.nn.sigmoid(heads_of(y @ cols(p["g_proj"]["kernel"]))))
        w_o = jax.lax.dynamic_slice_in_dim(p["out_proj"]["kernel"], first * d,
                                           group * d, axis=0)
        return out + gated.reshape(batch, seq, group * d) @ w_o, None

    zeros = jnp.zeros((batch, seq, p["out_proj"]["kernel"].shape[-1]),
                      y.dtype)
    return jax.lax.scan(add_group, zeros, jnp.arange(0, heads, group))[0]


def _interleaved_rotary(x, theta):
    """Rotary embedding of ``x`` ``[B, S, heads, dim]`` at positions
    ``0..S-1``: lanes ``2i`` and ``2i + 1`` turn together at
    ``theta^(-2i/dim)``."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return (jnp.zeros_like(x).at[..., 0::2].set(even * cos - odd * sin)
            .at[..., 1::2].set(odd * cos + even * sin))


def _causal_attention(q, k, v, block: int = 512):
    """``softmax(q k^T / sqrt(d_qk)) v`` under the causal mask, one block of
    query rows at a time (a `lax.map` over the blocks, each recomputed in
    the backward pass; the last block padded with rows past the sequence,
    dropped): the f32 ``[heads, S, S]`` scores never exist whole."""
    seq, scale = q.shape[1], 1.0 / math.sqrt(q.shape[-1])
    blocks = -(-seq // block)
    padded = jnp.pad(q, ((0, 0), (0, blocks * block - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)[None, :]

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(padded, start, block, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        visible = keys <= start + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, blocks * block, block))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape[0], blocks * block,
                                          *v.shape[2:])
    return out[:, :seq]


def reference_mla(model: dict, y, p):
    """Latent attention from the normed block input ``y``: ``q = W_q y``;
    ``[c_kv | k_rope] = W_kv_a y``; ``k_nope``, ``v`` from ``RMSNorm(c_kv)``;
    a per-head RMSNorm over q's and k's 192 lanes BEFORE the interleaved
    rotary on their last 64 (k's rotary lanes one head for all);
    ``softmax(q k^T / sqrt(192)) v`` at v's own 128 lanes; each head's
    context times ``sigmoid(w_{g,h} . y)``; ``W_o``."""
    nope, theta = model["qk_nope_head_dim"], model["rope_theta"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    q = jnp.einsum("bsh,hnd->bsnd", y, p["q_proj"]["kernel"])
    kv = y @ p["kv_a_proj"]["kernel"]
    c_kv = _rms_norm(kv[..., :rank], p["kv_a_ln"]["scale"], eps)
    k_nope = jnp.einsum("bsr,rnd->bsnd", c_kv, p["k_up"]["kernel"])
    k_rope = jnp.broadcast_to(kv[..., None, rank:],
                              k_nope.shape[:3] + (kv.shape[-1] - rank,))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    v = jnp.einsum("bsr,rnd->bsnd", c_kv, p["v_up"]["kernel"])
    q, k = _rms_norm(q, p["q_ln"], eps), _rms_norm(k, p["k_ln"], eps)
    q = jnp.concatenate([q[..., :nope], _interleaved_rotary(q[..., nope:],
                                                            theta)], -1)
    k = jnp.concatenate([k[..., :nope], _interleaved_rotary(k[..., nope:],
                                                            theta)], -1)
    ctx = _causal_attention(q, k, v)
    gate = jax.nn.sigmoid(y @ p["gate_proj"]["kernel"])
    return jnp.einsum("bsnd,ndh->bsh", ctx * gate[..., None],
                      p["output"]["kernel"])


def reference_selection(model: dict, choice, k: int | None = None):
    """idx ``[T, k]``: the group-limited top-k of ``choice`` (score +
    bias) ``[T, experts]``: ``n_group`` equal groups, each scored by the sum
    of its two largest entries; the ``topk_group`` best groups kept; the
    top-k experts of the kept groups."""
    k = k or model["num_experts_per_tok"]
    tokens, width = choice.shape
    groups = choice.reshape(tokens, model["n_group"], -1)
    two_best = jax.lax.top_k(groups, 2)[0].sum(-1)            # [T, groups]
    kept = jax.lax.top_k(two_best, model["topk_group"])[1]
    in_kept = jnp.zeros(two_best.shape, bool).at[
        jnp.arange(tokens)[:, None], kept].set(True)
    limited = jnp.where(in_kept[..., None], groups, -jnp.inf)
    return jax.lax.top_k(limited.reshape(tokens, width), k)[1]


def reference_routing(model: dict, y, moe):
    """(idx ``[T, k]`` over all the router's experts, weights ``[T, k]``):
    ``noaux_tc``: sigmoid scores, the group-limited top-k taken on score +
    bias, the weights on the score alone, normalised and scaled."""
    scores = jax.nn.sigmoid(y @ moe["router"])
    idx = reference_selection(model, scores + moe["router_bias"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, model["routed_scaling_factor"] * weights


def reference_routed(model: dict, y, moe):
    """The held experts' terms of ``sum_k w_k Expert_{idx_k}(y)`` for
    ``y`` ``[T, H]``: a loop over the held experts (a `lax.scan`, each
    expert recomputed in the backward pass), every one computed on every
    token and weighted by a dense mask of the tokens routed to it. Terms of
    absent experts are left out (the chip's share)."""
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


def reference_mixer(model: dict, x, p, mixer: str, heads_at_once: int = 8):
    """``h = x + Mixer(RMSNorm(x))``: the first half of a block."""
    y = _rms_norm(x, p["ln_1"]["scale"], model["rms_norm_eps"])
    if mixer == "kda":
        return x + reference_kda(model, y, p["kda"], heads_at_once)
    return x + reference_mla(model, y, p)


def reference_ffn(model: dict, x, p, ffn: str):
    """``x + FFN(RMSNorm(x))``, the FFN a dense SwiGLU or the held routed
    experts plus the shared expert: the second half of a block."""
    y = _rms_norm(x, p["ln_2"]["scale"], model["rms_norm_eps"])
    if ffn == "dense":
        return x + _swiglu(y, p, "mlp")
    routed = reference_routed(model, y.reshape(-1, y.shape[-1]), p["moe"])
    return x + routed.reshape(y.shape) + _swiglu(y, p, "shared")


def reference_block(model: dict, x, p, mixer: str, ffn: str,
                    heads_at_once: int = 8):
    """``h = x + Mixer(RMSNorm(x))``; ``h + FFN(RMSNorm(h))``."""
    return reference_ffn(model, reference_mixer(model, x, p, mixer,
                                                heads_at_once), p, ffn)


def _mtp_input(model: dict, params, x, ids):
    """The prediction module's block input on positions ``0..S-2``: the last
    block's state at ``i`` beside the embedding of token ``i + 1``, each
    RMS-normed, through ``W_eh``."""
    eps = model["rms_norm_eps"]
    merged = jnp.concatenate(
        [_rms_norm(x[:, :-1], params["ln_mtp_h"]["scale"], eps),
         _rms_norm(params["wte"]["embedding"][ids[:, 1:]],
                   params["ln_mtp_e"]["scale"], eps)], axis=-1)
    return merged @ params["mtp_eh_proj"]["kernel"]


#: Steps and rate of the bias's balancing at initialisation: the LFM2
#: family's rule (`perfbench/families/lfm2_moe.py`), its constants
#: DeepSeek-V3's (`assumed`).
BALANCE_STEPS, BALANCE_RATE = 256, 1e-3


def _balanced(model: dict, p, y):
    """``p`` with its ``moe/router_bias`` moved by `BALANCE_STEPS` steps of
    the balancing rule on the expert layer's input ``y``."""
    width = model["num_experts_published"]
    scores = jax.nn.sigmoid(y.reshape(-1, y.shape[-1]) @ p["moe"]["router"])

    def step(_, bias):
        idx = reference_selection(model, scores + bias)
        load = jnp.sum(idx[..., None] == jnp.arange(width), (0, 1))
        return bias + BALANCE_RATE * jnp.sign(jnp.mean(load) - load)

    bias = jax.lax.fori_loop(0, BALANCE_STEPS, step, p["moe"]["router_bias"])
    return {**p, "moe": {**p["moe"], "router_bias": bias}}


def balanced_expert_bias(model: dict, params, ids):
    """``params`` with every expert layer's ``router_bias`` (the prediction
    module's too) moved from its initial value by `BALANCE_STEPS` steps of
    the LFM2 family's balancing rule on the sequence ``ids``, layer after
    layer (a layer sees its input as the balanced layers before it give
    it), then held fixed: what a trained router is, every expert near ``T *
    k / experts`` tokens. With 8 of 512 experts held, the initial bias alone
    makes the held share of a step's rows, and the step time, follow the
    seed (ROADMAP B2d)."""
    params = dict(params)
    eps = model["rms_norm_eps"]
    # a forward pass only: every KDA head at once
    heads = model["num_attention_heads"]
    x = params["wte"]["embedding"][ids]
    for i, (mixer, ffn) in enumerate(_kinds(model)):
        p = params[f"h_{i}"]
        if ffn == "expert":
            h = reference_mixer(model, x, p, mixer, heads)
            p = _balanced(model, p, _rms_norm(h, p["ln_2"]["scale"], eps))
            params[f"h_{i}"] = p
        x = reference_block(model, x, p, mixer, ffn, heads)
    if model["num_nextn_predict_layers"]:
        p = params["mtp_block"]
        h = reference_mixer(model, _mtp_input(model, params, x, ids), p,
                            "mla")
        params["mtp_block"] = _balanced(
            model, p, _rms_norm(h, p["ln_2"]["scale"], eps))
    return params


def reference_hidden(model: dict, num_layers: int | None = None):
    """``(params, ids) -> (h [B, S, H], h_mtp [B, S-1, H] or None)``, each
    through its final norm, before the head: the blocks (each recomputed in
    the backward pass), and the prediction module on positions ``0..S-2``
    (one more MLA expert block and its own final norm)."""
    model = at_depth(model, num_layers)
    eps = model["rms_norm_eps"]

    def block(mixer, ffn):
        return jax.checkpoint(lambda x, p: reference_block(model, x, p, mixer,
                                                           ffn))

    def hidden(params, ids):
        x = params["wte"]["embedding"][ids]
        for i, (mixer, ffn) in enumerate(_kinds(model)):
            x = block(mixer, ffn)(x, params[f"h_{i}"])
        main = _rms_norm(x, params["ln_f"]["scale"], eps)
        if not model["num_nextn_predict_layers"]:
            return main, None
        h = block("mla", "expert")(_mtp_input(model, params, x, ids),
                                   params["mtp_block"])
        return main, _rms_norm(h, params["ln_mtp_f"]["scale"], eps)

    return hidden


def reference_logits(model: dict, num_layers: int | None = None):
    """``(params, ids) -> (logits [B, S, V], mtp_logits [B, S-1, V] or
    None)``: `reference_hidden` through the head."""
    hidden_of = reference_hidden(model, num_layers)

    def logits(params, ids):
        head = params["lm_head"]["kernel"]
        main, mtp = hidden_of(params, ids)
        return main @ head, None if mtp is None else mtp @ head

    return logits


def _cross_entropy(h, head, labels, block: int = 1024):
    """The mean over positions of `plain.cross_entropy` of ``h @ head``, a
    block of positions at a time (a `lax.map`, each block recomputed in the
    backward pass; the last padded and the padding left out): the f32 ``[S,
    vocab]`` logits never exist whole."""
    h, labels = h.reshape(-1, h.shape[-1]), labels.reshape(-1)
    rows = h.shape[0]
    blocks = -(-rows // block)
    h = jnp.pad(h, ((0, blocks * block - rows), (0, 0)))
    labels = jnp.pad(labels, (0, blocks * block - rows))

    @jax.checkpoint
    def one(args):
        hb, lb = args
        return plain.cross_entropy(hb @ head, lb)

    nll = jax.lax.map(one, (h.reshape(blocks, block, -1),
                            labels.reshape(blocks, block)))
    return jnp.sum(nll.reshape(-1)[:rows]) / rows


def reference_loss(model: dict, num_layers: int | None = None):
    """``loss(params, batch)``: ``L_main + mtp_loss_weight * L_mtp``,
    float32, straightforward `jax.numpy`; the module docstring lists the
    departures from the source."""
    model = at_depth(model, num_layers)
    hidden_of = reference_hidden(model)

    def loss(params, batch):
        ids = batch["input_ids"]
        head = params["lm_head"]["kernel"]
        main, mtp = hidden_of(params, ids)
        total = _cross_entropy(main[:, :-1], head, ids[:, 1:])
        if mtp is not None:
            total = total + model["mtp_loss_weight"] * _cross_entropy(
                mtp[:, :-1], head, ids[:, 2:])
        return total

    return loss
