"""Granite-4.0-H family (``granite_hybrid``): the program's model and loss,
the batch from the seed, the model-FLOPs functions, the scan's work, and a
plain reference of the same mathematics.

Only the program's public API is used (`models.GraniteHybridLmHeadModel`,
`models.granite_hybrid_lm_loss`, `models.GraniteHybridConfig`); the
reference uses none of it: plain `jax.numpy` over the parameter tree the
model initialises, written from the source's description (`transformers`'
``GraniteMoeHybrid*`` classes, whose Mamba-2 mixer is Bamba's). The
state-space layer is NOT computed by chunks there but by its one-equation
form, a head at a time; the convolution is an explicit sum over its shifts;
K and V are repeated for their groups; no kernel.

The ``model`` section of the configuration file keeps the published key
names. This chip's share (the file's ``reduced``): ``num_hidden_layers``
blocks, the ``layer_types`` listed there, of ``num_hidden_layers_published``;
``vocab_size`` ids of ``vocab_size_published``. The reference check's
shallower model (the traffic file's ``reference.layers``) takes its blocks
from ``reference_layer_types``, so that it holds both kinds of layer.

Departures of the reference from the source, all of form and none of value:
``time_step_limit`` is left at the source's default (0, inf), so ``dt`` is
not clamped; the scan is the one-equation sum and not the source's chunked
or sequential kernels; the attention's softmax runs a block of query rows at
a time; the SwiGLU's gate and up matrices are two leaves (the source's
``input_linear`` holds them side by side); the convolution's taps lie
``[tap, channel]`` (the source: ``conv1d.weight [channel, 1, tap]``); the
gated norm has no groups (the source's ``RMSNormGated`` normalises all of
its channels; with ``mamba_n_groups`` 1 the Mamba-2 reference's grouped norm
is the same).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import plain


def _kinds(model: dict, num_layers: int | None = None) -> list:
    """The mixers of the blocks run: this chip's ``layer_types``, or, for a
    shallower reference model, ``reference_layer_types``."""
    if num_layers is None or num_layers == len(model["layer_types"]):
        return list(model["layer_types"])
    kinds = list(model["reference_layer_types"])
    if len(kinds) != num_layers:
        raise ValueError(
            f"reference_layer_types names {len(kinds)} layers, the "
            f"reference check asks for {num_layers}")
    return kinds


def model_config(model: dict, dtype, num_layers: int | None = None,
                 dropout: bool = True):
    """The program's `GraniteHybridConfig` from the keys of the config file.
    The family has no dropout; ``dropout`` is accepted for the harness's
    call."""
    from dear_pytorch_tpu import models

    del dropout
    return models.GraniteHybridConfig(
        vocab_size=model["vocab_size"],
        hidden_size=model["hidden_size"],
        layer_types=tuple(_kinds(model, num_layers)),
        mamba_n_heads=model["mamba_n_heads"],
        mamba_d_head=model["mamba_d_head"],
        mamba_d_state=model["mamba_d_state"],
        mamba_n_groups=model["mamba_n_groups"],
        mamba_d_conv=model["mamba_d_conv"],
        mamba_chunk_size=model["mamba_chunk_size"],
        mamba_expand=model["mamba_expand"],
        mamba_conv_bias=model["mamba_conv_bias"],
        mamba_proj_bias=model["mamba_proj_bias"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        attention_bias=model["attention_bias"],
        shared_intermediate_size=model["shared_intermediate_size"],
        embedding_multiplier=model["embedding_multiplier"],
        residual_multiplier=model["residual_multiplier"],
        attention_multiplier=model["attention_multiplier"],
        logits_scaling=model["logits_scaling"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        remat=model["remat"],
        dtype=dtype,
    )


def make_loss(cfg, with_rng: bool):
    """(init_fn, loss_fn) through the program's model; ``loss_fn(params,
    batch)`` is the loss of `benchmarks/glm.py` for this family, the weights
    the model's own initialisation from the key."""
    from dear_pytorch_tpu import models

    if with_rng:
        raise ValueError("the family has no dropout: dropout_seed is null")
    model = models.GraniteHybridLmHeadModel(cfg)

    def init_fn(key, seq_len: int):
        # no parameter's shape depends on the sequence (no positions at all)
        del seq_len
        return model.init({"params": key},
                          jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"])
        return models.granite_hybrid_lm_loss(logits, batch["input_ids"])

    return init_fn, loss_fn


def make_batch(model: dict, key, batch_size: int, seq_len: int) -> dict:
    """Uniform random token ids drawn from the vocabulary slice held here;
    the targets come from shifting."""
    return {"input_ids": jax.random.randint(
        key, (batch_size, seq_len), 0, model["vocab_size"], jnp.int32)}


def batch_shapes(model: dict, batch_size: int, seq_len: int) -> dict:
    return {"input_ids": ((batch_size, seq_len), jnp.int32)}


def tokens_per_step(batch_size: int, seq_len: int) -> int:
    return batch_size * seq_len


# -- work --------------------------------------------------------------------

def _mamba_widths(model: dict) -> tuple:
    """(inner, conv channels): the scan's ``x`` is ``expand`` x hidden =
    heads x head width; the convolution runs over ``x``, ``B`` and ``C``."""
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return inner, inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def matmul_params_per_token(model: dict) -> dict:
    """Matmul parameters one token passes through, by part (taps, biases,
    ``A_log``, ``D`` and norms are no matmul and count nothing)."""
    h = model["hidden_size"]
    inner, conv_dim = _mamba_widths(model)
    q_width = model["num_attention_heads"] * model["head_dim"]
    kv_width = model["num_key_value_heads"] * model["head_dim"]
    return {
        "mamba": h * (inner + conv_dim + model["mamba_n_heads"]) + inner * h,
        "attention": 2 * h * q_width + 2 * h * kv_width,
        "mlp": 3 * h * model["shared_intermediate_size"],
        "head": model["vocab_size"] * h,
    }


def _scan_flops_per_token_forward(model: dict) -> int:
    """Matmul FLOPs a token of one state-space layer's scan going forward,
    by chunks of ``mamba_chunk_size`` ``Q`` (the form the source trains in):
    ``C B^T`` of a chunk ``2 Q g n``, the decayed scores against ``x`` ``2 Q
    h p``, the chunk's state ``2 h p n`` and its read by ``C`` ``2 h p n``.
    The whole ``[Q, Q]`` tile counts (a 128-square tile is one MXU pass);
    the decays, the gate and the skip are elementwise and count nothing."""
    q, g = model["mamba_chunk_size"], model["mamba_n_groups"]
    n, (hp, _) = model["mamba_d_state"], _mamba_widths(model)
    return 2 * q * g * n + 2 * q * hp + 4 * hp * n


def ssd_scan_flops(model: dict, tokens: int) -> float:
    """FLOPs a step of the state-space layers' scans, forward + backward
    (every matmul once going forward and twice coming back; the backward
    pass's recomputation is time and no FLOPs here), whatever implements
    the scan: a function of the model and the token count only."""
    return 3.0 * _kinds(model).count("mamba") * tokens \
        * _scan_flops_per_token_forward(model)


def ssd_scan_bytes(model: dict, tokens: int) -> float:
    """HBM bytes a step the scans cannot avoid, forward + backward: ``x``,
    ``dt``, ``B``, ``C`` read and ``y`` written going forward; those and
    ``dy`` read and ``dx``, ``ddt``, ``dB``, ``dC`` written coming back.
    Two bytes an element (the compute dtype), ``dt`` and ``ddt`` four."""
    x, _ = _mamba_widths(model)
    bc = 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    dt = 4 * model["mamba_n_heads"]
    forward = 2 * (x + bc + x) + dt
    backward = 2 * (x + bc + x) + dt + 2 * (x + bc) + dt
    return float(_kinds(model).count("mamba") * tokens
                 * (forward + backward))


def flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward, no recompute: 6 per
    matmul parameter (`matmul_params_per_token`), ``12 * S * heads *
    head_dim`` an attention layer for QK^T and PV (the full square, the
    convention of the other families and of `kernel_flops_util_pct`) and the
    scan's matmuls (`ssd_scan_flops`). This configuration at S=4096: 5.001
    GFLOP/token, 20.5 TFLOP a step."""
    p = matmul_params_per_token(model)
    kinds = _kinds(model)
    attention = kinds.count("attention")
    params = (kinds.count("mamba") * p["mamba"] + attention * p["attention"]
              + len(kinds) * p["mlp"] + p["head"])
    square = (12 * seq_len * model["num_attention_heads"]
              * model["head_dim"])
    return float(6 * params + attention * square + ssd_scan_flops(model, 1))


def attention_core_flops(model: dict, batch: int, seq: int) -> float:
    """FLOPs a step of the attention layers' cores over the CAUSAL TRIANGLE,
    forward + backward: ``6 * B * H * S^2 * D`` a layer (two matmuls going
    forward on the square, twice that coming back, half of it under the
    mask). Recomputation (the backward kernel's of the scores, the block's
    of the forward kernel) is time and no FLOPs here."""
    return (6.0 * _kinds(model).count("attention") * batch
            * model["num_attention_heads"] * seq * seq * model["head_dim"])


def initial_loss(model: dict) -> float:
    """Loss of a freshly initialised model. The tied head's logits are the
    unit-RMS output of the final RMSNorm against ``hidden_size`` embedding
    weights of N(0, initializer_range^2), over ``logits_scaling``: N(0, var)
    with ``var = hidden * range^2 / scaling^2`` over the vocabulary slice,
    whose expected cross-entropy is ``ln(vocab) + var / 2``: 10.1301 +
    0.0064 = 10.1365 here. (The input token's own embedding, 12 x its row,
    stays in the residual stream and lifts that one id's logit by about
    0.8; the target is the next token, drawn independently, so the loss
    does not see it.)"""
    var = (model["hidden_size"] * model["initializer_range"] ** 2
           / model["logits_scaling"] ** 2)
    return math.log(model["vocab_size"]) + var / 2


# -- plain reference ---------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _swiglu(y, p):
    return (jax.nn.silu(y @ p["mlp_gate"]["kernel"])
            * (y @ p["mlp_up"]["kernel"])) @ p["mlp_down"]["kernel"]


def _causal_attention(q, k, v, scale: float, block: int = 512):
    """``softmax(scale * q k^T) v`` under the causal mask, one block of
    query rows at a time (a `lax.map` over the blocks, each recomputed in
    the backward pass): the f32 ``[heads, S, S]`` scores never exist whole
    (2.1 GB at S=4096). The one way this differs in form from a textbook
    forward pass."""
    seq = q.shape[1]
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


def reference_scan(x, dt, a, b, c):
    """``y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r a) (c_t . b_s) dt_s x_s``:
    the recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``, ``y_t =
    S_t c_t`` unrolled into one sum over the past, a head at a time. ``x [B,
    S, h, p]``, ``dt [B, S, h]``, ``a [h]``, ``b`` and ``c`` ``[B, S, g, n]``
    (head ``i`` reads group ``i // (h / g)``). A head's ``[S, S]`` matrix of
    decays times ``c . b`` exists whole (67 MB in f32 at S=4096): the heads
    run one after another (`lax.map`), each recomputed in the backward pass,
    so one such matrix is live at a time. No chunks, no carried state."""
    heads, per = x.shape[2], x.shape[2] // b.shape[2]
    seq = x.shape[1]
    past = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]

    @jax.checkpoint
    def one_head(head):
        x_h, dt_h = x[:, :, head], dt[:, :, head]            # [B,S,p] [B,S]
        b_h, c_h = b[:, :, head // per], c[:, :, head // per]
        total = jnp.cumsum(dt_h * a[head], axis=1)           # sum_{r<=t}
        decay = jnp.exp(jnp.where(
            past, total[:, :, None] - total[:, None, :], -jnp.inf))
        weights = decay * jnp.einsum("btn,bsn->bts", c_h, b_h)
        return jnp.einsum("bts,bsp->btp", weights, dt_h[..., None] * x_h)

    y = jax.lax.map(one_head, jnp.arange(heads))             # [h, B, S, p]
    return jnp.moveaxis(y, 0, 2)


def reference_mamba(model: dict, y, p):
    """The Mamba-2 mixer from the normed block input ``y`` ``[B, S, H]``:
    ``[z | xBC | dt] = W_in y``; a depthwise causal convolution of ``xBC`` as
    an explicit sum over its ``mamba_d_conv`` shifts (tap ``j`` reads
    ``mamba_d_conv - 1 - j`` positions back, zeros before the sequence), its
    bias, silu; ``dt = softplus(dt + dt_bias)`` (not clamped), ``A =
    -exp(A_log)``; the scan (`reference_scan`) plus ``D x``; the gate
    ``silu(z)`` BEFORE an RMSNorm over all the inner channels; ``W_out``."""
    heads, width = model["mamba_n_heads"], model["mamba_d_head"]
    groups, state = model["mamba_n_groups"], model["mamba_d_state"]
    taps = model["mamba_d_conv"]
    inner, conv_dim = _mamba_widths(model)
    batch, seq = y.shape[:2]
    proj = y @ p["in_proj"]["kernel"]
    z, xbc = proj[..., :inner], proj[..., inner:inner + conv_dim]
    dt = jax.nn.softplus(proj[..., inner + conv_dim:] + p["dt_bias"])
    conv = jnp.zeros_like(xbc)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(xbc[:, :back]), xbc[:, :seq - back]], axis=1)
        conv = conv + p["conv_kernel"][j] * shifted
    if model["mamba_conv_bias"]:
        conv = conv + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(batch, seq, heads, width)
    b = xbc[..., inner:inner + groups * state].reshape(batch, seq, groups,
                                                       state)
    c = xbc[..., inner + groups * state:].reshape(batch, seq, groups, state)
    scanned = reference_scan(x, dt, -jnp.exp(p["A_log"]), b, c)
    scanned = scanned + p["D"][:, None] * x
    gated = scanned.reshape(batch, seq, inner) * jax.nn.silu(z)
    return _rms_norm(gated, p["gate_norm"], model["rms_norm_eps"]) \
        @ p["out_proj"]["kernel"]


def reference_attention(model: dict, y, p):
    """Grouped-query attention from the normed block input ``y``: no bias,
    no positions (no rotary), the softmax scale ``attention_multiplier``
    itself, K/V head ``j`` repeated for Q heads ``j * group .. (j + 1) *
    group - 1``."""
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    q = jnp.einsum("bsh,hnd->bsnd", y, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", y, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", y, p["v_proj"]["kernel"])
    ctx = _causal_attention(q, jnp.repeat(k, group, axis=2),
                            jnp.repeat(v, group, axis=2),
                            model["attention_multiplier"])
    return jnp.einsum("bsnd,ndh->bsh", ctx, p["output"]["kernel"])


def reference_block(model: dict, x, p, mixer: str):
    """``h = x + m * Mixer(RMSNorm(x))``; ``h + m * SwiGLU(RMSNorm(h))``
    with ``m`` the residual multiplier."""
    eps, m = model["rms_norm_eps"], model["residual_multiplier"]
    y = _rms_norm(x, p["ln_1"]["scale"], eps)
    if mixer == "mamba":
        x = x + m * reference_mamba(model, y, p["mamba"])
    else:
        x = x + m * reference_attention(model, y, p)
    return x + m * _swiglu(_rms_norm(x, p["ln_2"]["scale"], eps), p)


def reference_logits(model: dict, num_layers: int | None = None):
    """``(params, ids) -> logits [B, S, V]``: the embedding times its
    multiplier, the blocks, the final norm and the head tied to the
    embedding, over ``logits_scaling``, over the vocabulary slice."""

    def logits(params, ids):
        wte = params["wte"]["embedding"]
        x = model["embedding_multiplier"] * wte[ids]
        for i, mixer in enumerate(_kinds(model, num_layers)):
            x = reference_block(model, x, params[f"h_{i}"], mixer)
        x = _rms_norm(x, params["ln_f"]["scale"], model["rms_norm_eps"])
        return x @ wte.T / model["logits_scaling"]

    return logits


def reference_loss(model: dict, num_layers: int | None = None):
    """``loss(params, batch)``: next-token cross-entropy, float32,
    straightforward `jax.numpy`; the module docstring lists the departures
    from the source."""
    logits_of = reference_logits(model, num_layers)

    def loss(params, batch):
        ids = batch["input_ids"]
        return jnp.mean(plain.cross_entropy(logits_of(params, ids)[:, :-1],
                                            ids[:, 1:]))

    return loss
