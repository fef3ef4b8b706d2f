"""The dense Mamba-2 / attention hybrid (`models/granite_hybrid.py`) against
the benchmark's plain reference (`perfbench/families/granite_hybrid.py`:
plain `jax.numpy`, the state-space layer as the one-equation sum, nothing of
the program's), at small sizes, float32, seeded random weights."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import granite_hybrid
from perfbench import cell as cells
from perfbench import plain

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "granite_hybrid.py")

#: the published keys at test sizes: the cell's ten layers, two chunks of 8
#: in a sequence of 16; the softmax scale 1/4 where 1/sqrt(8) would be usual
TINY = dict(
    vocab_size=96, vocab_size_published=384, hidden_size=32,
    layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    reference_layer_types=["mamba", "attention"],
    num_hidden_layers=10, num_hidden_layers_published=40,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=8, mamba_n_groups=1,
    mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2, mamba_conv_bias=True,
    mamba_proj_bias=False, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, attention_bias=False, shared_intermediate_size=48,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.25, logits_scaling=8, rms_norm_eps=1e-5,
    initializer_range=0.02, remat=False)
B, S = 2, 16


def _setup(model, seed=0):
    cfg = FAM.model_config(model, jnp.float32)
    init_fn, loss_fn = FAM.make_loss(cfg, with_rng=False)
    params = init_fn(jax.random.PRNGKey(seed), S)
    # weights large enough that every path moves the result: matrices x5,
    # the convolution's bias (a vector) x25
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (25 if "conv_bias" in jax.tree_util.keystr(path)
                         else 5 if x.ndim > 1 else 1) * x, params)
    batch = FAM.make_batch(model, jax.random.PRNGKey(seed + 1), B, S)
    return cfg, params, batch, loss_fn


def _close(a, b, rel=2e-5):
    """Equal to ``rel`` of the reference's largest entry. Both sides are
    float32 at matmul precision "highest", so only summation order differs
    (1e-6 relative a matmul; the chunked scan's decays are exponentials of
    differences of running sums inside a chunk where the reference's run
    over the whole sequence); bf16 compute moves every tensor here by 4e-3
    of its size and more, two hundred times the limit."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * (np.abs(b).max()
                                                        + 1e-12))


def _far(a, b, rel=1e-3):
    """Further apart than fifty times `_close`'s limit."""
    a, b = np.asarray(a), np.asarray(b)
    assert np.abs(a - b).max() > rel * np.abs(b).max()


def _layers(kinds, **kw):
    return dict(layer_types=list(kinds), num_hidden_layers=len(kinds), **kw)


DEPTHS = {
    "mamba": _layers(["mamba"]),
    "attention": _layers(["attention"]),
    "reference_depth_mamba_attention": _layers(["mamba", "attention"]),
    "two_groups": _layers(["mamba", "attention"], mamba_n_groups=2),
    "one_chunk": _layers(["mamba"], mamba_chunk_size=16),
    "ten_layer_period": {},
    "ten_layer_period_remat": dict(remat=True),
}


@pytest.mark.parametrize("depth", DEPTHS)
def test_logits_loss_and_every_gradient_leaf_equal_the_reference(depth):
    model = {**TINY, **DEPTHS[depth]}
    cfg, params, batch, loss_fn = _setup(model)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(
            lambda p, i: models.GraniteHybridLmHeadModel(cfg).apply(
                {"params": p}, i))(params, ids)
        want = jax.jit(FAM.reference_logits(model))(params, ids)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            FAM.reference_loss(model)))(params, batch)
    assert logits.shape == (B, S, 96) and logits.dtype == jnp.float32
    _close(logits, want)
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert (jax.tree.structure(grads) == jax.tree.structure(ref_grads)
            == jax.tree.structure(params))
    jax.tree.map(_close, grads, ref_grads)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.asarray(g).any(), jax.tree_util.keystr(path)


def test_the_reference_depth_picks_one_mamba_and_the_attention_block():
    """The harness's check asks for two layers: the family gives it
    ``reference_layer_types``, not the first two (both ``mamba``)."""
    cfg = FAM.model_config(TINY, jnp.float32, num_layers=2)
    assert cfg.layer_types == ("mamba", "attention")
    assert FAM.model_config(TINY, jnp.float32).layer_types == tuple(
        TINY["layer_types"])
    with pytest.raises(ValueError, match="reference_layer_types names 2"):
        FAM.model_config(TINY, jnp.float32, num_layers=3)
    model = {**TINY, **DEPTHS["reference_depth_mamba_attention"]}
    _, params, batch, _ = _setup(model)
    with jax.default_matmul_precision("highest"):
        assert float(FAM.reference_loss(TINY, 2)(params, batch)) == float(
            FAM.reference_loss(model)(params, batch))


def test_the_tiny_preset_equals_the_reference():
    cfg = models.GRANITE_HYBRID_TINY
    model = {**TINY, "layer_types": list(cfg.layer_types),
             "attention_multiplier": cfg.attention_multiplier}
    net = models.GraniteHybridLmHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 96)
    params = net.init(jax.random.PRNGKey(0), ids)["params"]
    params = jax.tree.map(lambda x: 5 * x if x.ndim > 1 else x, params)
    with jax.default_matmul_precision("highest"):
        _close(net.apply({"params": params}, ids),
               FAM.reference_logits(model)(params, ids))
    assert set(cfg.layer_types) == {"mamba", "attention"}


def test_bf16_compute_would_fail_the_comparison():
    """The tolerance tells precisions apart: the program in bfloat16 is
    further from the float32 reference than `_close` allows."""
    _, params, batch, _ = _setup(TINY)
    low = FAM.model_config(TINY, jnp.bfloat16)
    got = models.GraniteHybridLmHeadModel(low).apply({"params": params},
                                                     batch["input_ids"])
    want = FAM.reference_logits(TINY)(params, batch["input_ids"])
    with pytest.raises(AssertionError):
        _close(got, want)


# -- one case a detail: it moves the result, in the program and in the
# -- reference alike, so dropping it from either fails the comparison above

def _program_logits(model, params, ids):
    cfg = FAM.model_config(model, jnp.float32)
    return models.GraniteHybridLmHeadModel(cfg).apply({"params": params}, ids)


DROPPED = {
    # the multiplier as a model without it would have it
    "embedding_multiplier": dict(embedding_multiplier=1),
    "residual_multiplier": dict(residual_multiplier=1),
    "attention_multiplier_is_the_softmax_scale":
        dict(attention_multiplier=1 / np.sqrt(8)),
    "logits_scaling": dict(logits_scaling=1),
    "mamba_conv_bias": dict(mamba_conv_bias=False),
}


@pytest.mark.parametrize("detail", DROPPED)
def test_dropping_a_detail_from_either_side_fails(detail):
    model = {**TINY, **DEPTHS["reference_depth_mamba_attention"]}
    without = {**model, **DROPPED[detail]}
    _, params, batch, _ = _setup(model)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        program = _program_logits(model, params, ids)
        reference = FAM.reference_logits(model)(params, ids)
        if detail == "mamba_conv_bias":
            # the program has no switch to leave a trained bias out: zero it
            zeroed = jax.tree_util.tree_map_with_path(
                lambda path, x: 0 * x if "conv_bias" in
                jax.tree_util.keystr(path) else x, params)
            program_without = _program_logits(model, zeroed, ids)
        else:
            program_without = _program_logits(without, params, ids)
        reference_without = FAM.reference_logits(without)(params, ids)
    _close(program, reference)
    _close(program_without, reference_without)
    _far(program_without, reference)
    _far(program, reference_without)
    with pytest.raises(AssertionError):
        _close(program_without, reference)


def _mixer_by_hand(p, y, order="gate_then_norm"):
    """One Mamba layer as a position-by-position float64 loop written from
    the equations: the fused projection split z | xBC | dt, four causal taps
    with bias and silu, softplus(dt + dt_bias), A = -exp(A_log), the
    recurrence, the D skip, the gate and the norm in ``order``."""
    p = jax.tree.map(lambda t: np.asarray(t, np.float64), p)
    y = np.asarray(y, np.float64)
    silu = lambda t: t / (1 + np.exp(-t))            # noqa: E731
    proj = y @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[..., :64], proj[..., 64:144], proj[..., 144:]
    conv = np.zeros_like(xbc)
    for t in range(S):
        for j in range(4):
            if t - 3 + j >= 0:
                conv[:, t] += p["conv_kernel"][j] * xbc[:, t - 3 + j]
    xbc = silu(conv + p["conv_bias"])
    x = xbc[0, :, :64].reshape(S, 8, 8)
    bm, cm = xbc[0, :, 64:72], xbc[0, :, 72:]
    dt = np.log1p(np.exp(dt[0] + p["dt_bias"]))
    a = -np.exp(p["A_log"])
    out = np.zeros((S, 8, 8))
    for h in range(8):
        state = np.zeros((8, 8))
        for t in range(S):
            state = (np.exp(dt[t, h] * a[h]) * state
                     + dt[t, h] * np.outer(x[t, h], bm[t]))
            out[t, h] = state @ cm[t] + p["D"][h] * x[t, h]
    out = out.reshape(S, 64)

    def rms(t):
        return t / np.sqrt((t ** 2).mean(-1, keepdims=True) + 1e-5)

    if order == "gate_then_norm":
        normed = rms(out * silu(z[0])) * p["gate_norm"]
    else:
        normed = rms(out) * p["gate_norm"] * silu(z[0])
    return normed @ p["out_proj"]["kernel"]


def test_the_mixer_by_hand_and_the_gate_before_the_norm():
    """Program and reference equal the float64 loop with ``norm(y *
    silu(z))``, and neither equals it with ``norm(y) * silu(z)``."""
    model = {**TINY, **DEPTHS["mamba"]}
    cfg, params, _, _ = _setup(model)
    p = params["h_0"]["mamba"]
    p = {**p, "gate_norm": 1.0 + jnp.arange(64.0) / 64}
    y = jax.random.normal(jax.random.PRNGKey(2), (1, S, 32))
    want = _mixer_by_hand(p, y)
    wrong = _mixer_by_hand(p, y, order="norm_then_gate")
    with jax.default_matmul_precision("highest"):
        got = granite_hybrid.Mamba2Mixer(cfg).apply({"params": p}, y)
        ref = FAM.reference_mamba(model, y, p)
    _close(got[0], want)
    _close(ref[0], want)
    _far(got[0], wrong)
    _far(ref[0], wrong)
    assert np.abs(want).max() > 1e-2


def test_gated_rms_norm_gates_first_over_all_channels():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    scale = 1.0 + jnp.arange(32.0) / 16
    got = granite_hybrid.gated_rms_norm(y, z, scale, 1e-5)
    g = np.asarray(y * jax.nn.silu(z), np.float64)
    want = g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * scale, atol=1e-5)
    # ... not a norm over runs of the width
    runs = g.reshape(2, 5, 4, 8)
    runs = runs / np.sqrt((runs ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(runs.reshape(2, 5, 32) * scale - np.asarray(got)).max() \
        > 1e-2
    assert granite_hybrid.gated_rms_norm(
        y.astype(jnp.bfloat16), z, scale, 1e-5).dtype == jnp.bfloat16


@pytest.mark.parametrize("side", ["program", "reference"])
def test_attention_has_no_positions(side):
    """An attention layer without positions sees the past as a set: the
    last position's logits do not change when the earlier tokens are
    permuted (with rotary or learned positions they would), and they do
    change when one of them is replaced."""
    model = {**TINY, **DEPTHS["attention"]}
    _, params, batch, _ = _setup(model)
    ids = batch["input_ids"]
    perm = np.r_[np.random.default_rng(0).permutation(S - 1), S - 1]
    logits = (_program_logits if side == "program" else
              lambda m, p, i: FAM.reference_logits(m)(p, i))
    with jax.default_matmul_precision("highest"):
        a = logits(model, params, ids)
        b = logits(model, params, ids[:, perm])
        c = logits(model, params, ids.at[:, 3].set((ids[:, 3] + 1) % 96))
    _close(a[:, -1], b[:, -1])
    _far(a[:, 1:-1], b[:, 1:-1])
    _far(a[:, -1], c[:, -1])


def test_attention_groups_its_heads_and_scales_by_the_multiplier(
        monkeypatch):
    """The core sees q ``[B, S, 4, 8]`` already carrying ``multiplier *
    sqrt(d)`` (the cores divide by ``sqrt(d)``) and k, v ``[B, S, 2, 8]``,
    bare projections of the normed input; K/V head j serves Q heads 2j,
    2j+1."""
    model = {**TINY, **DEPTHS["attention"]}
    cfg, params, batch, _ = _setup(model)
    seen = {}
    real = granite_hybrid.causal_attention

    def recording_core(q, k, v, mask, **kw):
        ctx = real(q, k, v, mask, **kw)
        seen.update(q=q, k=k, v=v, ctx=ctx, mask=mask)
        return ctx

    monkeypatch.setattr(granite_hybrid, "causal_attention", recording_core)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        models.GraniteHybridLmHeadModel(cfg).apply({"params": params}, ids)
        p0 = params["h_0"]
        y = FAM._rms_norm(12 * params["wte"]["embedding"][ids],
                          p0["ln_1"]["scale"], 1e-5)
        raw = {n: jnp.einsum("bsh,hnd->bsnd", y, p0[f"{n}_proj"]["kernel"])
               for n in "qkv"}
    assert seen["mask"] is None
    q, k, v, ctx = (np.asarray(seen[n]) for n in ("q", "k", "v", "ctx"))
    assert q.shape == (B, S, 4, 8) and k.shape == v.shape == (B, S, 2, 8)
    _close(q, raw["q"] * 0.25 * np.sqrt(8))
    _close(k, raw["k"])
    _close(v, raw["v"])
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0,
                       -jnp.inf)[None, None]
    _close(ctx, plain.attention(q, np.repeat(k, 2, axis=2),
                                np.repeat(v, 2, axis=2), causal))
    tiled = plain.attention(q, np.tile(k, (1, 1, 2, 1)),
                            np.tile(v, (1, 1, 2, 1)), causal)
    _far(ctx, tiled)
    assert set(p0) == {"ln_1", "ln_2", "q_proj", "k_proj", "v_proj",
                       "output", "mlp_gate", "mlp_up", "mlp_down"}


@pytest.mark.parametrize("t", [5, 8, 11])
def test_the_mamba_layer_is_causal(t):
    """Changing token t moves no logit before t and moves the one at t,
    across the chunk boundary at 8 too, and the state carries it on."""
    model = {**TINY, **DEPTHS["mamba"]}
    cfg, params, batch, _ = _setup(model)
    net = models.GraniteHybridLmHeadModel(cfg)
    ids = batch["input_ids"]
    other = ids.at[:, t].set((ids[:, t] + 1) % 96)
    la = np.asarray(net.apply({"params": params}, ids))
    lb = np.asarray(net.apply({"params": params}, other))
    np.testing.assert_allclose(la[:, :t], lb[:, :t], atol=1e-6)
    assert np.abs(la[:, t] - lb[:, t]).max() > 1e-4
    assert np.abs(la[:, -1] - lb[:, -1]).max() > 1e-7


def test_the_initialisation_is_the_mamba2_references():
    cfg = FAM.model_config({**TINY, "hidden_size": 256, "mamba_n_heads": 64,
                            **DEPTHS["mamba"]}, jnp.float32)
    p = models.GraniteHybridLmHeadModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    m = jax.tree.map(np.asarray, p["h_0"]["mamba"])
    assert all(m[k].dtype == np.float32 for k in
               ("A_log", "D", "dt_bias", "conv_kernel", "conv_bias",
                "gate_norm"))
    a = np.exp(m["A_log"])
    assert a.min() >= 1 and a.max() <= 16 and a.std() > 2
    dt = np.log1p(np.exp(m["dt_bias"]))          # softplus undoes the start
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.std(np.log(dt)) > 0.8              # log-uniform, not uniform
    assert (m["D"] == 1).all() and (m["gate_norm"] == 1).all()
    assert m["conv_kernel"].shape == (4, 512 + 16)
    assert m["conv_bias"].any()
    for name in ("in_proj", "out_proj"):
        assert np.std(m[name]["kernel"]) == pytest.approx(0.02, rel=0.05)
    assert m["in_proj"]["kernel"].shape == (256, 512 + 528 + 64)
    assert np.std(np.asarray(p["wte"]["embedding"])) == pytest.approx(
        0.02, rel=0.05)


def test_the_first_loss_is_near_the_familys_initial_loss():
    """``ln(vocab) + hidden * range^2 / (2 * scaling^2)``, not ``ln vocab``
    plus the unscaled head's half variance: at hidden 32 the two are too
    close to tell, so the head is read directly: its logits' variance at
    initialisation is ``hidden * range^2 / scaling^2``."""
    model = {**TINY, "hidden_size": 64, "mamba_n_heads": 16,
             "initializer_range": 0.2, **DEPTHS["mamba"]}
    cfg = FAM.model_config(model, jnp.float32)
    init_fn, loss_fn = FAM.make_loss(cfg, False)
    params = init_fn(jax.random.PRNGKey(0), S)
    batch = FAM.make_batch(model, jax.random.PRNGKey(1), 8, 64)
    loss = float(loss_fn(params, batch))
    want = FAM.initial_loss(model)
    assert want == pytest.approx(np.log(96) + 64 * 0.04 / 64 / 2)
    assert loss == pytest.approx(want, abs=0.05)
    # without logits_scaling the variance would be 64x: ln 96 + 1.28
    assert abs(loss - (np.log(96) + 64 * 0.04 / 2)) > 0.5


def test_registry_and_config_validation():
    assert models.granite_names() == ["granite_4_0_h_micro",
                                      "granite_hybrid_tiny"]
    tiny = models.get_model("granite_hybrid_tiny", dtype=jnp.bfloat16)
    assert isinstance(tiny, models.GraniteHybridLmHeadModel)
    assert tiny.config.dtype == jnp.bfloat16
    cfg = models.GRANITE_4_0_H_MICRO
    assert cfg.num_hidden_layers == 40 and cfg.head_dim == 64
    assert cfg.mamba_inner == 4096 and cfg.conv_dim == 4352
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    with pytest.raises(ValueError, match="'mamba' or 'attention'"):
        models.GraniteHybridConfig(layer_types=("mamba", "conv"))
    with pytest.raises(ValueError, match="'mamba' or 'attention'"):
        models.GraniteHybridConfig(layer_types=())
    with pytest.raises(ValueError, match="groups do not divide"):
        models.GraniteHybridConfig(mamba_n_groups=3)
    with pytest.raises(ValueError, match="mamba_expand x hidden_size"):
        models.GraniteHybridConfig(mamba_n_heads=32)
    with pytest.raises(ValueError, match="no bias path"):
        models.GraniteHybridConfig(mamba_proj_bias=True)
    with pytest.raises(ValueError, match="has its bias"):
        models.GraniteHybridConfig(mamba_conv_bias=False)
    with pytest.raises(KeyError, match="Granite"):
        models.get_model("granite")
