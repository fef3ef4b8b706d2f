"""The short-convolution hybrid (`models/lfm2_moe.py`) against the
benchmark's plain reference (`perfbench/families/lfm2_moe.py`: plain
`jax.numpy`, nothing of the program's) and against plain `jax.numpy`, at
small sizes, float32, seeded random weights."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import lfm2_moe
from dear_pytorch_tpu.parallel.ep import RoutedExperts
from perfbench import cell as cells
from perfbench import plain

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "lfm2_moe.py")
GLM = cells.load_py(ROOT / "perfbench" / "families" / "glm_moe.py")

#: the published keys at test sizes: 16 experts scored, 4 held from 4 on;
#: the cell's five layers (a dense conv layer, then one period)
TINY = dict(
    vocab_size=96, vocab_size_published=384, hidden_size=64, num_layers=5,
    num_hidden_layers=24,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, moe_intermediate_size=48,
    num_experts=4, num_experts_published=16, expert_offset=4,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1.0,
    use_expert_bias=True, conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
    rope_theta=1e6, max_position_embeddings=128000, initializer_range=0.02,
    remat=False)
B, S = 2, 32


def _setup(model, seed=0):
    cfg = FAM.model_config(model, jnp.float32)
    init_fn, loss_fn = FAM.make_loss(cfg, with_rng=False)
    params = init_fn(jax.random.PRNGKey(seed), S)
    # weights large enough that every path moves the result (the taps are
    # [3, H]: a matrix too)
    params = jax.tree.map(lambda x: 5 * x if x.ndim > 1 else x, params)
    batch = FAM.make_batch(model, jax.random.PRNGKey(seed + 1), B, S)
    return cfg, params, batch, loss_fn


def _close(a, b, rel=2e-5):
    """Equal to ``rel`` of the reference's largest entry. Both sides are
    float32 at matmul precision "highest", so only summation order differs
    (1e-6 relative a matmul); bf16 compute moves every tensor here by 4e-3
    of its size and more, two hundred times the limit."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * (np.abs(b).max()
                                                        + 1e-12))


def _kinds(*layer_types, dense=0, **kw):
    return dict(layer_types=list(layer_types), num_layers=len(layer_types),
                num_dense_layers=dense, **kw)


DEPTHS = {
    "conv_dense": _kinds("conv", dense=1),
    "conv_expert": _kinds("conv"),
    "attention_expert": _kinds("full_attention"),
    "attention_dense": _kinds("full_attention", dense=1),
    "five_layer_stack": {},
    "five_layer_stack_remat": dict(remat=True),
}


@pytest.mark.parametrize("depth", DEPTHS)
def test_logits_loss_and_every_gradient_leaf_equal_the_reference(depth):
    model = {**TINY, **DEPTHS[depth]}
    cfg, params, batch, loss_fn = _setup(model)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, i: models.Lfm2MoeLmHeadModel(cfg).apply(
            {"params": p}, i))(params, ids)
        want = jax.jit(FAM.reference_logits(model, model["num_layers"]))(
            params, ids)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            FAM.reference_loss(model, model["num_layers"])))(params, batch)
    assert logits.shape == (B, S, 96) and logits.dtype == jnp.float32
    _close(logits, want)
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert (jax.tree.structure(grads) == jax.tree.structure(ref_grads)
            == jax.tree.structure(params))
    jax.tree.map(_close, grads, ref_grads)
    # the selection-only bias receives no gradient, every other leaf some
    for name, g in grads.items():
        if "moe" in g:
            assert not np.asarray(g["moe"].pop("router_bias")).any(), name
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.asarray(g).any(), jax.tree_util.keystr(path)


def test_bf16_compute_would_fail_the_comparison():
    """The tolerance tells precisions apart: the program in bfloat16 is
    further from the float32 reference than `_close` allows."""
    cfg, params, batch, _ = _setup(TINY)
    low = FAM.model_config(TINY, jnp.bfloat16)
    got = models.Lfm2MoeLmHeadModel(low).apply({"params": params},
                                               batch["input_ids"])
    want = FAM.reference_logits(TINY, 5)(params, batch["input_ids"])
    with pytest.raises(AssertionError):
        _close(got, want)


# -- the short convolution ----------------------------------------------------

def _conv_inputs(seed=0, h=8):
    gates = jax.random.normal(jax.random.PRNGKey(seed), (2, S, 3 * h))
    taps = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, h))
    return gates, taps


def test_the_filter_is_an_explicit_three_tap_sum_between_two_gates():
    gates, taps = _conv_inputs()
    got = np.asarray(lfm2_moe.short_conv_filter(gates, taps))
    g = np.asarray(gates, np.float64)
    b, c, x = g[..., :8], g[..., 8:16], g[..., 16:]
    u, w = b * x, np.asarray(taps, np.float64)
    want = np.zeros_like(u)
    for t in range(S):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += w[j] * u[:, t - 2 + j]
    np.testing.assert_allclose(got, c * want, atol=1e-5)
    # the newest input takes the LAST tap (the source's conv1d over a
    # left-padded sequence), the one two back the first
    only_last = np.asarray(lfm2_moe.short_conv_filter(
        gates, taps.at[:2].set(0.0)))
    np.testing.assert_allclose(only_last, c * w[2] * u, atol=1e-5)


def test_the_convolution_is_causal():
    """Changing token t moves no output before t, in the filter and in the
    whole conv block, and moves the output at t."""
    gates, taps = _conv_inputs()
    t = 11
    moved = gates.at[:, t].add(1.0)
    a = np.asarray(lfm2_moe.short_conv_filter(gates, taps))
    b = np.asarray(lfm2_moe.short_conv_filter(moved, taps))
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t] - b[:, t]).max() > 1e-3
    # three taps: nothing past t + 2 moves either
    np.testing.assert_array_equal(a[:, t + 3:], b[:, t + 3:])
    model = {**TINY, **DEPTHS["conv_expert"]}
    cfg, params, batch, _ = _setup(model)
    ids = batch["input_ids"]
    other = ids.at[:, t].set((ids[:, t] + 1) % 96)
    net = models.Lfm2MoeLmHeadModel(cfg)
    la = np.asarray(net.apply({"params": params}, ids))
    lb = np.asarray(net.apply({"params": params}, other))
    np.testing.assert_array_equal(la[:, :t], lb[:, :t])
    assert np.abs(la[:, t] - lb[:, t]).max() > 1e-4


# -- grouped-query attention --------------------------------------------------

def test_qk_norm_precedes_the_rotary_and_heads_are_grouped(monkeypatch):
    """The core sees q ``[B, S, 4, 16]`` and k, v ``[B, S, 2, 16]``; q and
    k are rotations of per-head RMS-normed projections (norm first: every
    head of q and k then has the norm's weight's size at every position,
    which a norm after the rotation of un-normed heads would also give, so
    the order is pinned by swapping it and seeing the result change); the
    context is plain attention with K/V head j serving Q heads 2j, 2j+1."""
    model = {**TINY, **DEPTHS["attention_expert"]}
    cfg, params, batch, _ = _setup(model)
    # a weight that is not constant over lanes, so norm and rotation do not
    # commute
    lanes = 1.0 + jnp.arange(16.0) / 8
    params["h_0"]["q_ln"]["scale"] = lanes
    params["h_0"]["k_ln"]["scale"] = lanes[::-1]
    seen = {}
    real = lfm2_moe.causal_attention

    def recording_core(q, k, v, mask, **kw):
        ctx = real(q, k, v, mask, **kw)
        seen.update(q=q, k=k, v=v, ctx=ctx)
        return ctx

    monkeypatch.setattr(lfm2_moe, "causal_attention", recording_core)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        models.Lfm2MoeLmHeadModel(cfg).apply({"params": params}, ids)
    q, k, v, ctx = (np.asarray(seen[n]) for n in ("q", "k", "v", "ctx"))
    assert q.shape == (B, S, 4, 16) and k.shape == v.shape == (B, S, 2, 16)
    p0 = params["h_0"]
    y = FAM._rms_norm(params["wte"]["embedding"][ids], p0["ln_1"], 1e-5)
    with jax.default_matmul_precision("highest"):
        raw_q = jnp.einsum("bsh,hnd->bsnd", y, p0["q_proj"]["kernel"])
        raw_k = jnp.einsum("bsh,hnd->bsnd", y, p0["k_proj"]["kernel"])
    norm_first = FAM._rotary(FAM._rms_norm(raw_q, p0["q_ln"], 1e-5), 1e6)
    norm_last = FAM._rms_norm(FAM._rotary(raw_q, 1e6), p0["q_ln"], 1e-5)
    _close(q, norm_first)
    assert np.abs(np.asarray(norm_first - norm_last)).max() > 1e-2
    _close(k, FAM._rotary(FAM._rms_norm(raw_k, p0["k_ln"], 1e-5), 1e6))
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0,
                       -jnp.inf)[None, None]
    _close(ctx, plain.attention(q, np.repeat(k, 2, axis=2),
                                np.repeat(v, 2, axis=2), causal))
    # ... and not the interleaved assignment (K/V head j to Q heads j, j+2)
    tiled = plain.attention(q, np.tile(k, (1, 1, 2, 1)),
                            np.tile(v, (1, 1, 2, 1)), causal)
    assert np.abs(ctx - np.asarray(tiled)).max() > 1e-3


# -- the expert layer: no shared expert, the source's epsilon ----------------

H, F, WIDTH = 32, 24, 32


def _layer(held, offset, **kw):
    return RoutedExperts(router_width=WIDTH, experts_held=held,
                         expert_offset=offset, top_k=4, mlp_dim=F,
                         norm_topk_eps=1e-6, **kw)


def _reference_model(held, offset):
    return dict(num_experts_per_tok=4, norm_topk_prob=True,
                routed_scaling_factor=1.0, moe_intermediate_size=F,
                num_experts=held, expert_offset=offset)


def test_the_four_shares_add_up_to_the_uncut_32_expert_reference():
    """32 experts over 4 shares of 8: the four shares sum to the uncut
    layer and to the uncut reference. There is no shared expert to count
    once: the shares are all of the FFN."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, H))
    params = _layer(WIDTH, 0).init(jax.random.PRNGKey(4), x)["params"]
    params = {**params, "router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (WIDTH,))}

    def share(off):
        return {**params, "wi": params["wi"][off:off + 8],
                "wo": params["wo"][off:off + 8]}

    parts = [_layer(8, off).apply({"params": share(off)}, x)
             for off in range(0, WIDTH, 8)]
    whole = FAM.reference_routed(_reference_model(WIDTH, 0), x, params)
    _close(sum(parts), whole)
    _close(_layer(WIDTH, 0).apply({"params": params}, x), whole)
    for off, part in zip(range(0, WIDTH, 8), parts):
        assert np.abs(np.asarray(part)).max() > 0
        _close(part, FAM.reference_routed(_reference_model(8, off), x,
                                          share(off)))
    # ... and in the model: the block's FFN is the routed part alone
    model = {**TINY, **DEPTHS["conv_expert"]}
    cfg, params, _, _ = _setup(model)
    assert set(params["h_0"]) == {"ln_1", "conv", "ln_2", "moe"}


def test_norm_topk_eps_is_the_sources_and_its_default_leaves_glm_bit_equal():
    x = jax.random.normal(jax.random.PRNGKey(3), (40, H))
    router = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (H, WIDTH))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (WIDTH,))
    scores = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    idx, w = _layer(8, 0).route(x, router, bias)
    picked = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=2e-6)
    # 1e-6 is visible in float32, 1e-20 is not: the sums differ
    assert float(np.abs(np.asarray(w).sum(-1) - 1).max()) > 1e-7
    # the default is the literal GLM's routing had: bit-equal weights
    glm = RoutedExperts(router_width=WIDTH, experts_held=8, top_k=4,
                        mlp_dim=F, routed_scaling_factor=1.8)
    assert glm.norm_topk_eps == 1e-20
    idx_g, w_g = glm.route(x, router, bias)
    s32 = jax.nn.sigmoid(jnp.dot(x, router,
                                 precision=jax.lax.Precision.HIGHEST))
    p32 = jnp.take_along_axis(s32, idx_g, -1)
    np.testing.assert_array_equal(
        np.asarray(w_g),
        np.asarray(p32 / (jnp.sum(p32, -1, keepdims=True) + 1e-20) * 1.8))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_g))
    _close(GLM.reference_routing(
        dict(num_experts_per_tok=4, norm_topk_prob=True,
             routed_scaling_factor=1.8), x,
        {"router": router, "router_bias": bias})[1], w_g)


def test_the_benchmarks_weights_are_the_models_with_the_bias_balanced():
    """`make_loss`'s ``init_fn``: the model's own initialisation from the key,
    but for every expert layer's ``router_bias``, balanced by the family's
    rule on a calibration sequence drawn from the same key: there every
    expert of the router's 16 takes near ``T * k / 16`` assignments, where
    the initial N(0, 0.02^2) bias leaves the most loaded far above the
    mean."""
    model = {**TINY, "num_experts": 16, "expert_offset": 0}
    cfg = FAM.model_config(model, jnp.float32)
    key, seq = jax.random.PRNGKey(7), 512
    params = jax.jit(FAM.make_loss(cfg, False)[0], static_argnums=1)(key, seq)
    raw = jax.jit(lambda k: models.Lfm2MoeLmHeadModel(cfg).init(
        {"params": k}, jnp.zeros((1, 8), jnp.int32))["params"])(key)
    same = jax.tree_util.tree_map_with_path(
        lambda path, a, b: "router_bias" in jax.tree_util.keystr(path)
        or bool((a == b).all()), params, raw)
    assert all(jax.tree.leaves(same))
    ids = jax.random.randint(jax.random.fold_in(key, 1), (1, seq), 0, 96,
                             jnp.int32)
    batch = {"input_ids": ids}
    mean = ids.size * 4 / 16
    balanced = np.asarray(FAM.expert_assignments(cfg, params, batch))
    unbalanced = np.asarray(FAM.expert_assignments(cfg, raw, batch))
    assert balanced.shape == (4, 16)
    assert np.abs(balanced / mean - 1).max() < 0.08
    assert (unbalanced.max(1) / mean).min() > 1.25
    for i in range(1, 5):
        moved = np.abs(np.asarray(params[f"h_{i}"]["moe"]["router_bias"]
                                  - raw[f"h_{i}"]["moe"]["router_bias"]))
        # at most BALANCE_STEPS steps of BALANCE_RATE, and it did move
        assert 0 < moved.max() <= (FAM.BALANCE_STEPS * FAM.BALANCE_RATE
                                   + 1e-6)
    # the same key, the same weights
    again = jax.jit(FAM.make_loss(cfg, False)[0], static_argnums=1)(key, seq)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), params, again)))


# -- the counter, the FLOPs functions, the configuration file -----------------

def test_the_routing_counter_counts_what_the_reference_routes():
    cfg, params, batch, _ = _setup(TINY)
    counts = np.asarray(FAM.expert_assignments(cfg, params, batch))
    assert counts.shape == (4, 4)            # layers 1-4, experts 4-7
    assert 0 < counts.sum() <= 4 * B * S * 4
    # layer 1's, by the reference's routing of the same input
    x = params["wte"]["embedding"][batch["input_ids"]]
    with jax.default_matmul_precision("highest"):
        x = FAM.reference_block(TINY, x, params["h_0"], "conv", "dense")
        p1 = params["h_1"]
        mid = x + FAM.reference_attention(
            TINY, FAM._rms_norm(x, p1["ln_1"], 1e-5), p1)
        y = FAM._rms_norm(mid, p1["ln_2"], 1e-5).reshape(-1, 64)
        idx, _ = FAM.reference_routing(TINY, y, p1["moe"])
    want = [(np.asarray(idx) == 4 + e).sum() for e in range(4)]
    np.testing.assert_array_equal(counts[0], want)


def _file():
    return cells.load_json(ROOT / "perfbench/configs/lfm2-8b-a1b-ep4.json")


def test_flops_by_hand_the_parameter_count_and_the_initial_loss():
    model = _file()["model"]
    p = FAM.matmul_params_per_token(model)
    assert p["conv"] == 4 * 2048 ** 2 == 16777216
    assert p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10485760
    assert p["routed"] == (4 * 8 / 32) * 3 * 2048 * 1792 == 11010048
    by_hand = (6 * (4 * 16777216 + 10485760 + 3 * 2048 * 7168
                    + 4 * (2048 * 32 + 11010048) + 16384 * 2048)
               + 12 * 8192 * 2048)
    assert FAM.flops_per_token(model, 8192) == by_hand
    assert by_hand / 1e9 == pytest.approx(1.398, abs=0.001)
    assert FAM.expert_matmul_flops(model, 8192) == 6 * 11010048 * 8192
    # one attention layer's triangle: 0.82 TFLOP a step
    assert FAM.attention_core_flops(model, 1, 8192) == 6 * 32 * 8192 ** 2 * 64
    assert FAM.initial_loss(model) == pytest.approx(10.11, abs=0.01)
    assert FAM.tokens_per_step(1, 8192) == 8192
    # this chip's parameters, leaf by leaf: 507.8M
    cfg = FAM.model_config(model, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: FAM.make_loss(cfg, False)[0](k, 8),
                            jax.random.PRNGKey(0))
    size = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    conv = 4 * 2048 ** 2 + 3 * 2048
    experts = 2048 * 32 + 32 + 8 * 3 * 2048 * 1792
    assert size(shapes["wte"]) == 16384 * 2048
    assert size(shapes["h_0"]) == conv + 3 * 2048 * 7168 + 2 * 2048
    assert size(shapes["h_1"]) == 10485760 + 2 * 64 + experts + 2 * 2048
    assert size(shapes["h_2"]) == conv + experts + 2 * 2048 == 104933408
    assert size(shapes) == 507820288


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the source's config.json (the model-configs catalog's
    entry) is in the file under its own name, at the top level and in
    ``model``, unchanged but for those in ``reduced`` (and, in ``model``,
    the five ``layer_types`` run here)."""
    kinds = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
             for i in range(24)]
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": kinds,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    config = _file()
    share = {"num_experts": 8, "vocab_size": 16384, "num_dense_layers": 1}
    assert set(config["reduced"]) == set(share) | {"num_layers"}
    assert set(config["changed"]) == set(config["reduced"])
    for key, value in published.items():
        assert config[key] == share.get(key, value), key
        if key != "layer_types":
            assert config["model"][key] == share.get(key, value), key
    model = config["model"]
    assert tuple(kinds) == models.LFM2_8B_A1B.layer_types
    # published layers 1-5: the second dense layer, then one whole period
    assert model["layer_types"] == kinds[1:6] and model["num_layers"] == 5
    assert model["num_experts_published"] == 32
    assert model["vocab_size_published"] == 65536 == 4 * model["vocab_size"]
    assert model["head_dim"] * 32 == 2048
    # the program's preset is the published model
    preset = models.LFM2_8B_A1B
    for key, value in published.items():
        if key not in ("model_type", "layer_types"):
            assert getattr(preset, key) == value, key
    cfg = FAM.model_config(model, jnp.bfloat16)
    assert cfg.experts_held == 8 and cfg.num_experts == 32
    assert cfg.layer_types == tuple(kinds[1:6]) and cfg.head_dim == 64


def test_registry_and_config_validation():
    assert models.lfm2_names() == ["lfm2_8b_a1b", "lfm2_moe_tiny"]
    tiny = models.get_model("lfm2_moe_tiny", dtype=jnp.bfloat16)
    assert isinstance(tiny, models.Lfm2MoeLmHeadModel)
    assert tiny.config.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="names 5 layers"):
        models.Lfm2MoeConfig(layer_types=models.LFM2_MOE_TINY.layer_types)
    with pytest.raises(ValueError, match="unknown layer types"):
        models.Lfm2MoeConfig(num_hidden_layers=1, layer_types=("mamba",))
    with pytest.raises(ValueError, match="no bias path"):
        models.Lfm2MoeConfig(conv_bias=True)
