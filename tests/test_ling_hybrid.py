"""The delta-rule hybrid (`models/ling_hybrid.py`) against the benchmark's
plain reference (`perfbench/families/ling_hybrid.py`: plain `jax.numpy`,
its delta rule one position at a time, nothing of the program's) and
against NumPy, at small sizes, float32, seeded random weights."""

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import ling_hybrid
from dear_pytorch_tpu.parallel.ep import RoutedExperts
from perfbench import cell as cells
from perfbench import plain

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "ling_hybrid.py")

#: the published keys at test sizes: 16 experts scored in 4 groups, 4 held
#: from 4 on; a dense KDA layer, a KDA expert layer and an MLA expert layer
TINY = dict(
    vocab_size=96, vocab_size_published=384, hidden_size=64,
    layer_types=["kda", "kda", "mla"], first_layer=1,
    first_k_dense_replace=1, num_attention_heads=4, head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5.0, kda_chunk_size=8,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=6e6, intermediate_size=128, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=48, num_experts=4,
    num_experts_published=16, expert_offset=4, num_experts_per_tok=4,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    expert_swiglu_limit_list=[0] * 8, share_expert_swiglu_limit_list=[0] * 8,
    rms_norm_eps=1e-6, initializer_range=0.02, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3, remat=False,
    reference={"layer_types": ["kda"], "first_layer": 2,
               "first_k_dense_replace": 0, "num_nextn_predict_layers": 0})
B, S = 2, 32


def _setup(depth):
    """(model, cfg, params, batch, loss_fn) of a `DEPTHS` entry; the
    weights (the family's, the bias balanced) made once a depth, each call
    its own tree of them."""
    model = {**TINY, **DEPTHS[depth]}
    cfg = FAM.model_config(model, jnp.float32)
    init_fn, loss_fn = FAM.make_loss(cfg, with_rng=False)
    return (model, cfg, jax.tree.map(lambda x: x, _weights(depth)),
            FAM.make_batch(model, jax.random.PRNGKey(1), B, S), loss_fn)


@functools.lru_cache(maxsize=None)
def _weights(depth):
    cfg = FAM.model_config({**TINY, **DEPTHS[depth]}, jnp.float32)
    init_fn, _ = FAM.make_loss(cfg, with_rng=False)
    params = jax.jit(init_fn, static_argnums=1)(jax.random.PRNGKey(0), S)
    # weights large enough that every path moves the result (the taps are
    # [4, width]: a matrix too)
    return jax.tree.map(lambda x: 5 * x if x.ndim > 1 else x, params)


def _close(a, b, rel=2e-5):
    """Equal to ``rel`` of the reference's largest entry. Both sides are
    float32 at matmul precision "highest": only summation order differs (1e-6
    relative a matmul; the chunked delta rule against the one-position
    recurrence 4e-7, tests/test_kda.py); bf16 compute moves every tensor
    here by 1e-3 of its size and more, fifty times the limit
    (`test_bf16_compute_would_fail_the_comparison`)."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * (np.abs(b).max()
                                                        + 1e-12))


def _kinds(*layer_types, dense=0, **kw):
    return dict(layer_types=list(layer_types), first_k_dense_replace=dense,
                **kw)


DEPTHS = {
    "kda_dense": _kinds("kda", dense=1),
    "mla_expert_published_heads": _kinds(
        "mla", num_attention_heads=2, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_nextn_predict_layers=0),
    "three_layer_stack_remat": dict(remat=True),
}


@pytest.mark.parametrize("depth", DEPTHS)
def test_logits_loss_and_every_gradient_leaf_equal_the_reference(depth):
    model, cfg, params, batch, loss_fn = _setup(depth)
    ids = batch["input_ids"]
    layers = len(model["layer_types"])
    with jax.default_matmul_precision("highest"):
        logits, mtp = jax.jit(
            lambda p, i: models.LingHybridLmHeadModel(cfg).apply(
                {"params": p}, i))(params, ids)
        want, want_mtp = jax.jit(FAM.reference_logits(model, layers))(
            params, ids)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            FAM.reference_loss(model, layers)))(params, batch)
    assert logits.shape == (B, S, 96) and logits.dtype == jnp.float32
    _close(logits, want)
    if model["num_nextn_predict_layers"]:
        # the module's last position sees a wrapped-around token; the loss
        # leaves it out, the reference never computes it
        _close(mtp[:, :-1], want_mtp)
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert (jax.tree.structure(grads) == jax.tree.structure(ref_grads)
            == jax.tree.structure(params))
    jax.tree.map(_close, grads, ref_grads)
    # the selection-only bias receives no gradient, every other leaf some
    for name, g in grads.items():
        if isinstance(g, dict) and "moe" in g:
            assert not np.asarray(g["moe"].pop("router_bias")).any(), name
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.asarray(g).any(), jax.tree_util.keystr(path)


def test_bf16_compute_would_fail_the_comparison():
    """The tolerance tells precisions apart: the program in bfloat16 is
    further from the float32 reference than `_close` allows."""
    _, cfg, params, batch, _ = _setup("three_layer_stack_remat")
    low = FAM.model_config(TINY, jnp.bfloat16)
    got = jax.jit(lambda p, i: models.LingHybridLmHeadModel(low).apply(
        {"params": p}, i)[0])(params, batch["input_ids"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(FAM.reference_logits(TINY, 3))(
            params, batch["input_ids"])[0]
    with pytest.raises(AssertionError):
        _close(got, want)


def test_the_kda_layer_is_causal():
    _, cfg, params, batch, _ = _setup("kda_dense")
    ids, t = batch["input_ids"], 13
    other = ids.at[:, t].set((ids[:, t] + 1) % 96)
    run = jax.jit(lambda p, i: models.LingHybridLmHeadModel(cfg).apply(
        {"params": p}, i)[0])
    a, b = np.asarray(run(params, ids)), np.asarray(run(params, other))
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert np.abs(a[:, t:] - b[:, t:]).max(axis=(0, 2)).min() > 1e-6


@pytest.mark.parametrize("heads_at_once", [1, 2])
def test_the_kda_heads_a_group_at_a_time(monkeypatch, heads_at_once):
    """The program's op and the reference's mixer run the heads a group at
    a time (the cell: 16 and 8 of 32); any grouping is the heads at once."""
    _, cfg, params, batch, _ = _setup("kda_dense")
    p = params["h_0"]
    model = {**TINY, **DEPTHS["kda_dense"]}
    y = jax.random.normal(jax.random.PRNGKey(8), (B, S, 64))
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, y: FAM.reference_kda(model, y, p))(
            p["kda"], y)
        grouped = jax.jit(lambda p, y: FAM.reference_kda(
            model, y, p, heads_at_once))(p["kda"], y)
        monkeypatch.setattr(ling_hybrid, "_KDA_HEADS_AT_ONCE", heads_at_once)
        program = jax.jit(lambda p, y: ling_hybrid.KdaMixer(cfg).apply(
            {"params": p}, y))(p["kda"], y)
    _close(grouped, whole)
    _close(program, whole)


# -- latent attention: the padded core --------------------------------------

def test_the_padded_core_is_the_unpadded_one(monkeypatch):
    """At the published head widths the core sees q, k and v of 256 lanes,
    the lanes past 192 (q, k) and 128 (v) zero, and q times sqrt(256/192):
    what it returns in v's 128 lanes is softmax(q k^T / sqrt(192)) v of the
    unpadded heads."""
    _, cfg, params, batch, _ = _setup("mla_expert_published_heads")
    seen = {}
    real = ling_hybrid.causal_attention

    def recording_core(q, k, v, mask, **kw):
        ctx = real(q, k, v, mask, **kw)
        seen.update(q=q, k=k, v=v, ctx=ctx)
        return ctx

    monkeypatch.setattr(ling_hybrid, "causal_attention", recording_core)
    with jax.default_matmul_precision("highest"):
        models.LingHybridLmHeadModel(cfg).apply({"params": params},
                                                batch["input_ids"])
    q, k, v, ctx = (np.asarray(seen[n]) for n in ("q", "k", "v", "ctx"))
    assert q.shape == k.shape == v.shape == (B, S, 2, 256)
    assert ling_hybrid.core_width(192, 128) == 256
    assert not q[..., 192:].any() and not k[..., 192:].any()
    assert not v[..., 128:].any() and not ctx[..., 128:].any()
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0,
                       -jnp.inf)[None, None]
    with jax.default_matmul_precision("highest"):
        want = plain.attention(q[..., :192] * np.sqrt(192 / 256),
                               k[..., :192], v[..., :128], causal)
    _close(ctx[..., :128], want)


def test_qk_norm_precedes_the_interleaved_rotary():
    """q's heads are per-head RMS-normed projections whose last 8 lanes are
    turned in pairs (2i, 2i + 1), not in halves, and not normed after."""
    p = _setup("three_layer_stack_remat")[2]["h_2"]
    p["q_ln"] = 1.0 + jnp.arange(24.0) / 8
    y = jax.random.normal(jax.random.PRNGKey(4), (1, S, 64))
    x = np.asarray(jnp.einsum("bsh,hnd->bsnd", y, p["q_proj"]["kernel"],
                              precision="highest"))
    normed = x / np.sqrt(np.mean(x ** 2, -1, keepdims=True) + 1e-6) \
        * np.asarray(p["q_ln"])
    rope = ling_hybrid.interleaved_rotary_tables(S, 8, 6e6)
    turned = np.asarray(ling_hybrid.apply_interleaved_rotary(
        jnp.asarray(normed[..., 16:]), rope))
    cos, sin = (np.asarray(t)[None, :, None] for t in rope)
    want = normed[..., 16:].copy()
    want[..., 0::2] = normed[..., 16::2] * cos - normed[..., 17::2] * sin
    want[..., 1::2] = normed[..., 17::2] * cos + normed[..., 16::2] * sin
    np.testing.assert_allclose(turned, want, atol=1e-5)
    _close(FAM._interleaved_rotary(jnp.asarray(normed[..., 16:]), 6e6), want)
    halves = np.concatenate(
        [normed[..., 16:20] * cos - normed[..., 20:] * sin,
         normed[..., 20:] * cos + normed[..., 16:20] * sin], -1)
    assert np.abs(turned - halves).max() > 1e-2


# -- group-limited routing -----------------------------------------------------

H, F, WIDTH = 32, 24, 16


def _layer(held, offset, **kw):
    kw = {"n_group": 4, "topk_group": 2, **kw}
    return RoutedExperts(router_width=WIDTH, experts_held=held,
                         expert_offset=offset, top_k=4, mlp_dim=F,
                         routed_scaling_factor=2.5, **kw)


def _router(seed=5):
    x = jax.random.normal(jax.random.PRNGKey(3), (40, H))
    router = 0.5 * jax.random.normal(jax.random.PRNGKey(seed), (H, WIDTH))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (WIDTH,))
    return x, router, bias


def _numpy_selection(choice, groups, kept, k):
    out = []
    for row in np.asarray(choice, np.float64):
        by_group = row.reshape(groups, -1)
        score = np.sort(by_group, axis=-1)[:, -2:].sum(-1)
        keep = np.argsort(-score, kind="stable")[:kept]
        masked = np.full_like(by_group, -np.inf)
        masked[keep] = by_group[keep]
        out.append(np.argsort(-masked.reshape(-1), kind="stable")[:k])
    return np.stack(out)


def test_group_limited_routing_equals_a_numpy_selection():
    x, router, bias = _router()
    idx, w = _layer(4, 0).route(x, router, bias)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(x, router,
                                               precision="highest")))
    want = _numpy_selection(scores + np.asarray(bias), 4, 2, 4)
    np.testing.assert_array_equal(np.asarray(idx), want)
    # ... at most two groups of four experts, and not the flat top-4
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(idx))
    flat = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :4]
    assert (np.sort(flat, -1) != np.sort(want, -1)).any()
    picked = np.take_along_axis(scores, want, -1)
    np.testing.assert_allclose(w, 2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=2e-6)
    ref_idx, ref_w = FAM.reference_routing(
        {**TINY, "num_experts_per_tok": 4}, x,
        {"router": router, "router_bias": bias})
    np.testing.assert_array_equal(np.asarray(ref_idx), want)
    _close(ref_w, w)


@pytest.mark.parametrize("groups", [1, 4])
def test_one_group_or_every_group_kept_is_the_flat_top_k_bit_for_bit(groups):
    """``n_group`` 1 (GLM's and LFM2's layer) is the flat top-k as it was
    written before groups; all groups kept is too."""
    x, router, bias = _router(7)
    idx, w = _layer(4, 0, n_group=groups, topk_group=groups).route(
        x, router, bias)
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                                    precision=jax.lax.Precision.HIGHEST))
    _, flat = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), 4)
    picked = jnp.take_along_axis(scores, flat, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(weights * 2.5))


def test_groups_that_cannot_give_k_experts_are_refused():
    x, router, bias = _router()
    with pytest.raises(ValueError, match="cannot give 4 experts"):
        _layer(4, 0, n_group=8, topk_group=1).route(x, router, bias)


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """16 experts over 4 shares of 4: the shares' routed parts, plus the
    shared expert (which every chip computes alike) counted once, add up to
    the uncut reference layer's FFN, and each share is the reference's
    share."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, H))
    params = _layer(WIDTH, 0).init(jax.random.PRNGKey(4), x)["params"]
    params = {**params, "router_bias": 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), (WIDTH,))}
    shared = {f"shared_{n}": {"kernel": 0.2 * jax.random.normal(
        jax.random.PRNGKey(10 + i), shape)}
        for i, (n, shape) in enumerate((("gate", (H, F)), ("up", (H, F)),
                                        ("down", (F, H))))}

    def share(off):
        return {**params, "wi": params["wi"][off:off + 4],
                "wo": params["wo"][off:off + 4]}

    def model(held, off):
        return {**TINY, "num_experts": held, "expert_offset": off,
                "num_experts_published": WIDTH, "moe_intermediate_size": F}

    with jax.default_matmul_precision("highest"):
        parts = [_layer(4, off).apply({"params": share(off)}, x)
                 for off in range(0, WIDTH, 4)]
        shared_out = FAM._swiglu(x, shared, "shared")
        whole = FAM.reference_routed(model(WIDTH, 0), x, params) + shared_out
        _close(sum(parts) + shared_out, whole)
        for off, part in zip(range(0, WIDTH, 4), parts):
            assert np.abs(np.asarray(part)).max() > 0
            _close(part, FAM.reference_routed(model(4, off), x, share(off)))


# -- the configuration and its limits ---------------------------------------

def test_a_held_layer_that_clamps_is_refused():
    cfg = models.LING_3_0_FLASH
    assert cfg.expert_swiglu_limits[34:] == (0,) + (4,) * 7
    assert cfg.shared_swiglu_limits[33:] == (0,) + (5,) * 6 + (7,) * 2
    net = models.LingHybridLmHeadModel(cfg)
    with pytest.raises(ValueError, match="published layers 34-41 clamp"):
        jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8), jnp.int32)))
    # the cell's share (published layers 1-7) holds none of them
    import dataclasses

    dataclasses.replace(cfg, first_layer=1,
                        layer_types=cfg.layer_types[1:8]).refuse_clamps()
    with pytest.raises(ValueError, match="layers 30-41 are held"):
        dataclasses.replace(cfg, first_layer=30,
                            layer_types=cfg.layer_types[30:]).refuse_clamps()


def test_the_published_layer_kinds_are_five_kda_to_one_mla():
    kinds = ling_hybrid.published_layer_types()
    assert len(kinds) == 42 and kinds.count("mla") == 7
    assert [i for i, k in enumerate(kinds) if k == "mla"] == list(
        range(5, 42, 6))
    assert models.LING_3_0_FLASH.layer_types == kinds


def test_registry_and_the_routing_counter_lookup():
    assert models.ling_names() == ["ling_3_0_flash", "ling_hybrid_tiny"]
    net = models.get_model("ling_hybrid_tiny", dtype=jnp.bfloat16)
    assert isinstance(net, models.LingHybridLmHeadModel)
    assert net.config.dtype == jnp.bfloat16
    _, cfg, params, batch, _ = _setup("three_layer_stack_remat")
    counts = np.asarray(jax.jit(lambda p, i: models.expert_assignments(
        cfg, models.LingHybridLmHeadModel(cfg).apply(
            {"params": p}, i, mutable=["intermediates"])[1]["intermediates"]))(
                params, batch["input_ids"]))
    # two expert layers and the prediction module's, four experts held
    assert counts.shape == (3, 4)
    np.testing.assert_array_equal(counts, jax.jit(
        lambda p, b: FAM.expert_assignments(cfg, p, b))(params, batch))
    # a config of no sparse family is not read as another's
    with pytest.raises(TypeError, match="GraniteHybridConfig"):
        models.expert_assignments(models.GRANITE_HYBRID_TINY, {})
