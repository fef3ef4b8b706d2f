"""The sparse decoder (`models/glm_moe.py`) and its expert layer
(`parallel.ep.RoutedExperts`) against the benchmark's plain reference
(`perfbench/families/glm_moe.py`: plain `jax.numpy`, nothing of the
program's) and against plain `jax.numpy`, at small sizes, float32, seeded
random weights."""

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu import models
from dear_pytorch_tpu.models import glm_moe
from dear_pytorch_tpu.parallel.ep import RoutedExperts
from perfbench import cell as cells
from perfbench import plain

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "glm_moe.py")

#: the published keys at test sizes: 16 experts scored, 4 held from 4 on
TINY = dict(
    vocab_size=96, vocab_size_published=768, hidden_size=64, num_layers=3,
    num_hidden_layers=47, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=32, intermediate_size=128, moe_intermediate_size=48,
    n_routed_experts=4, n_routed_experts_published=16, expert_offset=4,
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, rope_theta=1e6, rms_norm_eps=1e-5,
    initializer_range=0.02, num_nextn_predict_layers=1, mtp_loss_weight=0.3,
    remat=False)
B, S = 2, 32


def _setup(model, seed=0):
    cfg = FAM.model_config(model, jnp.float32)
    init_fn, loss_fn = FAM.make_loss(cfg, with_rng=False)
    params = init_fn(jax.random.PRNGKey(seed), S)
    # weights large enough that every path moves the result
    params = jax.tree.map(lambda x: 5 * x if x.ndim > 1 else x, params)
    batch = FAM.make_batch(model, jax.random.PRNGKey(seed + 1), B, S)
    return cfg, params, batch, loss_fn


def _close(a, b, rel=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * (np.abs(b).max()
                                                        + 1e-12))


DEPTHS = {
    "dense_only": dict(num_layers=1, num_nextn_predict_layers=0),
    "expert": dict(num_layers=3, num_nextn_predict_layers=0),
    "expert_mtp": dict(num_layers=3, num_nextn_predict_layers=1),
    "expert_mtp_remat": dict(num_layers=2, remat=True),
}


@pytest.mark.parametrize("depth", DEPTHS)
def test_logits_loss_and_every_gradient_leaf_equal_the_reference(depth):
    model = {**TINY, **DEPTHS[depth]}
    cfg, params, batch, loss_fn = _setup(model)
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        logits, mtp = jax.jit(lambda p, i: models.GlmMoeLmHeadModel(
            cfg).apply({"params": p}, i))(params, ids)
        want, want_mtp = jax.jit(FAM.reference_logits(
            model, model["num_layers"]))(params, ids)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            FAM.reference_loss(model, model["num_layers"])))(params, batch)
    _close(logits, want)
    if model["num_nextn_predict_layers"]:
        # the program runs the module on all S positions; the last one sees
        # a wrapped-around token and is no prediction
        _close(mtp[:, :-1], want_mtp)
    else:
        assert mtp is None and want_mtp is None
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert (jax.tree.structure(grads) == jax.tree.structure(ref_grads)
            == jax.tree.structure(params))
    jax.tree.map(_close, grads, ref_grads)
    # the selection-only bias receives no gradient, every matrix some
    for name, g in grads.items():
        if "moe" in g:
            assert not np.asarray(g["moe"]["router_bias"]).any(), name
            assert np.asarray(g["moe"]["wi"]).any(), name


# -- the expert layer ---------------------------------------------------------

H, F, WIDTH = 32, 24, 16


def _layer(held, offset, **kw):
    return RoutedExperts(router_width=WIDTH, experts_held=held,
                         expert_offset=offset, top_k=kw.pop("top_k", 4),
                         mlp_dim=F, routed_scaling_factor=1.8, **kw)


@functools.lru_cache(maxsize=None)
def _whole():
    """(x [T, H], parameters of the uncut 16-expert layer)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (40, H))
    params = _layer(WIDTH, 0).init(jax.random.PRNGKey(4), x)["params"]
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (WIDTH,))
    return x, {**params, "router_bias": bias}


def _share(params, offset, held):
    return {**params, "wi": params["wi"][offset:offset + held],
            "wo": params["wo"][offset:offset + held]}


def _reference_model(held, offset, top_k=4, norm=True):
    return dict(num_experts_per_tok=top_k, norm_topk_prob=norm,
                routed_scaling_factor=1.8, moe_intermediate_size=F,
                n_routed_experts=held, expert_offset=offset)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: the routed parts of all four shares (what
    every chip computes alike, a shared expert, would be added once) equal
    the uncut layer, in the program and in the reference; and each share
    equals the reference given the same share."""
    x, params = _whole()
    whole = _layer(WIDTH, 0).apply({"params": params}, x)
    parts = [_layer(4, off).apply({"params": _share(params, off, 4)}, x)
             for off in range(0, WIDTH, 4)]
    _close(sum(parts), whole)
    _close(whole, FAM.reference_routed(_reference_model(WIDTH, 0), x, params))
    for off, part in zip(range(0, WIDTH, 4), parts):
        assert np.abs(np.asarray(part)).max() > 0
        _close(part, FAM.reference_routed(_reference_model(4, off), x,
                                          _share(params, off, 4)))


def test_no_token_is_dropped_when_all_route_to_one_held_expert():
    x, params = _whole()
    # top-1, and the bias puts expert 6 first for every token
    bias = jnp.zeros(WIDTH).at[6].set(10.0)
    p = {**_share(params, 4, 4), "router_bias": bias}
    out, col = _layer(4, 4, top_k=1).apply({"params": p}, x,
                                           mutable=["intermediates"])
    sizes = col["intermediates"]["assignments"][0]
    np.testing.assert_array_equal(sizes, [0, 0, x.shape[0], 0])
    gate_up = x @ p["wi"][2]
    expert = (jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:]) @ p["wo"][2]
    # norm_topk_prob over one weight is 1; the scale stays
    _close(out, 1.8 * expert)
    assert np.abs(np.asarray(out)).min(axis=1).max() > 0   # every row


def test_an_all_absent_routing_gives_the_shared_expert_alone():
    x, params = _whole()
    bias = jnp.zeros(WIDTH).at[:4].set(10.0)          # experts 0-3: absent
    p = {**_share(params, 4, 4), "router_bias": bias}
    out, col = _layer(4, 4).apply({"params": p}, x, mutable=["intermediates"])
    assert not np.asarray(out).any()
    assert not np.asarray(col["intermediates"]["assignments"][0]).any()
    # ... so a block's FFN is its shared expert
    model = {**TINY, "num_layers": 2, "num_nextn_predict_layers": 0}
    cfg, params, batch, _ = _setup(model)
    moe = params["h_1"]["moe"]
    absent = jnp.zeros(16).at[jnp.array([0, 1, 2, 3])].set(10.0)
    params["h_1"]["moe"] = {**moe, "router_bias": absent}
    x = jax.random.normal(jax.random.PRNGKey(8), (B, S, 64))
    rope = glm_moe.rotary_tables(S, 8, 1e6)
    with jax.default_matmul_precision("highest"):
        got = glm_moe.GlmBlock(cfg, "expert").apply(
            {"params": params["h_1"]}, x, rope)
        p1 = params["h_1"]
        mid = FAM.reference_attention(model, x, p1)
        want = mid + FAM._swiglu(FAM._rms_norm(mid, p1["ln_2"], 1e-5), p1,
                                 "shared")
    _close(got, want)


def test_the_bias_moves_the_selection_and_never_the_weights():
    x, params = _whole()
    layer = _layer(4, 4)
    router = params["router"]
    scores = jax.nn.sigmoid(x @ router)
    idx0, w0 = layer.route(x, router, jnp.zeros(WIDTH))
    idx1, w1 = layer.route(x, router, params["router_bias"])
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()   # it selects
    for idx, w in ((idx0, w0), (idx1, w1)):
        picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
        _close(w, 1.8 * picked / picked.sum(-1, keepdims=True))
        _close(np.asarray(w).sum(-1), np.full(x.shape[0], 1.8))
    # the selection is the top 4 of score + bias
    want = np.argsort(-np.asarray(scores + params["router_bias"]), -1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx1, -1), np.sort(want, -1))
    # without norm_topk_prob: the raw scores, scaled
    _, raw = _layer(4, 4, norm_topk_prob=False).route(
        x, router, params["router_bias"])
    _close(raw, 1.8 * np.take_along_axis(np.asarray(scores),
                                         np.asarray(idx1), -1))
    _close(FAM.reference_routing(_reference_model(4, 4, norm=False), x,
                                 params)[1], raw)


def test_softmax_scoring_and_a_bad_share_are_told_apart():
    x, params = _whole()
    idx, w = _layer(4, 4, scoring="softmax").route(
        x, params["router"], jnp.zeros(WIDTH))
    probs = jax.nn.softmax(x @ params["router"], -1)
    picked = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    _close(w, 1.8 * picked / picked.sum(-1, keepdims=True))
    with pytest.raises(ValueError, match="not among the router's"):
        _layer(4, 14).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="unknown scoring"):
        _layer(4, 4, scoring="tanh").route(x, params["router"],
                                           jnp.zeros(WIDTH))


def test_the_layers_gradients_are_gathers_of_the_plain_ones():
    """`_spread` / `_unpermute` carry hand-written gradients (no
    scatter-add): the layer's gradients equal the reference's."""
    x, params = _whole()
    p = _share(params, 4, 4)

    def ours(p, x):
        return jnp.sum(jnp.sin(_layer(4, 4).apply({"params": p}, x)))

    def ref(p, x):
        return jnp.sum(jnp.sin(FAM.reference_routed(
            _reference_model(4, 4), x, p)))

    got, want = jax.grad(ours, (0, 1))(p, x), jax.grad(ref, (0, 1))(p, x)
    jax.tree.map(_close, got, want)


@pytest.mark.parametrize("rows", ["gathers", "kernels"])
def test_rows_of_no_group_reach_neither_result_nor_gradient(monkeypatch,
                                                            rows):
    """On the TPU the grouped matmul leaves the rows past the held experts'
    groups unwritten, forward and backward (PR 31's first chip run: NaN
    from the second step on). A `ragged_dot` that poisons those rows the
    same way must change nothing. Nor must the row kernels the TPU path
    takes (`ops.moe_rows`, here in interpret mode at 128 lanes), whose own
    outputs hold anything past the held experts' rows: ``xs``, the
    cotangent of ``ys`` and the row-wise dots are poisoned there too."""
    from dear_pytorch_tpu.parallel import ep

    real = jax.lax.ragged_dot

    def poison(y, sizes):
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), y, jnp.nan)

    @jax.custom_vjp
    def dirty(x, w, sizes):
        return poison(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        y, vjp = jax.vjp(lambda x, w: real(x, w, sizes), x, w)
        return poison(y, sizes), (vjp, sizes)

    def bwd(res, g):
        vjp, sizes = res
        dx, dw = vjp(g)
        return poison(dx, sizes), dw, None

    dirty.defvjp(fwd, bwd)
    x, params = _whole()
    p = _share(params, 4, 4)
    if rows == "kernels":   # rows of whole lane tiles: 128 lanes, not 32
        n = 128 // H
        x = jnp.tile(x, (1, n))
        p = {**p, "router": jnp.tile(p["router"], (n, 1)) / n,
             "wi": jnp.tile(p["wi"], (1, n, 1)),
             "wo": jnp.tile(p["wo"], (1, 1, n))}

    def loss(p, x):
        return jnp.sum(jnp.sin(_layer(4, 4).apply({"params": p}, x)))

    want = jax.value_and_grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(ep.lax, "ragged_dot", dirty)
    if rows == "kernels":
        spread_rows = ep.moe_rows.spread_rows

        def dirty_rows(src, row, count, **kw):
            live = jnp.arange(row.shape[0]) < count
            out = spread_rows(src, row, count, **kw)
            return jax.tree.map(
                lambda o: jnp.where(live.reshape((-1,) + (1,) * (o.ndim - 1)),
                                    o, jnp.nan), out)

        monkeypatch.setattr(ep.moe_rows, "spread_rows", dirty_rows)
        monkeypatch.setattr(ep.moe_rows, "applies", lambda *a: True)
    got = jax.value_and_grad(loss, (0, 1))(p, x)
    assert np.isfinite(float(got[0]))
    jax.tree.map(_close, got, want)


# -- latent attention ---------------------------------------------------------

def test_latent_attention_is_plain_attention_on_its_q_k_v(monkeypatch):
    """The assembled MLA equals plain multi-head attention on its q, k, v;
    rotary touches only the rope lanes; one rotary key head serves all."""
    model = {**TINY, "num_layers": 1, "num_nextn_predict_layers": 0}
    cfg, params, _, _ = _setup(model)
    seen = {}

    def recording_core(q, k, v, mask, **kw):
        ctx = glm_moe_causal(q, k, v, mask, **kw)
        seen.update(q=q, k=k, v=v, ctx=ctx)
        return ctx

    glm_moe_causal = glm_moe.causal_attention
    monkeypatch.setattr(glm_moe, "causal_attention", recording_core)
    # the same token at every position: only the rotary tells positions apart
    ids = jnp.full((1, S), 7, jnp.int32)
    models.GlmMoeLmHeadModel(cfg).apply({"params": params}, ids)
    q, k, v, ctx = (np.asarray(seen[n]) for n in ("q", "k", "v", "ctx"))
    nope = model["qk_nope_head_dim"]
    assert q.shape == k.shape == (1, S, 4, 32) and v.shape == (1, S, 4, 32)
    for t in (q[..., :nope], k[..., :nope], v):
        np.testing.assert_allclose(t, np.broadcast_to(t[:, :1], t.shape),
                                   atol=1e-6)
    assert np.abs(q[:, 1:, :, nope:] - q[:, :1, :, nope:]).max() > 1e-3
    assert np.abs(k[:, 1:, :, nope:] - k[:, :1, :, nope:]).max() > 1e-3
    # a rotation: each (i, i + 4) pair keeps its norm
    pairs = k[..., nope:].reshape(1, S, 4, 2, 4)
    np.testing.assert_allclose(np.square(pairs).sum(3),
                               np.square(pairs[:, :1]).sum(3)
                               * np.ones((1, S, 1, 1)), rtol=1e-5)
    # one rotary key head for all heads
    np.testing.assert_array_equal(k[:, :, :1, nope:] * np.ones((1, 1, 4, 1)),
                                  k[..., nope:])
    causal = jnp.where(jnp.tril(jnp.ones((S, S), bool)), 0.0,
                       -jnp.inf)[None, None]
    _close(ctx, plain.attention(q, k, v, causal))
    # and the reference builds the same q, k, v
    x = params["wte"]["embedding"][ids]
    p0 = params["h_0"]
    rq, rk, rv = FAM.reference_attention_inputs(
        model, FAM._rms_norm(x, p0["ln_1"], 1e-5), p0)
    for got, want in ((q, rq), (k, rk), (v, rv)):
        _close(got, want)


# -- the second prediction depth ---------------------------------------------

def test_mtp_targets_are_two_ahead_and_lambda_weighs_its_loss():
    ids = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0, 96)
    sure = 30.0

    def predicting(ahead):
        target = jnp.roll(ids, -ahead, axis=1)
        return sure * jax.nn.one_hot(target, 96)

    right = glm_moe.glm_moe_lm_loss((predicting(1), predicting(2)), ids)
    assert float(right) < 1e-6
    main_wrong = glm_moe.glm_moe_lm_loss((predicting(2), predicting(2)), ids)
    mtp_wrong = glm_moe.glm_moe_lm_loss((predicting(1), predicting(1)), ids)
    assert float(main_wrong) > 20 and float(mtp_wrong) > 0.3 * 20
    logits = jax.random.normal(jax.random.PRNGKey(1), (2, B, S, 96))
    at = {lam: float(glm_moe.glm_moe_lm_loss(
        (logits[0], logits[1]), ids, mtp_loss_weight=lam))
        for lam in (0.0, 0.3, 1.0)}
    mtp_term = float(jnp.mean(plain.cross_entropy(logits[1][:, :-2],
                                                  ids[:, 2:])))
    assert at[0.0] == pytest.approx(float(glm_moe.glm_moe_lm_loss(
        (logits[0], None), ids)), abs=1e-6)
    assert at[0.3] - at[0.0] == pytest.approx(0.3 * mtp_term, rel=1e-5)
    assert at[1.0] - at[0.0] == pytest.approx(mtp_term, rel=1e-5)


# -- the counter, the FLOPs function, the configuration file ------------------

def test_the_routing_counter_counts_what_the_reference_routes():
    model = dict(TINY)
    cfg, params, batch, _ = _setup(model)
    counts = np.asarray(FAM.expert_assignments(cfg, params, batch))
    assert counts.shape == (3, 4)            # layers 1, 2 and the module
    assert 0 < counts.sum() <= 3 * B * S * 4
    # layer 1's, by the reference's routing of the same input
    x = params["wte"]["embedding"][batch["input_ids"]]
    with jax.default_matmul_precision("highest"):
        x = FAM.reference_block(model, x, params["h_0"], "dense")
        p1 = params["h_1"]
        mid = FAM.reference_attention(model, x, p1)
        y = FAM._rms_norm(mid, p1["ln_2"], 1e-5).reshape(-1, 64)
        idx, _ = FAM.reference_routing(model, y, p1["moe"])
    want = [(np.asarray(idx) == 4 + e).sum() for e in range(4)]
    np.testing.assert_array_equal(counts[0], want)


def test_flops_per_token_by_hand_and_the_initial_loss():
    model = cells.load_json(
        ROOT / "perfbench/configs/glm-4.7-flash-ep8.json")["model"]
    p = FAM.matmul_params_per_token(model)
    assert p["attention"] == (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                              + 512 * 20 * 448 + 5120 * 2048) == 21757952
    assert p["routed"] == 0.5 * 3 * 2048 * 1536
    by_hand = (6 * (6 * 21757952 + 3 * 2048 * 10240
                    + 5 * (9437184 + 2048 * 64 + 4718592)
                    + 4096 * 2048 + 2 * 19360 * 2048)
               + 6 * 12 * 4096 * 5120)
    assert FAM.flops_per_token(model, 4096) == by_hand
    assert by_hand / 1e9 == pytest.approx(3.62, abs=0.01)
    assert FAM.expert_matmul_flops(model, 4096) == 6 * 9437184 * 4096
    assert FAM.initial_loss(model) == pytest.approx(13.36, abs=0.01)
    assert FAM.tokens_per_step(2, 4096) == 8192


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the source's config.json (the model-configs catalog's
    entry) is in the file under its own name, at the top level and in
    ``model``, unchanged but for the three in ``reduced``."""
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    config = cells.load_json(
        ROOT / "perfbench/configs/glm-4.7-flash-ep8.json")
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert set(config["changed"]) == set(config["reduced"])
    for where in (config, config["model"]):
        for key, value in published.items():
            if key in config["reduced"]:
                assert where[key] != value
            else:
                assert where[key] == value, key
    model = config["model"]
    assert (model["num_layers"], model["n_routed_experts"],
            model["vocab_size"]) == (5, 8, 19360)
    assert model["n_routed_experts_published"] == 64
    assert model["vocab_size_published"] == 154880 == 8 * model["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]
    assert config["train"]["momentum"] == 0.9
    cfg = FAM.model_config(model, jnp.bfloat16)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.num_layers) == (64, 8,
                                                                       5)
    shapes = jax.eval_shape(
        lambda k: FAM.make_loss(cfg, False)[0](k, 4096), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n / 1e6 == pytest.approx(706, abs=1)
    assert shapes["h_1"]["moe"]["wi"].shape == (8, 2048, 3072)
    assert shapes["h_1"]["moe"]["router"].shape == (2048, 64)
