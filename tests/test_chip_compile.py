"""Compile the main path's kernels and one step program for a TPU v5e that
is described, not attached (on-chip-measurement guide, section 2).

The TPU compiler is installed with JAX; a `v5e:2x2` topology description
gives it four devices to compile for, so what Mosaic or XLA:TPU refuses
costs no chip time to find. A compile that passes is a compile, never a
run — `chip_smoke.py` is the run.

All of it lives in this one file, and the topology is described inside a
module-scoped fixture (never at import): only one process may load the
TPU's library, so only the xdist worker that is handed this file does.
"""

import functools
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import chip_smoke
from dear_pytorch_tpu.ops.collective_matmul import (
    allgather_matmul,
    fused_reduce_scatter_update,
    ring_all_gather,
)
from dear_pytorch_tpu.ops.flash_attention import flash_attention
from dear_pytorch_tpu.ops import grouped_matmul, moe_rows
from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
from dear_pytorch_tpu.ops.fusion import bucket_length
from dear_pytorch_tpu.parallel import DearState, build_train_step
from dear_pytorch_tpu.parallel.ep import RoutedExperts

P = jax.P
#: a 25 MB f32 fusion bucket (THRESHOLD_MB of the smoke), in elements
BUCKET = 25 * 2**20 // 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("dp",))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels pick interpret mode from `jax.default_backend()`, which
    is the CPU here; steer them to the Mosaic path for these compiles.
    (By module name through sys.modules: `dear_pytorch_tpu.ops` re-exports
    a `flash_attention` FUNCTION that shadows the module attribute.)"""
    for name in ("flash_attention", "collective_matmul", "moe_rows",
                 "grouped_matmul"):
        monkeypatch.setattr(sys.modules[f"dear_pytorch_tpu.ops.{name}"],
                            "_interpret", lambda: False)


def _on(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def test_perf_model_knows_the_described_device(topo):
    from dear_pytorch_tpu.utils import perf_model

    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert perf_model.device_peak_flops(topo.devices[0]) == 197e12


# GPT-2 124M attention at S=1024 (causal; the benchmark cell's batch),
# BERT-Base at S=128, and GLM-4.7-Flash's latent attention at S=4096: 20
# heads of 256, the one-head-a-block branch (the fused backward then holds
# dK/dV of 4096 rows at 256 lanes in VMEM, 8.4 MB f32 of the 64 MB limit);
# and LFM2-8B-A1B's attention layer at S=8192, 32 Q heads over 8 K/V heads of
# 64: the grouped form (a lane rotation by a traced amount brings a Q head
# to its K/V head's lanes; dK/dV of 8192 rows accumulate over the q
# sub-blocks too), its gradients back at K/V width
@pytest.mark.parametrize("shape,causal,kv_heads", [
    ((16, 1024, 12, 64), True, None), ((32, 128, 12, 64), False, None),
    ((1, 4096, 20, 256), True, None), ((1, 8192, 32, 64), True, 8)])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_kernel_compiles_for_v5e(compiled_kernels, one_chip, shape,
                                       causal, kv_heads, direction):
    attend = functools.partial(flash_attention, causal=causal)
    fn = attend if direction == "fwd" else jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_heads or shape[2], shape[3]),
                              jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(fn).lower(x, kv, kv).compile()
    assert compiled.as_text().count(KERNEL) == (1 if direction == "fwd"
                                                else 2)
    if direction == "bwd":
        assert [tuple(g.shape) for g in jax.tree.leaves(
            compiled.out_info)] == [x.shape, kv.shape, kv.shape]


#: a Pallas kernel in optimized HLO text
KERNEL = 'custom_call_target="tpu_custom_call"'
#: ... that draws a dropout mask (the name `ops.flash_attention` gives it)
DROPOUT_KERNEL = re.compile(r"/flash_(?:fwd|bwd)_dropout/")


# BERT-Large's attention in the benchmark cell: probabilities dropout
# inside the kernels, a key mask, not causal
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_dropout_kernels_compile_for_v5e(compiled_kernels, one_chip,
                                         direction):
    def attend(q, k, v, kv_mask, rng):
        return flash_attention(q, k, v, kv_mask=kv_mask, dropout_rng=rng,
                               dropout_rate=0.1)

    fn = attend if direction == "fwd" else jax.grad(
        lambda q, k, v, m, rng: attend(q, k, v, m, rng).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    x = jax.ShapeDtypeStruct((16, 512, 16, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv_mask = jax.ShapeDtypeStruct((16, 512), jnp.bool_, sharding=one_chip)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(fn).lower(x, x, x, kv_mask, rng).compile().as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == (1 if direction == "fwd" else 2)
    assert all(DROPOUT_KERNEL.search(line) for line in calls)


def test_default_bert_large_step_selects_the_dropout_kernels(
        compiled_kernels, one_chip):
    """BERT-Large at published widths and dropout, two layers, S=512, no
    ``attention_impl`` passed: the default core puts a forward and a fused
    backward kernel with dropout into each layer of the gradient program,
    under an ``attention`` scope (what `attention_core_ms` and
    `dropout_kernel_calls_per_step` read)."""
    import dataclasses

    from dear_pytorch_tpu import models

    cfg = dataclasses.replace(models.get_model(
        "bert_large", dtype=jnp.bfloat16).config, num_hidden_layers=2)
    assert cfg.attention_probs_dropout_prob == 0.1
    model = models.BertForPreTraining(cfg)
    shape = (4, 512)
    ids = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda key: model.init({"params": key}, jnp.zeros((1, 512),
                                                          jnp.int32),
                               train=False)["params"],
        jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        params)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    nsp = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)

    def loss(p, ids, labels, nsp_labels, rng):
        logits, nsp_logits = model.apply(
            {"params": p}, ids, jnp.zeros_like(ids), jnp.ones_like(ids),
            train=True, rngs={"dropout": rng})
        return models.bert_pretraining_loss(logits, nsp_logits, labels,
                                            nsp_labels)

    text = jax.jit(jax.grad(loss)).lower(params, ids, ids, nsp,
                                         rng).compile().as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == 4
    assert all("/attention/" in line and DROPOUT_KERNEL.search(line)
               for line in calls)
    assert sum("transpose(jvp(" in line for line in calls) == 2


@pytest.mark.parametrize("attn_dropout,kernels", [(0.0, 4), (0.1, 4)],
                         ids=["no-dropout", "attn-dropout"])
def test_default_gpt2_step_selects_the_kernel(compiled_kernels, one_chip,
                                              attn_dropout, kernels):
    """GPT-2 at published widths, two layers, S=1024, no ``attention_impl``
    passed: the default core puts a forward and a fused backward kernel into
    each layer of the gradient program, both under an ``attention`` scope
    (what `attention_core_ms` and `attention_kernel_calls_per_step` read);
    with GPT-2's published ``attn_pdrop`` they are the kernels that drop
    probabilities themselves, without it the kernels without."""
    import dataclasses

    cfg = dataclasses.replace(
        chip_smoke.gpt2_config(jnp.bfloat16, num_layers=2),
        attention_probs_dropout_prob=attn_dropout)
    model = chip_smoke.models.GptLmHeadModel(cfg)
    ids = jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda key: model.init({"params": key}, jnp.zeros((1, 1024),
                                                          jnp.int32),
                               train=False)["params"],
        jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        params)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def loss(p, ids, rng):
        logits = model.apply({"params": p}, ids, train=True,
                             rngs={"dropout": rng})
        return chip_smoke.models.gpt_lm_loss(logits, ids,
                                             vocab_size=cfg.vocab_size)

    text = jax.jit(jax.grad(loss)).lower(params, ids, rng).compile().as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == kernels
    assert all("/attention/" in line for line in calls)
    assert sum("transpose(jvp(" in line for line in calls) == kernels // 2
    assert all(bool(DROPOUT_KERNEL.search(line)) == (attn_dropout > 0)
               for line in calls)


# The routed experts' row kernels at the sparse cells' shape, T = 8192
# tokens choosing 4 experts, H = 2048, bf16: a row DMA out of a tiled buffer
# is what Mosaic refused in PR 24 (`Slice shape along dimension 0 must be
# aligned to tiling`), so the spread reads rows as whole tiles behind a
# leading index and the combine reads aligned 16-row chunks
@pytest.mark.parametrize("op", ["spread", "combine"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_moe_row_kernels_compile_for_v5e(compiled_kernels, one_chip, op,
                                         direction):
    T, k, H, E = 8192, 4, 2048, 8
    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    routing = (on((T, k), jnp.int32), on((T * k,), jnp.int32),
               on((T * k,), jnp.int32), on((E,), jnp.int32))
    x, ys = on((T, H), jnp.bfloat16), on((T * k, H), jnp.bfloat16)

    def spread(x, *routing):
        return moe_rows.spread(x, moe_rows.dispatch(*routing))

    def combine(ys, w, *routing):
        return moe_rows.combine(ys, w, moe_rows.dispatch(*routing),
                                jnp.bfloat16)

    fn, operands = {"spread": (spread, (x,)),
                    "combine": (combine, (ys, on((T, k), jnp.float32)))}[op]
    if direction == "bwd":
        fn = jax.grad(lambda *a, f=fn: f(*a).astype(jnp.float32).sum(),
                      argnums=tuple(range(len(operands))))
    text = jax.jit(fn).lower(*operands, *routing).compile().as_text()
    calls = [line for line in text.splitlines() if KERNEL in line]
    # each body is the other's gradient (a sum's gradient needs no forward)
    body = op if direction == "fwd" else {"spread": "combine",
                                          "combine": "spread"}[op]
    assert len(calls) == 1 and f"moe_{body}_rows" in calls[0]


# The routed experts' feed-forward at the sparse cells' shapes: 32,768
# sorted rows (8192 tokens x 4 experts) of H = 2048 against 8 held experts
# of 1536 (GLM-4.7-Flash) and 1792 (LFM2-8B-A1B; 14 lane tiles: its column
# tiles are 896 or 1792 wide), bf16 rows, f32 parameter leaves: two kernels
# forward, four more backward, whole-contraction blocks within the 64 MB of
# VMEM the kernels ask for
@pytest.mark.parametrize("mlp_dim", [1536, 1792],
                         ids=["glm-4.7-flash", "lfm2-8b-a1b"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_matmul_kernels_compile_for_v5e(compiled_kernels, one_chip,
                                                mlp_dim, direction):
    N, H, E = 32768, 2048, 8
    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    operands = (on((N, H), jnp.bfloat16), on((E, H, 2 * mlp_dim), jnp.float32),
                on((E, mlp_dim, H), jnp.float32))

    def fn(xs, wi, wo, sizes):
        return grouped_matmul.feed_forward(
            xs, wi.astype(jnp.bfloat16), wo.astype(jnp.bfloat16), sizes)

    if direction == "bwd":
        fn = jax.grad(lambda *a, f=fn: jnp.square(
            f(*a).astype(jnp.float32)).sum(), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*operands, on((E,), jnp.int32)).compile(
        ).as_text()
    names = sorted(re.search(r"/(grouped_\w+)", line).group(1)
                   for line in text.splitlines() if KERNEL in line)
    forward = ["grouped_gate_up", "grouped_matmul"]
    assert names == (forward if direction == "fwd" else sorted(
        forward + ["grouped_act_grad", "grouped_matmul",
                   "grouped_weight_grad", "grouped_weight_grad"]))


@pytest.mark.parametrize("width,mlp_dim,hidden,kernels", [
    (64, 1536, 2048, 4), (32, 1792, 2048, 4), (16, 48, 64, 0)],
    ids=["glm-4.7-flash", "lfm2-8b-a1b", "tiny"])
def test_routed_experts_layer_selects_the_row_kernels(
        compiled_kernels, one_chip, width, mlp_dim, hidden, kernels):
    """One `RoutedExperts` layer at the sparse cells' widths (8192 tokens,
    H 2048, 8 of 64 and 8 of 32 experts held), value and gradient: the
    spread and the combine and each one's backward are kernels, under the
    ``dispatch`` / ``combine`` scopes `moe_dispatch_ms` and
    `moe_row_kernel_calls_per_step` read; the tiny presets' 64 lanes keep
    the gathers."""
    T = 8192
    layer = RoutedExperts(router_width=width, experts_held=8, top_k=4,
                          mlp_dim=mlp_dim, dtype=jnp.bfloat16)
    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    x = on((T, hidden), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: on(a.shape, a.dtype),
        jax.eval_shape(lambda key: layer.init(key, jnp.zeros(
            (T, hidden), jnp.bfloat16))["params"], jax.random.PRNGKey(0)))

    def loss(p, x):
        with jax.named_scope("moe"):
            y = layer.apply({"params": p}, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if KERNEL in line and "/moe_" in line]
    assert len(calls) == kernels
    scopes = sorted(re.search(r"(transpose\(jvp\(moe\)\)|jvp\(moe\))/"
                              r"RoutedExperts/(\w+)/(?:jit\(\w+\)/)?"
                              r"moe_(\w+)_rows", line).groups()
                    for line in calls)
    assert scopes == sorted([
        ("jvp(moe)", "dispatch", "spread"),
        ("jvp(moe)", "combine", "combine"),
        ("transpose(jvp(moe))", "combine", "spread"),
        ("transpose(jvp(moe))", "dispatch", "combine")][:kernels])
    # and between them the feed-forward's six, under the ``experts`` scope
    # `expert_matmul_kernel_calls_per_step` counts and `moe_routed_ms` books
    found = (re.search(
        r"(transpose\(jvp\(moe\)\)|jvp\(moe\))/RoutedExperts/experts/"
        r"(?:jit\(\w+\)/)?(grouped_\w+)", line)
        for line in text.splitlines() if KERNEL in line)
    experts = sorted(m.groups() for m in found if m)
    assert experts == sorted([
        ("jvp(moe)", "grouped_gate_up"), ("jvp(moe)", "grouped_matmul"),
        ("transpose(jvp(moe))", "grouped_act_grad"),
        ("transpose(jvp(moe))", "grouped_matmul"),
        ("transpose(jvp(moe))", "grouped_weight_grad"),
        ("transpose(jvp(moe))", "grouped_weight_grad")][:6 * kernels // 4])


def _granite_block_text(one_chip, mixer, remat):
    """Optimized HLO of one Granite-4.0-H-Micro block at the cell's widths
    (hidden 2048, bf16, one sequence of 4096), value and gradient."""
    import dataclasses

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.models import granite_hybrid

    cfg = dataclasses.replace(models.GRANITE_4_0_H_MICRO,
                              layer_types=(mixer,), dtype=jnp.bfloat16)
    block = granite_hybrid.GraniteHybridBlock
    if remat:
        block = granite_hybrid.nn.remat(
            block, policy=granite_hybrid._BLOCK_POLICY)
    layer = block(cfg, mixer)
    on = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    x = on((1, 4096, 2048), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: on(a.shape, a.dtype),
        jax.eval_shape(lambda key: layer.init(key, jnp.zeros(
            (1, 8, 2048), jnp.bfloat16))["params"], jax.random.PRNGKey(0)))

    def loss(p, x):
        with jax.named_scope("block"):
            return jnp.sum(jnp.square(
                layer.apply({"params": p}, x).astype(jnp.float32)))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    return compiled.as_text(), compiled.memory_analysis()


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_granite_mamba_block_compiles_for_v5e_with_its_scopes(
        compiled_kernels, one_chip, remat):
    """One Mamba-2 block at the cell's widths (`x [1, 4096, 64, 64]`, one
    B/C group, state 128, chunk 256): XLA, no kernel; every inner scope of
    ``mamba`` is on instructions of the forward pass and, under
    ``transpose(jvp(...))``, of the backward pass, which is what
    `mamba_mixer_ms`, `ssd_scan_ms` and `ssd_scan_roofline_pct` join on; the
    scan's recomputation sits under its own ``checkpoint``; the temporaries
    (the f32 `[16, 64, 256, 256]` decay matrices among them) stay under 3
    GB."""
    text, memory = _granite_block_text(one_chip, "mamba", remat)
    assert KERNEL not in text and text.count(" while(") >= 2
    names = re.findall(r'op_name="([^"]*)"', text)
    for inner in ("in_proj", "conv1d", "ssd", "gate_norm", "out_proj"):
        scope = f"mamba/{inner}"
        assert any("jvp(block)" in n and "transpose(" not in n
                   and scope in n for n in names), scope
        assert any("transpose(jvp(block))" in n and scope in n
                   for n in names), scope
    assert any("mamba/ssd/checkpoint/rematted_computation" in n
               for n in names)
    assert memory.temp_size_in_bytes < 3e9


@pytest.mark.parametrize("remat,kernels", [(False, 2), (True, 3)],
                         ids=["stored", "remat"])
def test_granite_attention_block_holds_the_grouped_flash_kernels(
        compiled_kernels, one_chip, remat, kernels):
    """The position-free attention block at 32 Q / 8 K/V heads of 64,
    S=4096: the two grouped flash kernels under the bare ``attention``
    scope (`attention_kernel_calls_per_step`, `gqa_attention_flops_util_pct`
    read it); a recomputed block runs the forward kernel a second time in
    its backward pass (three calls: what the benchmark cell's step holds).
    No rotary table, no dense ``[S, S]`` scores."""
    text, _ = _granite_block_text(one_chip, "attention", remat)
    calls = [line for line in text.splitlines() if KERNEL in line]
    assert len(calls) == kernels
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(r"(?:^|[/(])attention(?:[/)]|$)", op_name), op_name
    assert sum("transpose(jvp(block))" in line for line in calls) \
        == kernels - 1
    assert "f32[1,32,4096,4096]" not in text
    assert not re.search(r"\b(cosine|sine)\(", text)


def test_gpt2_head_and_loss_keep_one_bf16_logits_buffer(one_chip):
    """GPT-2 124M's tied head and `gpt_lm_loss` at the benchmark cell's
    shapes, value and both gradients. XLA:TPU fuses the row maximum into
    the head matmul's epilogue and the softmax gradient into the two
    backward matmuls' operands, so the one buffer of the logits' extent is
    the matmul's own bf16 output. With the logits sliced
    (`[:, :-1]`, `[..., :vocab_size]`; until PR 34) the entry computation
    held two f32 buffers of that extent and a bf16 one, 6.59e9 bytes of
    temporaries. A JAX or libtpu that undoes the fusion fails here, not in
    a cell."""
    B, S, H, V, vocab = 16, 1024, 768, 50264, 50257

    def loss(x, table, ids):
        logits = jnp.einsum("bsh,vh->bsv", x, table).astype(jnp.float32)
        return chip_smoke.models.gpt_lm_loss(logits, ids, vocab_size=vocab)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((B, S, H), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((V, H), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    logits_sized = re.findall(
        rf"(\w+)\[{B},(?:{S}|{S - 1}),(?:{V}|{vocab})\]", entry)
    assert logits_sized and set(logits_sized) == {"bf16"}, set(logits_sized)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


def test_dear_step_compiles_for_four_v5e_chips(compiled_kernels, mesh4):
    """A 2-layer full-width GPT-2 `dear` step, lowered from shapes alone on
    the described mesh. Its buckets are padded to XLA:TPU's reduce-scatter
    spans and both legs travel as ``[n / 128, 128]`` (PR 41), so what
    XLA:TPU keeps is what the program asks for: one reduce-scatter and one
    all-gather a bucket, and no all-reduce but the loss's. (A flat bucket
    of any other length compiled to an all-reduce of a padded operand,
    ``from-cross-replica-sharding``, combined with its neighbours'.) The
    default attention core's kernels compile inside the `shard_map`. Not
    asynchronous: a bucket's gathered buffer is sliced into its leaves,
    and this libtpu overlaps an all-gather only where its consumer takes
    the buffer whole; no reduce-scatter is made asynchronous (PERF.md)."""
    model, loss_fn = chip_smoke.make_loss(
        chip_smoke.gpt2_config(jnp.bfloat16, num_layers=2))
    params = jax.eval_shape(
        lambda key, ids: model.init({"params": key}, ids,
                                    train=False)["params"],
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 1024), jnp.int32))
    ts = build_train_step(
        loss_fn, params, mesh=mesh4, mode="dear",
        threshold_mb=chip_smoke.THRESHOLD_MB,
        optimizer=fused_sgd(lr=chip_smoke.LR, momentum=chip_smoke.MOMENTUM),
        comm_dtype=jnp.bfloat16)
    sizes = [b.padded_size for b in ts.plan.buckets]
    assert sizes == [bucket_length(b.size, 4, "tpu")
                     for b in ts.plan.buckets]
    state = DearState(
        buffers=tuple(_on(mesh4, (n,), jnp.float32, P("dp")) for n in sizes),
        opt_state=tuple((_on(mesh4, (n,), jnp.float32, P("dp")),
                         _on(mesh4, (), jnp.bool_, P())) for n in sizes),
        step=_on(mesh4, (), jnp.int32, P()),
    )
    batch = {"input_ids": _on(mesh4, (32, 1024), jnp.int32, P("dp"))}
    lowered = ts.lower(state, batch)
    asked = lowered.as_text()
    assert "stablehlo.reduce_scatter" in asked
    assert "stablehlo.all_gather" in asked
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 4      # 2 layers x (fwd + bwd)
    kept = chip_smoke.count_collectives(text)
    nb = ts.plan.num_buckets
    assert kept == {"all-gather": nb, "reduce-scatter": nb,
                    "all-reduce": 1}, kept
    assert "from-cross-replica-sharding" not in text
    for g in range(nb):
        reduce = [line for line in text.splitlines()
                  if f"/dear/bucket{g}/reduce/" in line
                  and " reduce-scatter(" in line]
        assert len(reduce) == 1, (g, reduce)
        assert f"[{sizes[g] // 4 // 128},128]" in reduce[0]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_one_chip_step_gets_no_spans(one_chip):
    """On one described chip the plan keeps the multiple-of-world padding
    (world 1: none) and the legs their flat form: the one-chip cells'
    programs are the parent's (PR 41; no compiler option anywhere)."""
    from dear_pytorch_tpu.parallel import schedules as S

    params = {"w": jax.ShapeDtypeStruct((1000, 7), jnp.float32),
              "b": jax.ShapeDtypeStruct((7,), jnp.float32)}
    mesh1 = Mesh(np.array(list(one_chip.device_set)), ("dp",))
    ts = build_train_step(lambda p, x: jnp.sum(x @ p["w"] + p["b"]), params,
                          mesh=mesh1, mode="dear")
    assert [b.padded_size for b in ts.plan.buckets] == [7007]
    assert not S.SCHEDULES["dear"](
        mesh=mesh1, world=1, dcn=None, compressor=None,
        comm_dtype=jnp.bfloat16).lane_dense


@pytest.mark.parametrize("mode,build,lane_dense", [
    ("dear", dict(comm_dtype=jnp.bfloat16), True),
    ("fsdp", dict(gather_dtype=jnp.bfloat16), True),
    ("dear", dict(comm_dtype=None), False),              # an f32 wire
    ("dear", dict(comm_dtype=jnp.bfloat16, compressor="eftopk"), False),
    ("dear-fused", dict(comm_dtype=jnp.bfloat16), False),
    ("allreduce", dict(comm_dtype=jnp.bfloat16), False),
    ("rsag", dict(comm_dtype=jnp.bfloat16), False),
])
def test_lane_dense_only_where_the_span_rule_was_read(mesh4, mode, build,
                                                      lane_dense):
    """On the described 2x2 the span padding and the ``[n / 128, 128]``
    legs reach only what the rule was read on (PR 41): the dense
    'dear' / 'fsdp' legs over a bf16 wire. Compressed payloads (whose k is
    a share of the padded length), an f32 wire, the ring kernels and the
    replicated schedules keep the flat plan."""
    from dear_pytorch_tpu.parallel import schedules as S

    kw = dict(dcn=None, compressor=None, comm_dtype=None, gather_dtype=None)
    kw.update(build)
    assert S.SCHEDULES[mode](mesh=mesh4, world=4, **kw).lane_dense \
        is lane_dense


def _expect_refusal(compile_fn, words: str):
    """Run a compile that is expected to be refused; let the refusal
    propagate (the strict xfail records it) only when it is the recorded
    one — a new reason is a plain failure."""
    try:
        compile_fn()
    except Exception as e:
        if words not in str(e):
            pytest.fail(f"refused for a new reason: {e}")
        raise


def _ring(mesh, fn, in_specs, out_specs, *args):
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return lambda: jax.jit(mapped).lower(*args).compile()


# The ring kernels (mode="dear-fused", --ring-projections, ring-TP decode)
# have only ever run in interpret mode. With the collective_id API repair
# made (PR 24), Mosaic refuses each for v5e with the words below, so they
# cannot run on the chip today. strict: the kernel PR that repairs one
# (ROADMAP A6) has to flip its mark.


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "Mosaic: Slice shape along dimension 0 must be aligned to tiling (2), "
    "but is 1 — the (2, n) double-buffered comm scratch is sliced one row "
    "at a time"))
def test_ring_all_gather_compiles_for_v5e(compiled_kernels, mesh4):
    _expect_refusal(
        _ring(mesh4, lambda s: ring_all_gather(s, "dp"), P("dp"), P(),
              _on(mesh4, (BUCKET,), jnp.float32, P("dp"))),
        "must be aligned to tiling (2), but is 1")


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "Mosaic: Slice shape along dimension 0 must be aligned to tiling (4), "
    "but is 1 — the (world, shard) bf16 gradient view is sliced one row "
    "at a time"))
def test_fused_reduce_scatter_update_compiles_for_v5e(compiled_kernels,
                                                      mesh4):
    opt = fused_sgd(lr=0.01, momentum=0.9)

    def fused(g, p, m, seeded):
        return fused_reduce_scatter_update(g, p, (m, seeded), opt, "dp",
                                           mean_world=4)

    _expect_refusal(
        _ring(mesh4, fused, (P(), P("dp"), P("dp"), P()),
              (P("dp"), (P("dp"), P())),
              _on(mesh4, (BUCKET,), jnp.bfloat16, P()),
              _on(mesh4, (BUCKET,), jnp.float32, P("dp")),
              _on(mesh4, (BUCKET,), jnp.float32, P("dp")),
              _on(mesh4, (), jnp.bool_, P())),
        "must be aligned to tiling (4), but is 1")


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "Mosaic: Slice shape along dimension 1 must be aligned to tiling (128), "
    "but is 192 — GPT-2's K=768 over four chips gives 192-column slices"))
def test_allgather_matmul_compiles_for_v5e_at_gpt2_width(compiled_kernels,
                                                         mesh4):
    # QKV projection of GPT-2 124M: [tokens, 768] @ [768, 2304], the
    # weight row-sharded over the four chips
    _expect_refusal(
        _ring(mesh4, lambda x, w: allgather_matmul(x, w, "dp"),
              (P("dp"), P("dp")), P("dp"),
              _on(mesh4, (4 * 8192, 768), jnp.bfloat16, P("dp")),
              _on(mesh4, (768, 2304), jnp.bfloat16, P("dp"))),
        "must be aligned to tiling (128), but is 192")
