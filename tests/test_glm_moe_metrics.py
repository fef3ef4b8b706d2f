"""The sparse decoder's named scopes as `perfbench/scopes.py` (unedited)
books them, and each new per-layer reader on a made-up run."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cell as cells
from perfbench import moe_scopes, scopes, xplane

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "glm_moe.py")
F = "jit(device_step)/shard_map/jvp(GlmMoeLmHeadModel)/"
B = "jit(device_step)/shard_map/transpose(jvp(GlmMoeLmHeadModel))/"


@pytest.mark.parametrize("op_name,want", [
    (F + "h_0/ln_1/mul", ("forward", "layernorm")),
    (F + "h_0/query/q_down/dot_general", ("forward", "projections")),
    (F + "h_0/query/q_ln/rsqrt", ("forward", "projections")),
    (F + "h_3/key/kv_ln/mul", ("forward", "projections")),
    (F + "h_3/key/concatenate", ("forward", "projections")),
    (B + "h_3/value/v_up/dot_general", ("backward", "projections")),
    (F + "h_3/output/dot_general", ("forward", "projections")),
    (F + "h_3/attention/pallas_call", ("forward", "attention")),
    (B + "h_3/attention/pallas_call", ("backward", "attention")),
    (F + "h_0/mlp/mlp_gate/dot_general", ("forward", "mlp")),
    (F + "h_1/mlp/moe/route/top_k", ("forward", "mlp")),
    (B + "h_1/mlp/moe/experts/ragged_dot", ("backward", "mlp")),
    (F + "h_1/mlp/shared/shared_up/dot_general", ("forward", "mlp")),
    (F + "ln_f/mul", ("forward", "layernorm")),
    (F + "loss/lm_head/dot_general", ("forward", "loss")),
    (B.replace("GlmMoeLmHeadModel", "loss") + "reduce_sum",
     ("backward", "loss")),
    (F + "wte/take", ("forward", "embedding")),
    (F + "mtp/input_embeddings/mtp_eh_proj/dot_general",
     ("forward", "embedding")),
    (F + "mtp/input_embeddings/ln_mtp_h/mul", ("forward", "layernorm")),
    (F + "mtp/mtp_block/mlp/moe/combine/mul", ("forward", "mlp")),
    (F + "mtp/mtp_block/query/q_up/dot_general", ("forward", "projections")),
    (F + "mtp/loss/lm_head/dot_general", ("forward", "loss")),
    (F + "mtp/ln_mtp_f/mul", ("forward", "layernorm")),
])
def test_the_unedited_scope_table_books_the_new_program(op_name, want):
    assert scopes.classify(op_name) == want


@pytest.mark.parametrize("pattern,op_name,want", [
    (moe_scopes.ROUTED, F + "h_1/mlp/moe/route/top_k", True),
    (moe_scopes.ROUTED, B + "h_1/mlp/moe/combine/mul", True),
    (moe_scopes.ROUTED, F + "h_1/mlp/shared/shared_up/dot_general", False),
    (moe_scopes.ROUTED, F + "h_1/mlp/moe/assignments", False),
    (moe_scopes.EXPERTS, B + "mtp/mtp_block/mlp/moe/experts/ragged_dot", True),
    (moe_scopes.EXPERTS, F + "h_1/mlp/moe/dispatch/sort", False),
    (moe_scopes.MTP, F + "mtp/loss/lm_head/dot_general", True),
    (moe_scopes.MTP, "jit(s)/transpose(jvp(mtp))/loss/mul", True),
    (moe_scopes.MTP, F + "mtp_block/query/q_up/dot_general", False),
    (moe_scopes.MTP, F + "h_0/mlp/mlp_gate/dot_general", False),
])
def test_the_finer_scope_patterns(pattern, op_name, want):
    assert bool(pattern.search(op_name)) == want


def _line(name, op_name):
    return (f'  %{name} = f32[8]{{0}} custom-call(%p), metadata='
            f'{{op_name="{op_name}"}}\n')


OPS = {   # instruction: (op_name, ns in each of the two steps)
    "route.1": (F + "h_1/mlp/moe/route/top_k", 10),
    "sort.1": (F + "h_1/mlp/moe/dispatch/sort", 20),
    "ragged.1": (F + "h_1/mlp/moe/experts/ragged_dot", 100),
    "ragged.2": (B + "h_1/mlp/moe/experts/ragged_dot", 200),
    "combine.1": (B + "mtp/mtp_block/mlp/moe/combine/mul", 30),
    "shared.1": (F + "h_1/mlp/shared/shared_up/dot_general", 40),
    "q_up.1": (F + "h_1/query/q_up/dot_general", 50),
    "out.1": (B + "mtp/mtp_block/output/dot_general", 60),
    "head.2": (F + "mtp/loss/lm_head/dot_general", 70),
    "flash.1": (F + "h_1/attention/pallas_call", 80),
}
TEXT = ("HloModule jit_device_step\n\nENTRY %main.1 (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        + "".join(_line(n, op) for n, (op, _) in OPS.items()) + "}\n")
#: XLA:TPU's grouped-matmul kernels carry their own name and no scope: one
#: fed by layer 1's dispatch (forward), one (without any metadata) feeding
#: the prediction module's backward dispatch
UNNAMED = (
    "  %ragged-dot-none.3 = f32[8]{0} custom-call(%gte.1, %sort.1), "
    'custom_call_target="tpu_custom_call", '
    'metadata={op_name="ragged-dot-none"}\n'
    "  %ragged-dot-none = f32[8]{0} custom-call(%gte.1, %p), "
    'custom_call_target="tpu_custom_call"\n'
    + _line("gather.9", B + "mtp/mtp_block/mlp/moe/dispatch/gather").replace(
        "(%p)", "(%ragged-dot-none)")
    + "  %ragged-dot-none.7 = f32[8]{0} custom-call(%p), "
    'custom_call_target="tpu_custom_call"\n')


def _run(text=TEXT, counts=None):
    ops, at = [], 0
    for step in range(2):
        for name, (_, ns) in OPS.items():
            ops.append(xplane.Op(name, f"%{name} = f32[] op()", at, at + ns))
            at += ns
    runs = tuple(xplane.Op("jit_step", "%jit_step = f32[] op()", lo, hi)
                 for lo, hi in ((0, at // 2), (at // 2, at)))
    model = cells.load_json(
        ROOT / "perfbench/configs/glm-4.7-flash-ep8.json")
    cell = cells.Cell(name="x", chips=1, config_name="glm", config=model,
                      family=FAM, traffic={}, end_to_end=(), per_layer=())
    run = {"trace": xplane.Trace((xplane.Device(0, runs, tuple(ops), ()),),
                                 ()),
           "built": {"compiled_text": text}, "cell": cell,
           "peaks": {"bf16_flops_per_s": 197e12}}
    if counts is not None:
        run["routing_counts"] = np.asarray(counts, np.float32)
    return run


READERS = {name: cells.layer_reader(name) for name in (
    "moe_routed_ms", "moe_dispatch_ms", "expert_matmul_flops_util_pct",
    "latent_projection_ms", "mtp_ms", "expert_load_max_over_mean")}


@pytest.mark.parametrize("name,want_ns", [
    ("moe_routed_ms", 10 + 20 + 100 + 200 + 30),
    ("moe_dispatch_ms", 10 + 20 + 30),
    ("latent_projection_ms", 50 + 60),
    ("mtp_ms", 30 + 60 + 70),
])
def test_a_time_reader_on_a_made_up_run(name, want_ns):
    assert READERS[name](_run()) == pytest.approx(want_ns * 1e-6)


def test_the_unnamed_grouped_matmul_kernels_take_their_layers_scope():
    text = TEXT.replace("}\n", UNNAMED + "}\n")
    names = moe_scopes.instruction_scopes(text)
    assert names["ragged-dot-none.3"] == (
        F + "h_1/mlp/moe/experts/ragged_dot(inferred)")
    assert names["ragged-dot-none"] == (
        B + "mtp/mtp_block/mlp/moe/experts/ragged_dot(inferred)")
    assert names["ragged-dot-none.7"] == ""       # no moe neighbour: unnamed
    assert (scopes.instruction_scopes(text)["ragged-dot-none.3"]
            == "ragged-dot-none")
    run = _run(text=text)
    dev = run["trace"].devices[0]
    at = dev.window[1]
    extra = (xplane.Op("ragged-dot-none.3", "%x = f32[] op()", at, at + 500),
             xplane.Op("ragged-dot-none", "%x = f32[] op()", at + 500,
                       at + 800))
    runs = dev.modules[:-1] + (xplane.Op(
        "jit_step", "%jit_step = f32[] op()", dev.modules[-1].start,
        at + 800),)
    run["trace"] = xplane.Trace(
        (xplane.Device(0, runs, dev.ops + extra, ()),), ())
    per_step = 800 * 1e-6 / 2
    assert READERS["moe_routed_ms"](run) == pytest.approx(
        360e-6 + per_step)
    assert READERS["moe_dispatch_ms"](run) == pytest.approx(60e-6)
    assert READERS["mtp_ms"](run) == pytest.approx(160e-6 + 300e-6 / 2)


def test_the_roofline_share_and_the_imbalance_on_a_made_up_run():
    counts = [[500, 540, 480, 520, 510, 530, 490, 526],
              [400, 700, 512, 512, 512, 512, 512, 436]]
    run = _run(counts=counts)
    flops = 6 * 3 * 2048 * 1536 * float(np.sum(counts))
    assert READERS["expert_matmul_flops_util_pct"](run) == pytest.approx(
        100 * flops / (300e-9 * 197e12))
    assert READERS["expert_load_max_over_mean"](run) == pytest.approx(
        700 / 512)
    # an even load reads 1
    assert READERS["expert_load_max_over_mean"](
        _run(counts=np.full((2, 8), 512))) == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's program has no such scope and no such counter: the
    reader returns None and does not raise."""
    text = TEXT.replace("/moe/", "/dense/").replace("mtp/", "x/").replace(
        "/query/", "/q/").replace("/output/", "/o/")
    run = _run(text=text)
    run["routing_counts"] = None
    assert READERS[name](run) is None


def test_the_routing_counter_through_the_program_api():
    """`moe_scopes.routing_counts` on a tiny built step: the counter of the
    weights the state holds, by the family's `expert_assignments`."""
    from dear_pytorch_tpu.comm import backend
    from perfbench import harness
    from test_glm_moe import TINY

    backend.shutdown()
    mesh = backend.init(devices=jax.devices()[:1])
    try:
        config = cells.load_json(
            ROOT / "perfbench/configs/glm-4.7-flash-ep8.json")
        config["model"] = {**config["model"], **TINY}
        cell = cells.Cell(
            name="glm.tiny", chips=1, config_name="glm", config=config,
            family=FAM, end_to_end=(), per_layer=(),
            traffic={"seq_len": 16, "batch_per_chip": 2, "chips": 1,
                     "mode": "dear"})
        built = harness.build(cell, mesh, seed=5)
        run = {"cell": cell, "built": built}
        counts = moe_scopes.routing_counts(run)
        assert counts.shape == (3, 4) and counts.sum() > 0
        assert moe_scopes.routing_counts(run) is counts       # kept
        cfg = FAM.model_config(config["model"], jnp.bfloat16)
        # (jitted as the helper jits it: a bf16 near-tie may fall the other
        # way in another program)
        want = jax.jit(lambda p, b: FAM.expert_assignments(cfg, p, b))(
            built["ts"].gather_params(built["state"]), built["batch"])
        np.testing.assert_array_equal(counts, np.asarray(want))
        # the compiled step carries every scope the readers look for
        names = set(scopes.instruction_scopes(built["compiled_text"])
                    .values())
        for pattern in (moe_scopes.ROUTED, moe_scopes.EXPERTS,
                        moe_scopes.MTP):
            assert any(pattern.search(n) for n in names)
            assert any(pattern.search(n) and "transpose(jvp(" in n
                       for n in names)
        parts = {scopes.classify(n) for n in names}
        for want_part in ("projections", "attention", "mlp", "loss",
                          "layernorm", "embedding"):
            assert ("forward", want_part) in parts, want_part
    finally:
        backend.shutdown()
