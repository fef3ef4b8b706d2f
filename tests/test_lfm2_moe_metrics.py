"""The hybrid decoder's named scopes as `perfbench/scopes.py` (unedited)
books them, and each new per-layer reader on a made-up run."""

import pathlib

import jax
import numpy as np
import pytest

from perfbench import cell as cells
from perfbench import moe_scopes, scopes, xplane

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "lfm2_moe.py")
F = "jit(device_step)/shard_map/jvp(Lfm2MoeLmHeadModel)/"
B = "jit(device_step)/shard_map/transpose(jvp(Lfm2MoeLmHeadModel))/"


@pytest.mark.parametrize("op_name,want", [
    (F + "h_0/ln_1/mul", ("forward", "layernorm")),
    (F + "h_1/query/q_proj/dot_general", ("forward", "projections")),
    (F + "h_1/query/q_ln/rsqrt", ("forward", "projections")),
    (B + "h_1/key/k_ln/mul", ("backward", "projections")),
    (F + "h_1/key/concatenate", ("forward", "projections")),
    (B + "h_1/value/v_proj/dot_general", ("backward", "projections")),
    (F + "h_1/output/dot_general", ("forward", "projections")),
    (F + "h_1/attention/pallas_call", ("forward", "attention")),
    (B + "h_1/attention/pallas_call", ("backward", "attention")),
    (F + "h_0/mlp/mlp_gate/dot_general", ("forward", "mlp")),
    (F + "h_2/mlp/moe/route/top_k", ("forward", "mlp")),
    (B + "h_2/mlp/moe/experts/ragged_dot", ("backward", "mlp")),
    (F + "ln_f/mul", ("forward", "layernorm")),
    (F + "loss/wte.attend/dot_general", ("forward", "loss")),
    (B.replace("Lfm2MoeLmHeadModel", "loss") + "reduce_sum",
     ("backward", "loss")),
    (F + "wte/take", ("forward", "embedding")),
    # `_PARTS` has no part for the short convolution: its operator is booked
    # as unattributed (PERF.md section 7 asks a later PR for the part)
    (F + "h_0/conv/in_proj/dot_general", ("forward", scopes.UNATTRIBUTED)),
    (B + "h_0/conv/filter/mul", ("backward", scopes.UNATTRIBUTED)),
])
def test_the_unedited_scope_table_books_the_new_program(op_name, want):
    assert scopes.classify(op_name) == want


READERS = {name: cells.layer_reader(name) for name in (
    "short_conv_ms", "short_conv_filter_ms", "gqa_attention_flops_util_pct")}
CONV = cells.load_py(ROOT / "perfbench/layer_metrics/short_conv_ms.py").CONV
FILTER = cells.load_py(
    ROOT / "perfbench/layer_metrics/short_conv_filter_ms.py").FILTER


@pytest.mark.parametrize("pattern,op_name,want", [
    (CONV, F + "h_0/conv/in_proj/dot_general", True),
    (CONV, B + "h_4/conv/filter/mul", True),
    (CONV, B + "h_4/conv/out_proj/transpose", True),
    (CONV, "jit(s)/transpose(jvp(conv))/filter/mul", True),
    (CONV, F + "h_1/query/q_proj/dot_general", False),
    # XLA's own names are no scope of the program
    (CONV, F + "h_1/conv_general_dilated", False),
    (CONV, F + "h_1/mlp/convert_element_type", False),
    (FILTER, F + "h_0/conv/filter/pad", True),
    (FILTER, F + "h_0/conv/in_proj/dot_general", False),
    (FILTER, F + "h_0/filter/mul", False),
])
def test_the_convolution_scope_patterns(pattern, op_name, want):
    assert bool(pattern.search(op_name)) == want


def _line(name, op_name):
    return (f'  %{name} = f32[8]{{0}} custom-call(%p), metadata='
            f'{{op_name="{op_name}"}}\n')


OPS = {   # instruction: (op_name, ns in each of the two steps)
    "in.1": (F + "h_0/conv/in_proj/dot_general", 100),
    "filter.1": (F + "h_0/conv/filter/mul", 20),
    "out.1": (F + "h_0/conv/out_proj/dot_general", 40),
    "filter.2": (B + "h_2/conv/filter/pad", 30),
    "in.2": (B + "h_2/conv/in_proj/transpose", 150),
    "flash.1": (F + "h_1/attention/pallas_call", 300),
    "flash.2": (B + "h_1/attention/pallas_call", 700),
    "q.1": (F + "h_1/query/q_proj/dot_general", 50),
    "gate.1": (F + "h_0/mlp/mlp_gate/dot_general", 60),
}
TEXT = ("HloModule jit_device_step\n\nENTRY %main.1 (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        + "".join(_line(n, op) for n, (op, _) in OPS.items()) + "}\n")


def _run(text=TEXT, family=FAM):
    ops, at = [], 0
    for step in range(2):
        for name, (_, ns) in OPS.items():
            ops.append(xplane.Op(name, f"%{name} = f32[] op()", at, at + ns))
            at += ns
    runs = tuple(xplane.Op("jit_step", "%jit_step = f32[] op()", lo, hi)
                 for lo, hi in ((0, at // 2), (at // 2, at)))
    config = cells.load_json(ROOT / "perfbench/configs/lfm2-8b-a1b-ep4.json")
    traffic = cells.load_json(ROOT / "perfbench/traffic/s8192.json")
    cell = cells.Cell(name="x", chips=1, config_name="lfm2", config=config,
                      family=family, traffic=traffic, end_to_end=(),
                      per_layer=())
    return {"trace": xplane.Trace((xplane.Device(0, runs, tuple(ops), ()),),
                                  ()),
            "built": {"compiled_text": text}, "cell": cell,
            "peaks": {"bf16_flops_per_s": 197e12}}


@pytest.mark.parametrize("name,want_ns", [
    ("short_conv_ms", 100 + 20 + 40 + 30 + 150),
    ("short_conv_filter_ms", 20 + 30),
])
def test_a_time_reader_on_a_made_up_run(name, want_ns):
    assert READERS[name](_run()) == pytest.approx(want_ns * 1e-6)


def test_a_fusion_named_after_its_members_is_still_the_convolutions():
    """XLA fuses the RMSNorm into the ``in_proj`` matmul that follows it;
    `scopes.instruction_scopes` then names that fusion after its members
    (``conv`` is no part of its table). The readers go by the root."""
    ln = F + "h_0/ln_1/mul"
    fused = ("%fused.1 (a: f32[8]) -> f32[8] {\n" + _line("m.1", ln)
             + _line("m.2", ln) + _line("m.3", ln) + "}\n\n")
    call = ('  %in.1 = f32[8]{0} fusion(%p), kind=kOutput, calls=%fused.1, '
            f'metadata={{op_name="{OPS["in.1"][0]}"}}\n')
    text = fused + TEXT.replace(_line("in.1", OPS["in.1"][0]), call)
    assert scopes.instruction_scopes(text)["in.1"] == ln     # the repair
    assert READERS["short_conv_ms"](_run(text=text)) == pytest.approx(
        340e-6)
    assert moe_scopes.ms_under(_run(text=text), CONV) == pytest.approx(
        240e-6)                       # what the shared join would read


def test_the_attention_cores_share_of_its_roofline_on_a_made_up_run():
    """One attention layer's causal triangle at (1, 8192, 32, 64), two
    matmuls forward and four back, over the time under ``attention``."""
    flops = 6 * 1 * 32 * 8192 ** 2 * 64
    assert flops == pytest.approx(0.8246e12, rel=1e-3)
    assert READERS["gqa_attention_flops_util_pct"](_run()) == pytest.approx(
        100 * flops / (1000e-9 * 197e12))
    # at the predicted 16-20 ms it reads a fifth to a quarter of the peak:
    # the share cannot pass 100 unless the core ran under 4.2 ms
    assert 100 * flops / (18e-3 * 197e12) == pytest.approx(23.3, abs=0.1)
    assert flops / 197e12 == pytest.approx(4.186e-3, rel=1e-3)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's program has no such scope: the reader returns None and
    does not raise."""
    text = TEXT.replace("/conv/", "/mixer/").replace("/attention/", "/core/")
    assert READERS[name](_run(text=text)) is None


def test_a_family_without_the_flops_function_reads_nothing():
    glm = cells.load_py(ROOT / "perfbench/families/glm_moe.py")
    assert not hasattr(glm, "attention_core_flops")
    assert READERS["gqa_attention_flops_util_pct"](_run(family=glm)) is None


def test_the_compiled_step_carries_every_scope_the_readers_look_for():
    from dear_pytorch_tpu.comm import backend
    from perfbench import harness
    from test_lfm2_moe import TINY

    backend.shutdown()
    mesh = backend.init(devices=jax.devices()[:1])
    try:
        config = cells.load_json(
            ROOT / "perfbench/configs/lfm2-8b-a1b-ep4.json")
        config["model"] = {**config["model"], **TINY}
        cell = cells.Cell(
            name="lfm2.tiny", chips=1, config_name="lfm2", config=config,
            family=FAM, end_to_end=(), per_layer=(),
            traffic={"seq_len": 16, "batch_per_chip": 2, "chips": 1,
                     "mode": "dear"})
        built = harness.build(cell, mesh, seed=5)
        run = {"cell": cell, "built": built}
        counts = moe_scopes.routing_counts(run)
        assert counts.shape == (4, 4) and counts.sum() > 0
        names = set(scopes.instruction_scopes(built["compiled_text"])
                    .values())
        for pattern in (CONV, FILTER, moe_scopes.ROUTED, moe_scopes.EXPERTS):
            assert any(pattern.search(n) for n in names)
            assert any(pattern.search(n) and "transpose(jvp(" in n
                       for n in names)
        for inner in ("in_proj", "filter", "out_proj"):
            assert any(f"/conv/{inner}/" in n for n in names), inner
        parts = {scopes.classify(n) for n in names}
        for want_part in ("projections", "attention", "mlp", "loss",
                          "layernorm", "embedding"):
            assert ("forward", want_part) in parts, want_part
        # the family's assignments feed the accepted roofline reader
        share = cells.layer_reader("expert_load_max_over_mean")(run)
        assert share >= 1.0
        assert np.isfinite(share)
    finally:
        backend.shutdown()
