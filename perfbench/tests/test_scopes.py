"""The scope join: `instruction_scopes`, `classify`, `step_table` and
`exposed_by_leg` on a made-up text and made-up intervals, the readers on a
made-up run, and on a tiny CPU-compiled dear step that every collective is
named and that little is left unattributed."""

import re

import jax
import pytest

from perfbench import cell as cells
from perfbench import harness, scopes, xplane

J = "jit(device_step)/shard_map/"
TEXT = f"""\
HloModule jit_device_step

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %exp.9 = f32[8]{{0}} exponential(%param_0.1), metadata={{op_name="{J}jvp(GptLmHeadModel)/h_0/attention/softmax/exp" stack_frame_id=3}}
}}

%fused_computation.5 (param_0.2: f32[8]) -> bf16[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %add_any.3 = f32[8]{{0}} add(%param_0.2, %param_0.2), metadata={{op_name="{J}transpose(jvp(loss))/add_any"}}
  %pad.1 = f32[8]{{0}} pad(%add_any.3), metadata={{op_name="{J}transpose(jvp(loss))/pad"}}
  ROOT %convert.4 = bf16[8]{{0}} convert(%pad.1), metadata={{op_name="{J}convert.73"}}
}}

%fc3 (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  %mul.8 = f32[8]{{0}} multiply(%param_0.3, %param_0.3), metadata={{op_name="{J}transpose(jvp(GptLmHeadModel))/h_0/ln_2/mul"}}
  ROOT %dot.5 = f32[8]{{0}} dot(%mul.8, %mul.8), metadata={{op_name="{J}transpose(jvp(GptLmHeadModel))/h_0/mlp/mlp_in/dot_general"}}
}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0), metadata={{op_name="state.buffers[0]"}}
  %all-gather.1 = f32[32]{{0}} all-gather(%p), dimensions={{0}}, metadata={{op_name="{J}dear/bucket0/gather/all_gather"}}
  %fusion.1 = f32[8]{{0}} fusion(%all-gather.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{J}jvp(GptLmHeadModel)/h_0/attention/softmax/exp"}}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fc2, metadata={{op_name="{J}jvp(BertForPreTraining)/layer_3/attention/attention/dropout/mul"}}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kOutput, calls=%fc3, metadata={{op_name="{J}transpose(jvp(GptLmHeadModel))/h_0/mlp/mlp_in/dot_general"}}
  %add_convert_fusion = bf16[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{J}convert.73"}}
  %fusion.4 = f32[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fc4, metadata={{op_name="{J}dear/pack/concatenate"}}
  %copy-start.7 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%fusion.4)
  %copy-done.7 = f32[8]{{0}} copy-done(%copy-start.7)
  %all-reduce.5 = (f32[8]{{0}}, f32[8]{{0}}) all-reduce(%fusion.4, %copy-done.7), to_apply=%add
  %get-tuple-element.1 = f32[8]{{0}} get-tuple-element(%all-reduce.5), index=0
  %fusion.6 = f32[8]{{0}} fusion(%get-tuple-element.1), kind=kLoop, calls=%fc6, metadata={{op_name="{J}dear/bucket3/update/sub"}}
  %all-reduce.8 = f32[8]{{0}} all-reduce(%fusion.6), to_apply=%add
  %fusion.9 = f32[8]{{0}} fusion(%all-reduce.8), kind=kLoop, calls=%fc9, metadata={{op_name="{J}dear/unpack/dynamic_slice"}}
  ROOT %psum.2 = f32[] all-reduce(%fusion.9), to_apply=%add, metadata={{op_name="{J}dear/metrics/psum"}}
}}
"""


def test_instruction_scopes_one_pass_and_unnamed_is_empty():
    names = scopes.instruction_scopes(TEXT)
    assert names["fusion.1"].endswith("h_0/attention/softmax/exp")
    assert names["exp.9"] == names["fusion.1"]          # a ROOT line too
    assert names["p"] == "state.buffers[0]"
    assert names["copy-done.7"] == "" and names["get-tuple-element.1"] == ""
    assert "main.1" not in names and "fused_computation.1" not in names


def test_a_fusion_whose_root_names_no_part_is_named_after_its_members():
    """The logits-gradient fusion of the GPT-2 step is rooted in a convert
    the compiler made (op_name ``…/convert.73``); its members are the loss's
    backward pass (7.3 ms a step in the dp4 trace, PR 29)."""
    names = scopes.instruction_scopes(TEXT)
    assert scopes.classify(names["add_convert_fusion"]) == ("backward", "loss")
    assert scopes.classify(names["convert.4"]) == ("other", "unattributed")
    # a fusion whose root does name a part keeps it
    assert names["fusion.1"].endswith("h_0/attention/softmax/exp")


def test_an_unnamed_collective_is_named_after_its_neighbours():
    """XLA:TPU's combined collectives carry no metadata: the gradient
    all-reduce feeds a bucket's update (through a get-tuple-element), the
    all-reduce that stands in for a gather feeds the unpack."""
    names = scopes.instruction_scopes(TEXT)
    assert names["all-reduce.5"] == "dear/bucket3/reduce/(inferred)"
    assert names["all-reduce.8"] == "dear/gather/(inferred)"
    assert scopes.leg_of(names["all-reduce.5"]) == "reduce"
    assert scopes.leg_of(names["all-reduce.8"]) == "gather"
    assert scopes.leg_of(names["all-gather.1"]) == "gather"
    assert scopes.leg_of(names["psum.2"]) == "other"


@pytest.mark.parametrize("op_name, want", [
    (J + "jvp(GptLmHeadModel)/h_0/attention/scores/bqhd,bkhd->bhqk/dot_general",
     ("forward", "attention")),
    (J + "transpose(jvp(GptLmHeadModel))/h_11/attention/softmax/mul",
     ("backward", "attention")),
    # dropout beats attention, so no op is counted twice
    (J + "jvp(BertForPreTraining)/layer_3/attention/attention/dropout/mul",
     ("forward", "dropout")),
    (J + "transpose(jvp(BertForPreTraining))/layer_3/Dropout_1/mul",
     ("backward", "dropout")),
    # BERT's attention *module* holds the projections; its MLP's second
    # matmul is a Dense called "output" as well
    (J + "jvp(BertForPreTraining)/layer_3/attention/query/dot_general",
     ("forward", "projections")),
    (J + "jvp(BertForPreTraining)/layer_3/attention/output/dot_general",
     ("forward", "projections")),
    (J + "jvp(BertForPreTraining)/layer_3/mlp/output/dot_general",
     ("forward", "mlp")),
    (J + "jvp(GptLmHeadModel)/h_0/mlp/mlp_in/dot_general", ("forward", "mlp")),
    (J + "jvp(GptLmHeadModel)/h_0/ln_1/reduce_sum", ("forward", "layernorm")),
    (J + "jvp(BertForPreTraining)/layer_0/attention_ln/mul",
     ("forward", "layernorm")),
    (J + "jvp(GptLmHeadModel)/wte/jit(_take)/gather", ("forward", "embedding")),
    (J + "jvp(GptLmHeadModel)/wte.attend/dot_general", ("forward", "loss")),
    (J + "jvp(loss)/reduce_max", ("forward", "loss")),
    (J + "transpose(jvp(loss))/jit(take_along_axis)/scatter-add",
     ("backward", "loss")),
    ("jit(f)/jvp(attention)/while/body/mul", ("forward", "attention")),
    (J + "jvp(GptLmHeadModel)/h_0/add", ("forward", "unattributed")),
    (J + "checkpoint/rematted_computation/GptLmHeadModel/h_0/mlp/mul",
     ("backward", "mlp")),
    (J + "dear/bucket12/update/sub", ("update", "update")),
    (J + "dear/clip/sqrt", ("update", "clip")),
    (J + "dear/sdc_fp/reduce_sum", ("update", "sdc_fp")),
    (J + "dear/pack/concatenate", ("schedule", "pack")),
    (J + "dear/unpack/dynamic_slice", ("schedule", "unpack")),
    (J + "dear/bucket0/gather/all_gather", ("schedule", "gather")),
    (J + "dear/bucket0/reduce/reduce_scatter", ("schedule", "reduce")),
    (J + "dear/rng/random_fold_in", ("schedule", "rng")),
    (J + "dear/metrics/psum", ("schedule", "metrics")),
    # fsdp differentiates through the gather: the scope wins over the phase
    (J + "transpose(jvp(dear/bucket1/gather))/reduce_scatter",
     ("schedule", "gather")),
    ("", ("other", "unattributed")),
    ("state.buffers[0]", ("other", "unattributed")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def _op(name, start, end):
    return xplane.Op(name, f"%{name} = f32[] op()", start, end)


def _device(ops, async_ops=(), runs=((0, 100), (100, 200))):
    return xplane.Device(0, tuple(_op("jit_step", *r) for r in runs),
                         tuple(ops), tuple(async_ops))


def test_step_table_sums_to_the_busy_time_of_the_line():
    dev = _device([_op("fusion.1", -5, 10),      # clipped to the window
                   _op("fusion.2", 10, 30), _op("fusion.3", 40, 90),
                   _op("copy-done.7", 90, 96), _op("fusion.6", 100, 130),
                   _op("not-in-the-text", 130, 131),
                   _op("fusion.4", 190, 210)])   # clipped
    table = scopes.step_table(dev, scopes.instruction_scopes(TEXT))
    lo, hi = dev.window
    busy_ns = sum(min(o.end, hi) - max(o.start, lo) for o in dev.ops)
    assert sum(table.values()) == pytest.approx(busy_ns * 1e-6 / 2, rel=1e-12)
    assert table[("forward", "attention")] == pytest.approx(10e-6 / 2)
    assert table[("forward", "dropout")] == pytest.approx(20e-6 / 2)
    assert table[("backward", "mlp")] == pytest.approx(50e-6 / 2)
    assert table[("update", "update")] == pytest.approx(30e-6 / 2)
    assert table[("schedule", "pack")] == pytest.approx(10e-6 / 2)
    assert table[("other", "unattributed")] == pytest.approx(7e-6 / 2)
    assert scopes.total(table, phase="forward") == pytest.approx(30e-6 / 2)
    assert scopes.total(table, parts=("gather",)) is None
    top = scopes.top_unattributed(dev, scopes.instruction_scopes(TEXT))
    assert top[0][:3] == ["other", "copy-done", ""]


def test_straddles_names_the_fusions_that_hold_another_part():
    """The matmul fusion of the MLP's backward pass is rooted in the MLP and
    holds a layernorm instruction: booked whole to ``mlp``, and listed."""
    dev = _device([_op("fusion.1", 0, 10), _op("fusion.3", 40, 90),
                   _op("add_convert_fusion", 90, 95)], runs=((0, 100),))
    assert scopes.straddles(dev, TEXT) == [
        ["mlp", "layernorm", pytest.approx(50e-6)]]


def test_a_container_keeps_only_its_own_time():
    """A ``while`` on the synchronous line spans the operations of its body:
    each instant goes to the innermost operation, and the sum is the line's
    busy time, not more."""
    dev = _device([_op("while.1", 10, 60),           # own: 10-20, 50-60
                   _op("fusion.1", 20, 30), _op("fusion.3", 30, 50),
                   _op("fusion.6", 60, 70)], runs=((0, 100),))
    table = scopes.step_table(dev, scopes.instruction_scopes(TEXT))
    assert table[("other", "unattributed")] == pytest.approx(20e-6)
    assert table[("forward", "attention")] == pytest.approx(10e-6)
    assert table[("backward", "mlp")] == pytest.approx(20e-6)
    assert sum(table.values()) == pytest.approx(60e-6)
    assert sum(table.values()) == pytest.approx(
        xplane.length([(o.start, o.end) for o in dev.ops]) * 1e-6)


def test_exposed_by_leg_books_each_instant_once_and_sums_to_the_total():
    dev = _device(
        ops=[_op("fusion.1", 0, 20),
             _op("all-reduce.5", 20, 30),        # reduce, on the sync line
             _op("fusion.3", 40, 50)],
        async_ops=[_op("all-gather.1", 10, 45),   # gather: 20-40 uncovered,
                                                  # 20-30 of it is reduce's
                   _op("all-reduce.8", 60, 70),   # gather (inferred)
                   _op("psum.2", 80, 81)],        # not a collective by name
        runs=((0, 50), (50, 100)))
    names = scopes.instruction_scopes(TEXT)
    legs = scopes.exposed_by_leg(dev, names)
    assert legs["reduce"] == pytest.approx(10e-6 / 2)
    assert legs["gather"] == pytest.approx((10 + 10) * 1e-6 / 2)
    assert legs["other"] is None
    whole = xplane.length(dev.exposed_collectives()) * 1e-6 / 2
    assert legs["reduce"] + legs["gather"] == pytest.approx(whole)


def test_a_combined_collective_goes_to_one_leg_whole():
    dev = _device(ops=[], async_ops=[_op("all-reduce.5", 10, 30)],
                  runs=((0, 100),))
    legs = scopes.exposed_by_leg(dev, scopes.instruction_scopes(TEXT))
    assert legs == {"reduce": pytest.approx(20e-6), "gather": None,
                    "other": None}


def test_the_readers_on_a_made_up_run_and_on_a_program_without_scopes():
    dev = _device([_op("fusion.1", 0, 10), _op("fusion.2", 10, 30),
                   _op("fusion.3", 40, 90), _op("fusion.4", 90, 95),
                   _op("fusion.6", 100, 130), _op("copy-done.7", 130, 140)],
                  async_ops=[_op("all-gather.1", 95, 100)])
    run = {"trace": xplane.Trace((dev,), ()),
           "built": {"compiled_text": TEXT}}

    def read(name):
        return cells.layer_reader(name)(run)

    assert read("forward_ms") == pytest.approx(15e-6)
    assert read("backward_ms") == pytest.approx(25e-6)
    assert read("attention_core_ms") == pytest.approx(5e-6)
    assert read("dropout_ms") == pytest.approx(10e-6)
    assert read("optimizer_update_ms") == pytest.approx(15e-6)
    assert read("pack_unpack_ms") == pytest.approx(2.5e-6)
    assert read("unattributed_pct") == pytest.approx(100 * 10 / 125)
    assert read("exposed_gather_ms") == pytest.approx(2.5e-6)
    assert read("exposed_reduce_ms") is None     # no collective of that leg
    # the parent's program: no scope of this PR, and nothing raises
    bare = re.sub(r"dear/\w+/|dear/|attention/attention/dropout/"
                  r"|attention/softmax/|mlp/", "", TEXT)
    run["built"]["compiled_text"] = bare
    assert read("forward_ms") and read("backward_ms")
    for name in ("attention_core_ms", "dropout_ms", "optimizer_update_ms",
                 "pack_unpack_ms", "exposed_reduce_ms", "exposed_gather_ms"):
        assert read(name) is None, name
    assert read("unattributed_pct") > 50
    no_collective = _device([_op("fusion.1", 0, 10)])
    run["trace"] = xplane.Trace((no_collective,), ())
    assert read("exposed_reduce_ms") is None
    assert read("exposed_gather_ms") is None


# -- on a compiled step -------------------------------------------------------

#: of the instructions the tiny CPU-compiled dear step runs outside its
#: fusions and that carry an op_name (parameters, constants, tuples and
#: bitcasts left out; XLA:CPU gives a third of its fusions no metadata, which
#: XLA:TPU does not), the share no part of the table claims: residual adds,
#: the step counter, an iota. 7-8% here (PR 29); a table that loses a
#: pattern reads far more.
UNATTRIBUTED_SHARE = 0.15
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


@pytest.mark.parametrize("config_name", ["gpt2-124m", "bert-large"])
def test_tiny_dear_step_names_its_collectives_and_most_of_its_work(
        config_name):
    from dear_pytorch_tpu.comm import backend
    from test_cells_tiny import tiny_cell

    backend.shutdown()
    mesh = backend.init(devices=jax.devices()[:4])
    try:
        built = harness.build(tiny_cell(config_name, 4), mesh, seed=11)
    finally:
        backend.shutdown()
    text = built["compiled_text"]
    names = scopes.instruction_scopes(text)
    outside, computation = [], ""
    for line in text.splitlines():
        if line and not line[0].isspace():
            computation = line
            continue
        m = scopes._INSTRUCTION.match(line)
        if not m or "fused_computation" in computation:
            continue
        opcode = _OPCODE.search(line, m.end())
        if opcode and opcode.group(1) not in _NO_WORK:
            outside.append((m.group(1), opcode.group(1)))
    assert len(outside) > 100
    for name, opcode in outside:
        if xplane._COLLECTIVE.search(opcode):
            phase, part = scopes.classify(names[name])
            assert (phase, part) in {("schedule", "gather"),
                                     ("schedule", "reduce"),
                                     ("schedule", "metrics")}, (name, names[name])
    everywhere = {scopes.classify(op_name) for op_name in names.values()}
    for want in (("forward", "attention"), ("backward", "attention"),
                 ("forward", "mlp"), ("forward", "loss"),
                 ("schedule", "pack"), ("schedule", "unpack"),
                 ("update", "update")):
        assert want in everywhere, want
    if config_name == "bert-large":
        assert ("forward", "dropout") in everywhere
    parts = [scopes.classify(names[name])[1] for name, _ in outside
             if names[name]]
    share = parts.count(scopes.UNATTRIBUTED) / len(parts)
    print(f"unattributed: {share:.3f} of {len(parts)} named instructions "
          f"({len(outside) - len(parts)} carry no op_name)")
    assert share < UNATTRIBUTED_SHARE, share
