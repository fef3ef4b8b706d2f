"""`loss_ms` on made-up traced runs: a step whose text has a ``loss`` part
(head matmul, the cross-entropy's reduction, the backward fusion the
compiler rooted in its own convert) and one that has none."""

import pytest

from perfbench import cell, xplane

J = "jit(device_step)/shard_map/"
WITH_LOSS = f"""\
HloModule jit_device_step

%fc5 (param_0.2: f32[8]) -> bf16[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %mul.3 = f32[8]{{0}} multiply(%param_0.2, %param_0.2), metadata={{op_name="{J}transpose(jvp(loss))/mul"}}
  ROOT %convert.4 = bf16[8]{{0}} convert(%mul.3), metadata={{op_name="{J}convert.73"}}
}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0), metadata={{op_name="state.buffers[0]"}}
  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kOutput, calls=%fc1, metadata={{op_name="{J}jvp(GptLmHeadModel)/h_0/mlp/mlp_in/dot_general"}}
  %fusion.2 = bf16[8]{{0}} fusion(%fusion.1), kind=kOutput, calls=%fc2, metadata={{op_name="{J}jvp(GptLmHeadModel)/wte.attend/dot_general"}}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fc3, metadata={{op_name="{J}jvp(loss)/reduce_sum"}}
  %fusion.4 = bf16[8]{{0}} fusion(%fusion.3), kind=kLoop, calls=%fc5, metadata={{op_name="{J}convert.73"}}
  ROOT %fusion.5 = f32[8]{{0}} fusion(%fusion.4), kind=kOutput, calls=%fc6, metadata={{op_name="{J}transpose(jvp(GptLmHeadModel))/wte.attend/dot_general"}}
}}
"""
WITHOUT = WITH_LOSS.replace("wte.attend", "head").replace("(loss)", "(f)")


def _op(name, start, end):
    return xplane.Op(name, f"%{name} = f32[] op()", start, end)


def _read(text):
    ops = [_op("fusion.1", 0, 50), _op("fusion.2", 50, 70),
           _op("fusion.3", 70, 74), _op("fusion.4", 100, 108),
           _op("fusion.5", 108, 148)]
    dev = xplane.Device(0, (_op("jit_step", 0, 100), _op("jit_step", 100, 200)),
                        tuple(ops), ())
    run = {"trace": xplane.Trace((dev,), ()), "built": {"compiled_text": text}}
    return cell.layer_reader("loss_ms")(run)


def test_sums_head_and_cross_entropy_forward_and_backward_per_step():
    # 20 + 4 forward, 8 + 40 backward, over the trace's two steps
    assert _read(WITH_LOSS) == pytest.approx((20 + 4 + 8 + 40) * 1e-6 / 2)


def test_a_program_without_the_scope_reports_nothing():
    assert _read(WITHOUT) is None
