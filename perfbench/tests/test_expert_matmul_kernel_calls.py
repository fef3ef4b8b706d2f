"""`expert_matmul_kernel_calls_per_step` on compiled-step texts with the
grouped-matmul kernels of an expert layer, with other kernels only (the row
kernels, the flash kernels, XLA's own grouped matmul), and with none."""

from perfbench import cell

CALL = ('  %{name}.{n} = bf16[32768,2048]{{1,0}} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}')
FWD = "jit(device_step)/jvp(GlmMoeLmHeadModel)/layer_{i}/mlp/moe/"
BWD = "jit(device_step)/transpose(jvp(GlmMoeLmHeadModel))/layer_{i}/mlp/moe/"
#: what else a sparse cell's step holds: a row kernel, a flash kernel, and
#: XLA:TPU's grouped matmul, whose ``op_name`` is its own name or absent
OTHERS = [
    CALL.format(name="moe_spread_rows", n=90, op=FWD.format(i=1)
                + "dispatch/jit(_spread_call)/moe_spread_rows/pallas_call"),
    CALL.format(name="moe_combine_rows", n=91, op=BWD.format(i=1)
                + "dispatch/jit(_combine_call)/moe_combine_rows/pallas_call"),
    CALL.format(name="flash_fwd", n=92, op=FWD.format(i=1).replace(
        "mlp/moe/", "attention/attention/flash_fwd/pallas_call")),
    CALL.format(name="ragged-dot-none", n=93, op="ragged-dot-none"),
    '  %ragged-dot-none.94 = bf16[32768,3072]{1,0} custom-call(%a, %b, %c), '
    'custom_call_target="tpu_custom_call"',
]


def _read(text, metric="expert_matmul_kernel_calls_per_step"):
    return cell.layer_reader(metric)({"built": {"compiled_text": text}})


def _layer(i):
    """The six kernels `RoutedExperts` holds on the TPU, each behind the
    inner jit that shares its trace across layers."""
    return [CALL.format(name=body, n=6 * i + n, op=side.format(i=i)
                        + f"experts/jit({fn})/{body}/pallas_call")
            for n, (side, fn, body) in enumerate([
                (FWD, "_gate_up", "grouped_gate_up"),
                (FWD, "_matmul", "grouped_matmul"),
                (BWD, "_act_grad", "grouped_act_grad"),
                (BWD, "_matmul", "grouped_matmul"),
                (BWD, "_weight_grad", "grouped_weight_grad"),
                (BWD, "_weight_grad", "grouped_weight_grad")])]


def test_counts_six_kernels_an_expert_layer():
    text = "\n".join(line for i in range(1, 6) for line in _layer(i))
    assert _read(text) == 30.0
    assert _read("\n".join(line for i in range(1, 5) for line in _layer(i)
                           ) + "\n" + "\n".join(OTHERS)) == 24.0
    # they are neither row kernels nor attention kernels
    assert _read(text, "moe_row_kernel_calls_per_step") is None
    assert _read(text, "attention_kernel_calls_per_step") is None


def test_only_kernels_under_the_experts_scope_count():
    """Kernels under ``moe/dispatch`` and ``attention``, XLA's unscoped
    ``ragged-dot-none`` (named or not), a fusion under ``moe/experts`` that
    is no kernel, and a kernel of an ``experts`` scope outside ``moe``."""
    text = "\n".join(OTHERS + [
        '  %fusion.3 = bf16[32768,1536] fusion(%x), kind=kLoop, metadata={'
        'op_name="' + FWD.format(i=1) + 'experts/mul"}',
        CALL.format(name="other", n=4,
                    op="jit(device_step)/jvp(M)/experts/other/pallas_call"),
    ])
    assert _read(text) is None
    assert _read(text, "moe_row_kernel_calls_per_step") == 2.0


def test_a_program_on_ragged_dot_reports_nothing():
    assert _read("ENTRY %main { ROOT %r = f32[] constant(0) }") is None
