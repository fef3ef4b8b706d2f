"""`moe_row_kernel_calls_per_step` on compiled-step texts with the row
kernels of an expert layer, with other kernels only, and with none."""

from perfbench import cell

CALL = ('  %{name}.{n} = bf16[32768,2048]{{1,0}} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}')
FWD = "jit(device_step)/jvp(GlmMoeLmHeadModel)/layer_{i}/mlp/moe/"
BWD = "jit(device_step)/transpose(jvp(GlmMoeLmHeadModel))/layer_{i}/mlp/moe/"


def _read(text, metric="moe_row_kernel_calls_per_step"):
    return cell.layer_reader(metric)({"built": {"compiled_text": text}})


def _layer(i):
    """The four kernels `RoutedExperts` holds on the TPU: each body's
    backward is the other body, under the forward's scope."""
    return [CALL.format(name=body, n=4 * i + n, op=side.format(i=i) + scope
                        + f"/{body}/pallas_call")
            for n, (side, scope, body) in enumerate([
                (FWD, "dispatch", "moe_spread_rows"),
                (FWD, "combine", "moe_combine_rows"),
                (BWD, "combine", "moe_spread_rows"),
                (BWD, "dispatch", "moe_combine_rows")])]


def test_counts_four_kernels_an_expert_layer():
    text = "\n".join(line for i in range(1, 6) for line in _layer(i))
    assert _read(text) == 20.0
    # they are no attention kernels
    assert _read(text, "attention_kernel_calls_per_step") is None


def test_other_kernels_and_gathers_are_not_counted():
    """The flash kernels, XLA's grouped matmul under ``moe/experts``, a
    gather fusion under ``moe/dispatch`` that is no kernel, and a kernel of
    a ``dispatch`` scope outside ``moe``."""
    text = "\n".join([
        CALL.format(name="flash_fwd", n=1, op=FWD.format(i=1).replace(
            "mlp/moe/", "attention/attention/flash_fwd/pallas_call")),
        CALL.format(name="ragged-dot", n=2,
                    op=FWD.format(i=1) + "experts/ragged_dot"),
        '  %fusion.3 = bf16[32768,2048] fusion(%x), kind=kLoop, metadata={'
        'op_name="' + FWD.format(i=1) + 'dispatch/gather"}',
        CALL.format(name="other", n=4,
                    op="jit(device_step)/jvp(M)/dispatch/other/pallas_call"),
    ])
    assert _read(text) is None


def test_a_program_of_gathers_reports_nothing():
    assert _read("ENTRY %main { ROOT %r = f32[] constant(0) }") is None
