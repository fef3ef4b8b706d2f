"""`dropout_kernel_calls_per_step` on compiled-step texts with kernels that
draw a dropout mask, with plain kernels only, and with none."""

from perfbench import cell

CALL = ('  %{name}.{n} = bf16[16,512,1024]{{2,1,0}} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}')
FWD = ("jit(device_step)/jvp(BertForPreTraining)/layer_{i}/attention/"
       "attention/jit(_fwd_call)/{kernel}pallas_call")
BWD = ("jit(device_step)/transpose(jvp(BertForPreTraining))/layer_{i}/"
       "attention/attention/jit(_bwd_call)/{kernel}pallas_call")


def _read(text, metric="dropout_kernel_calls_per_step"):
    return cell.layer_reader(metric)({"built": {"compiled_text": text}})


def _kernels(layers, dropout):
    fwd = "flash_fwd_dropout/" if dropout else ""
    bwd = "flash_bwd_dropout/" if dropout else ""
    lines = [CALL.format(name=fwd[:-1] or "custom-call", n=i,
                         op=FWD.format(i=i, kernel=fwd))
             for i in range(layers)]
    lines += [CALL.format(name=bwd[:-1] or "custom-call", n=100 + i,
                          op=BWD.format(i=i, kernel=bwd))
              for i in range(layers)]
    return "\n".join(lines)


def test_counts_the_kernels_that_draw_a_mask():
    text = _kernels(3, dropout=True)
    assert _read(text) == 6.0
    # they are attention kernels too
    assert _read(text, "attention_kernel_calls_per_step") == 6.0


def test_plain_kernels_are_not_counted():
    """Kernels without dropout (both GPT cells, the GLM cell), a dropout
    fusion that is no kernel, and a dropout kernel outside attention."""
    text = "\n".join([
        _kernels(2, dropout=False),
        '  %fusion.1 = bf16[8] fusion(%x), kind=kLoop, metadata={op_name='
        '"jit(device_step)/jvp(M)/layer_0/attention/attention/dropout/mul"}',
        CALL.format(name="mlp_dropout", n=7,
                    op="jit(device_step)/jvp(M)/layer_0/mlp/mlp_dropout/"
                       "pallas_call"),
    ])
    assert _read(text) is None
    assert _read(text, "attention_kernel_calls_per_step") == 4.0


def test_a_dense_program_reports_nothing():
    assert _read("ENTRY %main { ROOT %r = f32[] constant(0) }") is None
