"""`attention_kernel_calls_per_step` on compiled-step texts with and without
Pallas kernels under an ``attention`` scope."""

from perfbench import cell

CALL = ('  %custom-call.{n} = bf16[16,1024,768]{{2,1,0}} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}')
FWD = "jit(device_step)/jvp(GptLmHeadModel)/h_{i}/attention/jit(_fwd_call)/pallas_call"
BWD = ("jit(device_step)/shard_map/transpose(jvp(GptLmHeadModel))/h_{i}/"
       "attention/jit(_{leg}_call)/pallas_call")


def _read(text):
    reader = cell.layer_reader("attention_kernel_calls_per_step")
    return reader({"built": {"compiled_text": text}})


def test_counts_forward_and_backward_kernels_under_attention():
    lines = [CALL.format(n=i, op=FWD.format(i=i)) for i in range(2)]
    lines += [CALL.format(n=10 + 2 * i + j, op=BWD.format(i=i, leg=leg))
              for i in range(2) for j, leg in enumerate(("dq", "dkv"))]
    assert _read("\n".join(lines)) == 6.0


def test_other_kernels_and_other_attention_ops_are_not_counted():
    text = "\n".join([
        # a Pallas kernel outside any attention scope (a ring matmul, say)
        CALL.format(n=0, op="jit(device_step)/dear/bucket0/gather/pallas_call"),
        # an attention op that is no kernel, and a scope that only looks alike
        '  %fusion.1 = bf16[8] fusion(%x), kind=kLoop, metadata={op_name='
        '"jit(device_step)/jvp(GptLmHeadModel)/h_0/attention/softmax/exp"}',
        CALL.format(n=2, op="jit(step)/jvp(M)/h_0/attention_like/pallas_call"),
    ])
    assert _read(text) is None


def test_a_dense_program_reports_nothing():
    assert _read("ENTRY %main { ROOT %r = f32[] constant(0) }") is None
