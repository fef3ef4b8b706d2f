"""Models and kernels: Pallas kernels (``tpu_custom_call``) in the compiled
step's text whose ``op_name`` lies under ``moe/experts``: the grouped-matmul
kernels of `ops.grouped_matmul` that walk only the rows some held expert
works on, where `parallel.ep.RoutedExperts` selected them. An expert layer
holds six: gate / up with the SwiGLU and the output matmul forward; the
SwiGLU's gradient, the rows' gradient and the two weight gradients backward,
so five expert layers are 30 and four are 24. The instruction's OWN
``op_name`` is read: XLA's grouped-matmul kernel (``ragged-dot-none``, a
``tpu_custom_call`` too) carries no scope and is not counted, whatever scope
`moe_scopes.instruction_scopes` infers for it. An exact count of what the
program holds, not of what ran; a program whose experts run on
`lax.ragged_dot` reports nothing."""

from perfbench import moe_scopes
from perfbench.layer_metrics import attention_kernel_calls_per_step as kernels


def count(compiled_text: str) -> int:
    total = 0
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = kernels._OP_NAME.search(line)
        if op_name and moe_scopes.EXPERTS.search(op_name.group(1)):
            total += 1
    return total


def read(run):
    total = count(run["built"]["compiled_text"])
    return float(total) if total else None
