"""Models and kernels: Pallas kernels (``tpu_custom_call``) in the compiled
step's text whose OWN ``op_name`` lies under ``kda/scan``: the chunked delta
rule's kernels of `ops.kda`, where `kda_chunked` selected them. A KDA layer
holds two: the forward kernel, and the backward kernel (its own state pass,
then the chunks backward) under ``transpose(jvp(...))``; a rematerialized
block that keeps the scan's output does not run the forward kernel again.
Six KDA layers are 12. The instruction's own ``op_name`` is read: a kernel
without one is not counted, whatever scope its neighbours give it. An exact
count of what the program holds, not of what ran; a program whose scan is
XLA's chunked form (no kernel) reports nothing."""

from perfbench.layer_metrics import attention_kernel_calls_per_step as kernels
from perfbench.layer_metrics import kda_scan_ms


def count(compiled_text: str) -> int:
    total = 0
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = kernels._OP_NAME.search(line)
        if op_name and kda_scan_ms.SCAN.search(op_name.group(1)):
            total += 1
    return total


def read(run):
    total = count(run["built"]["compiled_text"])
    return float(total) if total else None
