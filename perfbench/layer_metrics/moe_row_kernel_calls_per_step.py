"""Models and kernels: Pallas kernels (``tpu_custom_call``) in the compiled
step's text whose ``op_name`` lies under ``moe/dispatch`` or ``moe/combine``:
the row kernels of `ops.moe_rows` that move only the rows some held expert
works on, where `parallel.ep.RoutedExperts` selected them. An expert layer
holds four: the spread and the combine, and each one's backward (which is
the other body), so five expert layers are 20 and four are 16. An exact
count of what the program holds, not of what ran; a program that moves its
rows with XLA's gathers reports nothing."""

import re

from perfbench.layer_metrics import attention_kernel_calls_per_step as kernels

_MOVES_ROWS = re.compile(r"(?:^|[/(])moe/(?:dispatch|combine)(?:[/)]|$)")


def count(compiled_text: str) -> int:
    total = 0
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = kernels._OP_NAME.search(line)
        if op_name and _MOVES_ROWS.search(op_name.group(1)):
            total += 1
    return total


def read(run):
    total = count(run["built"]["compiled_text"])
    return float(total) if total else None
