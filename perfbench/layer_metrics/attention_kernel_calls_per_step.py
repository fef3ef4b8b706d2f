"""Models and kernels: Pallas kernels (``tpu_custom_call``) in the compiled
step's text whose ``op_name`` lies under an ``attention`` scope: the flash
kernels the model's default core selected, forward and backward (12 layers
of a forward and a fused backward kernel are 24; with separate dq and dkv
kernels, 36). An exact count of what the program holds, not
of what ran; a program with none reports nothing."""

import re

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ATTENTION = re.compile(r"(?:^|[/(])attention(?:[/)]|$)")


def count(compiled_text: str) -> int:
    total = 0
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = _OP_NAME.search(line)
        if op_name and _ATTENTION.search(op_name.group(1)):
            total += 1
    return total


def read(run):
    total = count(run["built"]["compiled_text"])
    return float(total) if total else None
