"""Models and kernels: Pallas kernels (``tpu_custom_call``) in the compiled
step's text under an ``attention`` scope whose kernel draws a dropout mask:
the flash kernels with attention-probabilities dropout live, told apart by
the name `ops.flash_attention` gives their `pallas_call`s
(``flash_fwd_dropout``, ``flash_bwd_dropout``: a path element of the
``op_name`` that ends in ``_dropout``). 24 layers of a forward and a fused
backward kernel are 48. An exact count of what the program holds, not of
what ran; a program with none (no kernel, or kernels without dropout)
reports nothing."""

import re

from perfbench.layer_metrics import attention_kernel_calls_per_step as kernels

_DRAWS_A_MASK = re.compile(r"(?:^|[/(])\w+_dropout(?:[/)]|$)")


def count(compiled_text: str) -> int:
    """The attention kernels (`attention_kernel_calls_per_step`'s count)
    among the lines whose ``op_name`` holds a ``*_dropout`` element."""
    drawing = [line for line in compiled_text.splitlines()
               if (op_name := kernels._OP_NAME.search(line))
               and _DRAWS_A_MASK.search(op_name.group(1))]
    return kernels.count("\n".join(drawing))


def read(run):
    total = count(run["built"]["compiled_text"])
    return float(total) if total else None
