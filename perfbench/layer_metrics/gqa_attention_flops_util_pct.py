"""Models and kernels: the grouped-query attention core's share of its
compute roofline: the family's `attention_core_flops` (QK^T and PV over the
CAUSAL TRIANGLE, two matmuls going forward and four coming back: ``6 * B * H
* S^2 * D`` a layer) over the device time under the bare ``attention`` scope
(forward + backward, whatever implements the core) times the chip's bf16
peak. The backward pass's recomputation of the scores adds to the time and
not to the FLOPs, so it cannot read high. A family without the function, or
a program without the scope, reads nothing."""

import re

from perfbench import moe_scopes

ATTENTION = re.compile(r"(?:^|[/(])attention(?:[/)]|$)")


def read(run):
    cell = run["cell"]
    flops_of = getattr(cell.family, "attention_core_flops", None)
    ms = moe_scopes.ms_under(run, ATTENTION)
    if flops_of is None or ms is None:
        return None
    flops = flops_of(cell.config["model"], cell.traffic["batch_per_chip"],
                     cell.traffic["seq_len"])
    return 100.0 * flops / (ms * 1e-3 * run["peaks"]["bf16_flops_per_s"])
