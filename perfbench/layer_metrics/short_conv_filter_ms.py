"""Models and kernels: milliseconds per step, forward + backward, under
``conv/filter`` only: the short convolution's two gates and its taps,
everything elementwise between the two projections. The bandwidth-bound part
of `short_conv_ms`, what a fused kernel would move; what XLA fuses into a
projection's matmul is that matmul's, under ``conv/in_proj`` or
``conv/out_proj``. A program without the scope reads nothing."""

import re

from perfbench.layer_metrics import short_conv_ms

FILTER = re.compile(r"(?:^|[/(])conv/filter(?:[/)]|$)")


def read(run):
    return short_conv_ms.ms_under(run, FILTER)
