"""Models and kernels: milliseconds per step, forward + backward, under
``mamba/ssd`` only: all of the state-space scan (the discretisation, the
intra-chunk matrices, the chunk states, the recurrence over chunks, the
``D`` skip, and their recomputation in the backward pass), what a scan
kernel would move. The projections, the convolution and the gated norm are
`mamba_mixer_ms`'s other scopes. A program without the scope reads nothing."""

import re

from perfbench.layer_metrics import short_conv_ms

SSD = re.compile(r"(?:^|[/(])mamba/ssd(?:[/)]|$)")


def read(run):
    return short_conv_ms.ms_under(run, SSD)
