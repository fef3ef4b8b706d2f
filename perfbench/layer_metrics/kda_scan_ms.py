"""Models and kernels: milliseconds per step, forward + backward, under
``kda/scan`` only: all of the chunked delta rule (`ops.kda.kda_chunked`: the
factorised decays, the triangular solve, the chunks' contributions, the
recurrence over chunks, the outputs, and their recomputation in the
backward pass), what a scan kernel would move. The projections, the
convolutions, the gates and the output norm are `kda_mixer_ms`'s other
scopes. A program without the scope reads nothing."""

import re

from perfbench.layer_metrics import short_conv_ms

SCAN = re.compile(r"(?:^|[/(])kda/scan(?:[/)]|$)")


def read(run):
    return short_conv_ms.ms_under(run, SCAN)
