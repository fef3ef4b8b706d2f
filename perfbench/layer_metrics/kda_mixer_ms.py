"""Models and kernels: milliseconds per step, forward + backward (the
rematerialised forward work of a recomputed block included), in the Kimi
Delta Attention mixers of all ``kda`` layers: the ``kda`` scope of
`models/ling_hybrid.py` (``in_proj``, ``conv``, ``gate``, ``scan``,
``out_norm_gate``, ``out_proj``). A program without the scope reads nothing.

The join is by each instruction's OWN ``op_name``, as `mamba_mixer_ms`'s
(that module says why)."""

import re

from perfbench import scopes
from perfbench.layer_metrics import short_conv_ms

KDA = re.compile(r"(?:^|[/(])kda(?:[/)]|$)")
_INNER = re.compile(r"(?:^|[/(])kda/(\w+)")


def _log_by_inner_scope(run) -> None:
    """The mixers' time and device operations a step by inner scope and
    pass, as a ``[kda]`` log line (rematerialised forward work runs in the
    backward pass and is booked there)."""
    from perfbench import harness

    device = run["trace"].devices[0]
    names = short_conv_ms.own_op_names(run["built"]["compiled_text"])
    steps = len(device.modules)
    cells = {}
    for op, ns in scopes._sync_ops(device):
        name = names.get(op.name, "")
        found = _INNER.search(name)
        if found:
            key = (found.group(1), scopes.classify(name)[0])
            ms, ops = cells.get(key, (0.0, set()))
            cells[key] = (ms + ns * 1e-6 / steps, ops | {op.name})
    harness.log("[kda] ms a step (device operations) by inner scope: "
                + "; ".join(f"{inner} {phase} {ms:.3f} ({len(ops)})"
                            for (inner, phase), (ms, ops)
                            in sorted(cells.items())))


def read(run):
    total = short_conv_ms.ms_under(run, KDA)
    if total is not None:
        _log_by_inner_scope(run)
    return total
