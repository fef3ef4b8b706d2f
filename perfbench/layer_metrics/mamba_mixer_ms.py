"""Models and kernels: milliseconds per step, forward + backward (the
rematerialised forward work of a recomputed block included), in the Mamba-2
mixers of all ``mamba`` layers: the ``mamba`` scope of
`models/granite_hybrid.py` (``in_proj``, ``conv1d``, ``ssd``, ``gate_norm``,
``out_proj``). A program without the scope reads nothing.

The join is by each instruction's OWN ``op_name`` (`short_conv_ms`'s: a
fusion's is its root's), not `scopes.instruction_scopes`': that one renames a
fusion whose root names no part of `scopes._PARTS` after most of its
members, and ``mamba`` is no part there, so the ``in_proj`` matmul that XLA
fuses with the RMSNorm before it would be read as the norm's."""

import re

from perfbench import scopes
from perfbench.layer_metrics import short_conv_ms

MAMBA = re.compile(r"(?:^|[/(])mamba(?:[/)]|$)")
_INNER = re.compile(r"(?:^|[/(])mamba/(\w+)")


def _log_by_inner_scope(run) -> None:
    """The mixers' time and device operations a step by inner scope and
    pass, as a ``[mamba]`` log line (rematerialised forward work runs in
    the backward pass and is booked there)."""
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
    harness.log("[mamba] ms a step (device operations) by inner scope: "
                + "; ".join(f"{inner} {phase} {ms:.3f} ({len(ops)})"
                            for (inner, phase), (ms, ops)
                            in sorted(cells.items())))


def read(run):
    total = short_conv_ms.ms_under(run, MAMBA)
    if total is not None:
        _log_by_inner_scope(run)
    return total
