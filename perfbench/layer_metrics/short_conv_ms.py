"""Models and kernels: milliseconds per step, forward + backward, in the
gated short-convolution operators of all ``conv`` layers: the ``conv`` scope
of `models/lfm2_moe.py` (both projections, both multiplicative gates and the
depthwise causal taps). A program without the scope reads nothing.

The join is by each instruction's OWN ``op_name`` (a fusion's is its
root's), not `scopes.instruction_scopes`': that one renames a fusion whose
root names no part of `scopes._PARTS` after most of its members, and
``conv`` is no part there, so the ``in_proj`` matmul that XLA fuses with the
RMSNorm before it would be read as the norm's."""

import functools
import re

from perfbench import scopes

CONV = re.compile(r"(?:^|[/(])conv(?:[/)]|$)")


@functools.lru_cache(maxsize=2)
def own_op_names(compiled_text: str) -> dict:
    """{instruction: its own ``op_name``} of optimized HLO text."""
    names = {}
    for line in compiled_text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if m:
            found = scopes._OP_NAME.search(line, m.end())
            names[m.group(1)] = found.group(1) if found else ""
    return names


def ms_under(run, pattern):
    """ms per step of device 0's synchronous-line operations whose own
    ``op_name`` matches ``pattern`` (`scopes.step_table`'s time base: each
    instant booked to the innermost operation); ``None`` if none does."""
    device = run["trace"].devices[0]
    names = own_op_names(run["built"]["compiled_text"])
    ns = sum(ns for o, ns in scopes._sync_ops(device)
             if pattern.search(names.get(o.name, "")))
    return ns * 1e-6 / len(device.modules) if ns else None


def _log_by_inner_scope(run) -> None:
    """The operator's time and device operations a step by inner scope and
    pass, as a ``[conv]`` log line: whether ``conv/filter`` is one fused
    pass or several shows in its count."""
    from perfbench import harness

    device = run["trace"].devices[0]
    names = own_op_names(run["built"]["compiled_text"])
    steps = len(device.modules)
    cells = {}
    for op, ns in scopes._sync_ops(device):
        found = re.search(r"(?:^|[/(])conv/(\w+)", names.get(op.name, ""))
        if found:
            back = "transpose(jvp(" in names[op.name]
            key = (found.group(1), "backward" if back else "forward")
            ms, ops = cells.get(key, (0.0, set()))
            cells[key] = (ms + ns * 1e-6 / steps, ops | {op.name})
    harness.log("[conv] ms a step (device operations) by inner scope: "
                + "; ".join(f"{inner} {phase} {ms:.3f} ({len(ops)})"
                            for (inner, phase), (ms, ops)
                            in sorted(cells.items())))


def read(run):
    total = ms_under(run, CONV)
    if total is not None:
        _log_by_inner_scope(run)
    return total
