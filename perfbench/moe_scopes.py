"""What the sparse decoder's per-layer readers share: device time under
scopes finer than `perfbench.scopes.classify`'s parts, and the program's
routing counter.

``mlp/moe/{route,dispatch,experts,combine}`` are `jax.named_scope`s of
`parallel.ep.RoutedExperts`, ``mtp`` of `models/glm_moe.py`; the join is
`perfbench.scopes`' (trace event -> instruction -> ``op_name``), so a fusion
is booked whole to its root's scope here too, with one repair of its own
(`instruction_scopes`: the grouped-matmul kernels XLA leaves without a
name). A program without these scopes reads nothing (``None``), and the
run's line leaves the metric out.
"""

from __future__ import annotations

import functools
import re

from perfbench import scopes


def _under(*elements: str):
    """A pattern for consecutive path elements anywhere in an ``op_name``."""
    return re.compile(r"(?:^|[/(])%s(?:[/)]|$)" % "/".join(elements))


ROUTED = _under("moe", "(?:route|dispatch|experts|combine)")
EXPERTS = _under("moe", "experts")
MTP = _under("mtp")


_MOE = re.compile(r"^(.*(?:^|[/(])moe)/")


@functools.lru_cache(maxsize=2)
def instruction_scopes(compiled_text: str) -> dict:
    """`scopes.instruction_scopes`, with one more repair: XLA:TPU rewrites
    `jax.lax.ragged_dot` into a grouped-matmul custom call
    (``%ragged-dot-none.<n>``) that carries no scope, so the expert
    matmuls themselves (17.7 of 281 ms a step in this PR's first traced
    run) would be no scope's. Each takes ``<its layer's moe scope>/experts``
    from the first named instruction that uses it, or that it uses, under a
    ``moe`` scope (its neighbours are the layer's own dispatch, activation
    and combine, forward or backward alike)."""
    names = dict(scopes.instruction_scopes(compiled_text))
    # (the rewrite leaves ``op_name="ragged-dot-none"``, the kernel's own
    # name and no scope)
    unnamed = {n for n, op in names.items()
               if n.startswith("ragged-dot-none") and "/" not in op}
    if not unnamed:
        return names
    uses = {}     # unnamed kernel -> op_names of its users and operands
    for line in compiled_text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if not m:
            continue
        refs = set(scopes._REFERENCE.findall(line[m.end():]))
        if m.group(1) in unnamed:
            for r in refs:
                uses.setdefault(m.group(1), []).append(names.get(r, ""))
        elif names.get(m.group(1)):
            for r in refs & unnamed:
                uses.setdefault(r, []).append(names[m.group(1)])
    for kernel, op_names in uses.items():
        moe = next(filter(None, map(_MOE.match, op_names)), None)
        if moe:
            names[kernel] = moe.group(1) + "/experts/ragged_dot(inferred)"
    return names


def ms_under(run: dict, pattern) -> float | None:
    """ms per step, forward + backward, of device 0's synchronous-line
    operations whose ``op_name`` matches ``pattern`` (each instant booked to
    the innermost operation: `scopes.step_table`'s time base)."""
    device = run["trace"].devices[0]
    names = instruction_scopes(run["built"]["compiled_text"])
    ns = sum(ns for o, ns in scopes._sync_ops(device)
             if pattern.search(names.get(o.name, "")))
    return ns * 1e-6 / len(device.modules) if ns else None


def routing_counts(run: dict):
    """``[expert layers, experts held]`` (numpy): the program's routing
    counter (`models.expert_assignments`, the ``intermediates`` the expert
    layer sows) on the run's batch and the weights its state holds after the
    traced steps, from one forward pass outside the traced stretch. Kept in
    ``run``: two readers ask. ``None`` for a family without the counter."""
    if "routing_counts" not in run:
        run["routing_counts"] = _routing_counts(run)
    return run["routing_counts"]


def _routing_counts(run: dict):
    import jax
    import numpy as np

    from perfbench import harness

    cell, built = run["cell"], run["built"]
    fam = cell.family
    if not hasattr(fam, "expert_assignments"):
        return None
    cfg = fam.model_config(
        cell.config["model"],
        harness.DTYPES[cell.config["train"]["compute_dtype"]])
    params = built["ts"].gather_params(built["state"])
    counts = jax.jit(lambda p, b: fam.expert_assignments(cfg, p, b))(
        params, built["batch"])
    counts = np.asarray(counts)
    harness.log("[routing] assignments per held expert, by expert layer: "
                + "; ".join(" ".join(str(int(n)) for n in row)
                            for row in counts))
    return counts
