"""The overlap evidence that needs no chip: the HLO overlappability metric
at world=8 — the dear-vs-allreduce claim, measured where it exists. For
every collective op in the compiled (optimized, scheduled) step, the
fraction of the program's compute ops that are dependency-INDEPENDENT of it
(neither ancestor nor descendant). Independent compute is what any
scheduler on any backend may run concurrently with the collective; a
serialized schedule shows up as a low fraction no matter the hardware. The
DeAR design claim (reference dear/dear_dopt.py:274-308: RS under backward,
AG under next forward) passes iff dear's mean fraction exceeds the naive
allreduce schedule's.

Exposed collective TIME is a device number: `perfbench` reads it from a
chip trace (`exposed_collective_ms`, and by leg through the step's named
scopes: `exposed_reduce_ms`, `exposed_gather_ms`; docs/OBSERVABILITY.md).

Writes perf/overlap_r05/summary.json and exits nonzero if the claim
fails. Asserted in-suite by tests/test_overlap.py.

Usage:  python scripts/overlap_report.py [--out perf/overlap_r05]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODES = ("dear", "dear-fused", "allreduce", "fsdp")


def hlo_overlap_metric(mode: str) -> dict:
    """Compile a bucketed MLP train step at world=8 on the emulated CPU
    mesh and score each collective's independent-compute fraction (the
    metric itself lives in `observability.overlap.hlo_collective_stats`
    — one implementation for this script, the auditor, and the suite)."""
    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.observability.overlap import hlo_collective_stats
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step

    mesh = backend.init()
    n_layers = 4
    ks = jax.random.split(jax.random.PRNGKey(0), n_layers)
    params = {
        f"l{i:02d}": {"w": jax.random.normal(ks[i], (256, 256)) * 0.1,
                      "b": jnp.zeros((256,))}
        for i in range(n_layers)
    }

    def loss(p, b):
        x, y = b
        for i in range(n_layers):
            x = jnp.tanh(x @ p[f"l{i:02d}"]["w"] + p[f"l{i:02d}"]["b"])
        return jnp.mean((x - y) ** 2)

    ts = build_train_step(
        loss, params, mesh=mesh, mode=mode, nearby_layers=1,
        optimizer=fused_sgd(lr=0.01, momentum=0.9), donate=False,
    )
    state = ts.init(params)
    batch = (jnp.zeros((32, 256)), jnp.zeros((32, 256)))
    text = ts.lower(state, batch).compile().as_text()
    return hlo_collective_stats(text)


def main(argv=None) -> int:
    # the metric only exists on a multi-device mesh: force the 8-device
    # emulated CPU world (set before jax is first imported, below)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["DEAR_NUM_CPU_DEVICES"] = "8"
    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "perf",
                                                  "overlap_r05"))
    args = ap.parse_args(argv)

    summary: dict = {"hlo_world8": {}}
    for mode in MODES:
        try:
            summary["hlo_world8"][mode] = hlo_overlap_metric(mode)
        except Exception as exc:  # noqa: BLE001
            summary["hlo_world8"][mode] = {"error": str(exc)[:300]}

    dear = summary["hlo_world8"].get("dear", {})
    ar = summary["hlo_world8"].get("allreduce", {})
    ok = (
        isinstance(dear.get("mean_independent_compute_frac"), float)
        and isinstance(ar.get("mean_independent_compute_frac"), float)
        and dear["mean_independent_compute_frac"]
        > ar["mean_independent_compute_frac"]
    )
    summary["claim_dear_overlappability_above_allreduce"] = bool(ok)

    # dear-fused A/B: its ring transport lives INSIDE the Pallas kernels
    # (sub-XLA — invisible to XLA's scheduler, which is the point), so the
    # structural metric only sees whatever collectives the lowering leaves
    # in the program (on the CPU interpret lowering, the RDMA emulation).
    # The gated claim is computability — the mode compiles at world=8 and
    # the metric evaluates — plus the per-mode numbers for the A/B; the
    # exposed-vs-hidden TIME comparison is the auditor's job:
    #   python -m dear_pytorch_tpu.observability.report \
    #       --modes dear,dear-fused
    fused = summary["hlo_world8"].get("dear-fused", {})
    fused_ok = isinstance(
        fused.get("mean_independent_compute_frac"), float)
    summary["dear_fused_vs_dear"] = {
        "note": ("ring transport is sub-XLA (in-kernel remote copies); "
                 "HLO fractions compare only scheduler-visible structure"),
        "dear_mean_independent_compute_frac":
            dear.get("mean_independent_compute_frac"),
        "dear_fused_mean_independent_compute_frac":
            fused.get("mean_independent_compute_frac"),
        "dear_collectives": {
            k: v["count"] for k, v in dear.get("collectives", {}).items()},
        "dear_fused_collectives": {
            k: v["count"] for k, v in fused.get("collectives", {}).items()},
    }
    summary["claim_dear_fused_compiles_and_scores"] = bool(fused_ok)
    ok = ok and fused_ok
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
