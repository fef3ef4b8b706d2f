"""Capture + compare collective EXPOSURE for dear vs allreduce vs fsdp.

Runs the same ResNet-18 training step under each schedule on whatever
devices JAX finds (the emulated CPU mesh under ``JAX_PLATFORMS=cpu
DEAR_NUM_CPU_DEVICES=8``), traces a few steps with jax.profiler, then feeds each trace
to scripts/trace_analysis.py and writes a comparison summary.

The number reported: **exposed_collective_pct** — collective time on
the synchronous device timeline as % of step (DeAR's design claim is
that this is smaller than the naive allreduce schedule's, reference
dear/dear_dopt.py:274-308).

CAVEAT — this script is for REAL multi-device hardware (a TPU pod
slice). On the emulated CPU mesh the 8 "devices" share one thread pool
and serialize through rendezvous waits, so exposure percentages there
measure the emulation, not the schedule; the suite-asserted claim lives
in scripts/overlap_report.py's dependency-based HLO metric instead.

Usage:
  python scripts/capture_schedule_traces.py --out perf/overlap_pod
  python scripts/capture_schedule_traces.py --steps 6 --batch 64 --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODES = ("dear", "allreduce", "fsdp")


def capture(mode: str, out_dir: str, steps: int, batch: int, smoke: bool):
    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D

    mesh = backend.init()
    model = models.get_model("resnet18", dtype=jnp.bfloat16)
    size = 64 if smoke else 224
    batch_data = data.synthetic_image_batch(
        jax.random.PRNGKey(0), batch, image_size=size, dtype=jnp.bfloat16)
    sharding = jax.sharding.NamedSharding(mesh, jax.P("dp"))
    batch_data = jax.tree.map(
        lambda x: jax.device_put(x, sharding), batch_data)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           batch_data["image"], train=False)
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    def loss_fn(p, mstate, b):
        logits, new_state = model.apply(
            {"params": p, **mstate}, b["image"], train=True,
            mutable=["batch_stats"])
        return data.softmax_xent(logits, b["label"]), new_state

    ts = D.build_train_step(
        loss_fn, params, mesh=mesh, mode=mode, threshold_mb=5.0,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        comm_dtype=jnp.bfloat16,
        model_state_template=model_state,
    )
    state = ts.init(params, model_state)
    # warm up (compile) OUTSIDE the trace
    state, metrics = ts.step(state, batch_data)
    float(metrics["loss"])
    jax.profiler.start_trace(out_dir)
    try:
        for _ in range(steps):
            state, metrics = ts.step(state, batch_data)
        float(metrics["loss"])
    finally:
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "perf",
                                                  "overlap_r05"))
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int, default=64, help="global batch")
    ap.add_argument("--smoke", action="store_true", help="64px images")
    ap.add_argument("--mode", choices=MODES,
                    help="capture ONE mode (child-process use)")
    args = ap.parse_args(argv)

    if args.mode:  # child: capture one schedule and exit
        capture(args.mode, os.path.join(args.out, args.mode), args.steps,
                args.batch, args.smoke)
        return 0

    import subprocess

    from trace_analysis import analyze, find_trace_file

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    summary = {}
    for mode in MODES:
        cmd = [sys.executable, os.path.abspath(__file__), "--mode", mode,
               "--out", args.out, "--steps", str(args.steps),
               "--batch", str(args.batch)]
        if args.smoke:
            cmd.append("--smoke")
        try:
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=1800)
            if proc.returncode != 0:
                summary[mode] = {"error": proc.stderr[-400:]}
                continue
            report = analyze(find_trace_file(os.path.join(args.out, mode)))
        except Exception as exc:  # noqa: BLE001 — keep the other modes'
            # results (TimeoutExpired, missing/unparseable trace, ...)
            summary[mode] = {"error": f"{type(exc).__name__}: "
                                      f"{str(exc)[:300]}"}
            continue
        summary[mode] = {
            "ms_per_step": report["ms_per_step"],
            "exposed_collective_pct": report["exposed_collective_pct"],
            "exposed_collective_ms_per_step":
                report["exposed_collective_ms_per_step"],
            "overlapped_collective_ms_per_step":
                report["overlapped_collective_ms_per_step"],
        }
    summary["note"] = (
        "report only; the asserted dear-vs-allreduce claim is "
        "scripts/overlap_report.py's HLO metric (see docstring caveat)"
    )
    out_path = os.path.join(args.out, "summary.json")
    os.makedirs(args.out, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
