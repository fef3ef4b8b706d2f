"""Where does the ResNet-50 step time go? (run on the real chip)

Builds the bench-identical DeAR step and reports a component breakdown:
forward-only, forward+backward, full step in dear / allreduce / no-comm
modes, host dispatch rate vs device completion rate, XLA cost
analysis (FLOPs, HBM bytes), and an optional jax.profiler trace.

Usage:  python scripts/profile_resnet.py [--trace-dir DIR] [--batch 64]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def timed(fn, *args, warmup=5, iters=20, fetch=None):
    """Mean seconds per call under async dispatch + single final fetch."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    if fetch is not None:
        fetch(out)
    else:
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--model", type=str, default="resnet50",
                    help="CNN from the zoo (mnistnet = fast CPU drive)")
    ap.add_argument("--trace-dir", type=str, default=None)
    args = ap.parse_args()

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D
    from dear_pytorch_tpu.utils import perf_model

    runner.apply_platform_env()
    mesh = backend.init()
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}  peak bf16: "
          f"{perf_model.device_peak_flops(dev) / 1e12:.0f} TFLOP/s")

    if models.is_bert(args.model):
        raise SystemExit(f"--model {args.model}: CNN names only "
                         f"({models.cnn_names()}); this script feeds image "
                         "batches")
    model = models.get_model(args.model, dtype=jnp.bfloat16)
    if args.model.lower() == "mnistnet":
        batch = data.synthetic_mnist_batch(jax.random.PRNGKey(0), args.batch)
    else:
        batch = data.synthetic_image_batch(
            jax.random.PRNGKey(0), args.batch, dtype=jnp.bfloat16
        )
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["image"], train=False
    )
    params = variables["params"]
    has_bn = "batch_stats" in variables
    model_state = (
        {"batch_stats": variables["batch_stats"]} if has_bn else None
    )

    if has_bn:
        def loss_fn(p, mstate, b):
            logits, new_state = model.apply(
                {"params": p, **mstate}, b["image"], train=True,
                mutable=["batch_stats"],
            )
            return data.softmax_xent(logits, b["label"]), new_state
    else:
        def loss_fn(p, b):
            # deterministic (no dropout): this script measures schedules,
            # not regularization
            logits = model.apply({"params": p}, b["image"], train=False)
            return data.softmax_xent(logits, b["label"])

    # ---- forward only ------------------------------------------------------
    fwd = jax.jit(lambda v, x: model.apply(v, x, train=False))
    t_fwd = timed(fwd, variables, batch["image"])
    print(f"forward only          : {t_fwd * 1e3:7.2f} ms "
          f"({args.batch / t_fwd:8.1f} img/s)")

    # ---- forward + backward (no comm, no optimizer) ------------------------
    if has_bn:
        grad_fn = jax.jit(
            jax.grad(lambda p, ms, b: loss_fn(p, ms, b)[0], argnums=0)
        )
        t_bwd = timed(grad_fn, params, model_state, batch)
    else:
        grad_fn = jax.jit(jax.grad(loss_fn, argnums=0))
        t_bwd = timed(grad_fn, params, batch)
    print(f"fwd+bwd (grads only)  : {t_bwd * 1e3:7.2f} ms "
          f"({args.batch / t_bwd:8.1f} img/s)")

    # one configuration for EVERY build below — the A/B and trace runs must
    # measure the same step the mode loop does. gather_dtype mirrors
    # bench.py's default (bf16 pre-gather cast) but only applies to the
    # sharded schedule; the allreduce baseline rejects it.
    step_kwargs = dict(
        mesh=mesh, threshold_mb=25.0,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        comm_dtype=jnp.bfloat16, model_state_template=model_state,
    )
    dear_kwargs = dict(step_kwargs, gather_dtype=jnp.bfloat16)

    # ---- full steps per mode ----------------------------------------------
    results = {}
    for mode in ("dear", "allreduce"):
        kw = dear_kwargs if mode == "dear" else step_kwargs
        ts = D.build_train_step(loss_fn, params, mode=mode, **kw)
        state = ts.init(params, model_state)
        compiled = ts.lower(state, batch).compile()
        cost = {}
        try:
            cost = compiled.cost_analysis()
        except Exception:
            pass

        holder = {"s": state, "m": None}

        def step():
            holder["s"], holder["m"] = compiled(holder["s"], batch)
            return holder["m"]["loss"]

        # device completion rate (async dispatch + one fetch)
        t_step = timed(step, fetch=lambda x: float(x))
        # host dispatch rate (never waits)
        t0 = time.perf_counter()
        for _ in range(20):
            step()
        t_dispatch = (time.perf_counter() - t0) / 20
        float(holder["m"]["loss"])

        flops = float(cost.get("flops", 0.0))
        mfu = perf_model.mfu(flops, t_step, dev)
        results[mode] = (t_step, t_dispatch, flops, mfu)
        print(f"full step [{mode:9s}] : {t_step * 1e3:7.2f} ms "
              f"({args.batch / t_step:8.1f} img/s)  "
              f"dispatch {t_dispatch * 1e3:6.2f} ms/step  "
              f"flops/step {flops / 1e9:6.1f} G  MFU {100 * mfu:5.1f}%  "
              f"HBM {float(cost.get('bytes accessed', 0)) / 1e9:5.2f} GB")

    t_step, t_disp, flops, _ = results["dear"]
    print("\nbreakdown (dear step):")
    print(f"  fwd+bwd compute     {t_bwd * 1e3:7.2f} ms "
          f"({100 * t_bwd / t_step:5.1f}% of step)")
    print(f"  pack/opt/comm rest  {(t_step - t_bwd) * 1e3:7.2f} ms")
    if t_disp > 0.8 * t_step:
        print("  !! host dispatch rate ~= step rate: the dispatch "
              "path, not the device, likely bounds throughput")

    # ---- scanned-protocol A/B: k steps per dispatch ------------------------
    # Isolates per-dispatch cost: if per-step time collapses as
    # k grows, dispatch was the bottleneck; if flat, the device binds.
    # donate=True like the mode loop: donate=False would add a state-sized
    # copy per dispatch that amortizes with k exactly like dispatch latency,
    # faking a dispatch-bound signature
    ts = D.build_train_step(loss_fn, params, mode="dear", **dear_kwargs)
    print("\nscanned protocol (one compiled k-step program per dispatch):")
    for kk in (1, 4, 10):
        runner_fn = ts.multi_step(kk)
        st = ts.init(params, model_state)
        holder2 = {"s": st, "m": None}

        def stepk():
            holder2["s"], holder2["m"] = runner_fn(holder2["s"], batch)
            return holder2["m"]["loss"]

        tk = timed(stepk, warmup=3, iters=max(10 // kk, 3),
                   fetch=lambda x: float(x))
        print(f"  k={kk:3d}: {tk / kk * 1e3:7.2f} ms/step "
              f"({args.batch * kk / tk:8.1f} img/s)")

    if args.trace_dir:
        ts = D.build_train_step(loss_fn, params, mode="dear", **dear_kwargs)
        state = ts.init(params, model_state)
        for _ in range(3):
            state, m = ts.step(state, batch)
        float(m["loss"])
        with jax.profiler.trace(args.trace_dir):
            for _ in range(10):
                state, m = ts.step(state, batch)
            float(m["loss"])
        print(f"trace written to {args.trace_dir}")


if __name__ == "__main__":
    main()
