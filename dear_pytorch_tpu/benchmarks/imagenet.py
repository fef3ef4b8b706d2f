"""Synthetic CNN training benchmark (reference dear/imagenet_benchmark.py).

Trains a torchvision-parity CNN (ResNet / DenseNet / VGG / InceptionV4) on
fake ImageNet data under the selected communication schedule and prints
throughput in the reference's format (img/sec per device, total ± 1.96σ).

Example:
  python -m dear_pytorch_tpu.benchmarks.imagenet \
      --model resnet50 --batch-size 64 --fp16 --mode dear --threshold 25
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from dear_pytorch_tpu import models
from dear_pytorch_tpu.benchmarks import runner
from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS
from dear_pytorch_tpu.models import data


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU Synthetic CNN Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--model", type=str, default="resnet50",
                   help=f"one of {models.cnn_names()}")
    p.add_argument("--stem", type=str, default="conv7",
                   choices=["conv7", "s2d"],
                   help="ResNet stem: 's2d' = space-to-depth stem, the "
                        "exact TPU-friendly repack of the 7x7/s2 conv "
                        "(models/resnet.py)")
    runner.add_common_args(p)
    return p


def setup_cnn(args, mesh):
    """Model + fake data + loss for a CNN benchmark on ``mesh``.

    Returns ``(loss_fn, params, model_state, batch, sharding, image_size,
    global_bs)``; shared by the throughput CLI below and the scaling sweep
    (benchmarks/scaling.py), which calls it once per sub-mesh size.
    """
    world = mesh.shape[DP_AXIS]
    dtype = jnp.bfloat16 if args.fp16 else jnp.float32
    model_kwargs = {}
    if getattr(args, "stem", "conv7") != "conv7":
        if not args.model.lower().startswith("resnet"):
            raise SystemExit("--stem s2d applies to ResNet models only")
        model_kwargs["stem"] = args.stem
    model = models.get_model(args.model, dtype=dtype, **model_kwargs)
    image_size = 299 if args.model.lower() == "inceptionv4" else 224
    if args.model.lower() == "mnistnet":
        image_size = 28

    global_bs = args.batch_size * world
    if args.model.lower() == "mnistnet":
        batch = data.synthetic_mnist_batch(jax.random.PRNGKey(0), global_bs)
    else:
        batch = data.synthetic_image_batch(
            jax.random.PRNGKey(0), global_bs, image_size=image_size,
            dtype=dtype,
        )
    sharding = jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS))
    batch = runner.stage_global(batch, sharding)  # multi-host safe

    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["image"], train=False
    )
    params = variables["params"]
    has_bn = "batch_stats" in variables
    model_state = (
        {"batch_stats": variables["batch_stats"]} if has_bn else None
    )

    if has_bn:
        def loss_fn(p, mstate, b, rng):
            logits, new_state = model.apply(
                {"params": p, **mstate}, b["image"], train=True,
                mutable=["batch_stats"], rngs={"dropout": rng},
            )
            return data.softmax_xent(logits, b["label"]), new_state
    else:
        def loss_fn(p, b, rng):
            logits = model.apply(
                {"params": p}, b["image"], train=True,
                rngs={"dropout": rng},
            )
            return data.softmax_xent(logits, b["label"])

    return (loss_fn, params, model_state, batch, sharding, image_size,
            global_bs)


def main(argv=None) -> runner.BenchResult:
    args = build_parser().parse_args(argv)
    runner.apply_platform_env()
    scan_steps = runner.validate_scan_steps(args)  # before any resources
    mesh = backend.init()
    world = backend.dp_size(mesh)

    (loss_fn, params, model_state, batch, sharding, image_size,
     global_bs) = setup_cnn(args, mesh)
    has_bn = model_state is not None

    cfg = runner.config_from_args(args, world=world)
    ts, stepper = runner.build_stepper(
        cfg, loss_fn, params, mesh, model_state=model_state,
        mgwfbp=args.mgwfbp,
    )
    state = ts.init(params, model_state) if has_bn else ts.init(params)

    runner.log(f"Model: {args.model}")
    runner.log(f"BF16: {args.fp16}")
    runner.log(f"Batch size: {args.batch_size} (per device), "
               f"{global_bs} global")
    runner.log(f"Number of {runner.device_name()}s: {world}")
    runner.log(f"Schedule: {args.mode}; "
               f"fusion: {ts.plan.num_buckets} bucket(s)")

    from dear_pytorch_tpu.runtime import pipeline as RP

    spec = (
        RP.mnist_spec(global_bs) if args.model.lower() == "mnistnet"
        else RP.image_spec(global_bs, image_size=image_size)
    )
    next_batch, close = runner.make_batch_source(args, spec, sharding, batch)

    holder = {"state": state, "metrics": None, "batch": batch}
    step_fn, timed_kwargs = runner.make_step_source(
        args, scan_steps, ts, stepper, holder, next_batch
    )
    runner.run_pretune(args, stepper, holder, next_batch)

    def sync():
        # One device->host scalar fetch drains the in-order pipeline; cheaper
        # than block_until_ready on every buffer.
        if holder["metrics"] is not None:  # warmup may be zero steps
            float(holder["metrics"]["loss"])

    metrics_log = runner.metrics_from_args(args)
    # with --mfu, one AOT cost analysis BEFORE timing: the run-health
    # monitor watches live per-iteration MFU, log_mfu reuses the flops
    flops = (runner.step_flops(getattr(stepper, "ts", ts), holder["state"], batch)
             if args.mfu else None)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        result = runner.run_timed(
            step_fn,
            unit="img",
            sync=sync,
            metrics=metrics_log,
            flops_per_step=flops,
            **timed_kwargs,
        )
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
        if metrics_log is not None:
            metrics_log.close()
        close()
    if args.mfu:
        # the autotuner may have re-bucketed: use its CURRENT step (the
        # precomputed flops short-circuits the recompile when present)
        runner.log_mfu(getattr(stepper, "ts", ts), holder["state"], batch,
                       result, flops=flops)
    return result


if __name__ == "__main__":
    main()
