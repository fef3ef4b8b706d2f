"""ResNet-50 conv-efficiency sweep (round 5).

The round-4 evidence pinned ResNet-50's 28% MFU on XLA conv efficiency
(ViT under the same schedule reads 59%; bs256 is a null result;
per-op trace is flat with the stem+stage-1 region aggregating ~14% of
step). This sweep attacks exactly that with the levers a TPU actually
has:

  base       control re-run of the bench configuration
  vmem64     compiler_options xla_tpu_scoped_vmem_limit_kib=65536
  vmem96     compiler_options xla_tpu_scoped_vmem_limit_kib=98304
  s2d        space-to-depth stem (models/resnet.py: exact 7x7/s2
             equivalence via repack_stem_conv7_to_s2d, MLPerf-style)
  s2d_vmem64 both
  lhs        xla_tpu_enable_latency_hiding_scheduler=true
  thresh512  fusion threshold 512 MB (one bucket: fewer pack/unpack copies)
  thresh5    fusion threshold 5 MB (many buckets)

A default run covers every config above and overwrites --out; pass
--configs/--out to run a subset without clobbering a committed artifact
(the per-round analyses cite specific --out files).

Compiler options ride ``jitted.lower(...).compile(compiler_options=...)``.

Each config runs in its own subprocess (one config's failure must not
sink the sweep; the parent never touches a device, so each child gets the
chip) under the single-fetch timing protocol (bench.py's):
warmup, then NUM_ITERS scanned 10-step programs dispatched back-to-back
with ONE scalar fetch at the end.

Usage:
  python scripts/conv_sweep.py                  # full sweep -> artifacts
  python scripts/conv_sweep.py --one s2d        # single config, JSON line
  python scripts/conv_sweep.py --smoke          # CPU-sized dry run

Artifacts: perf/onchip_r05/conv_sweep.json (+ per-config logs).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIGS: dict[str, dict] = {
    "base": {},
    "vmem64": {"compiler_options": {"xla_tpu_scoped_vmem_limit_kib": "65536"}},
    "vmem96": {"compiler_options": {"xla_tpu_scoped_vmem_limit_kib": "98304"}},
    "s2d": {"model_kwargs": {"stem": "s2d"}},
    "s2d_vmem64": {
        "model_kwargs": {"stem": "s2d"},
        "compiler_options": {"xla_tpu_scoped_vmem_limit_kib": "65536"},
    },
    "lhs": {"compiler_options": {
        "xla_tpu_enable_latency_hiding_scheduler": "true"}},
    # bucket-count levers: the r04 trace shows 'copy' (pack/unpack +
    # layout copies) at ~7% of step; one giant bucket vs many small ones
    "thresh512": {"train_kwargs": {"threshold_mb": 512.0}},
    "thresh5": {"train_kwargs": {"threshold_mb": 5.0}},
}


def run_one(name: str, smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D
    from dear_pytorch_tpu.utils import perf_model

    cfg = CONFIGS[name]
    runner.apply_platform_env()
    mesh = backend.init()

    batch_size = 8 if smoke else 64
    image = 64 if smoke else 224
    model = models.get_model(
        "resnet18" if smoke else "resnet50", dtype=jnp.bfloat16,
        **cfg.get("model_kwargs", {}),
    )
    batch = data.synthetic_image_batch(
        jax.random.PRNGKey(0), batch_size, image_size=image,
        dtype=jnp.bfloat16,
    )
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["image"], train=False
    )
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    def loss_fn(p, mstate, b):
        logits, new_state = model.apply(
            {"params": p, **mstate}, b["image"], train=True,
            mutable=["batch_stats"],
        )
        return data.softmax_xent(logits, b["label"]), new_state

    train_kwargs = dict(mode="dear", threshold_mb=25.0,
                        comm_dtype=jnp.bfloat16, gather_dtype=None)
    train_kwargs.update(cfg.get("train_kwargs", {}))
    ts = D.build_train_step(
        loss_fn, params, mesh=mesh,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        model_state_template=model_state, **train_kwargs,
    )
    state = ts.init(params, model_state)

    n_per_iter = 2 if smoke else 10
    n_iters = 2 if smoke else 10
    jitted = ts.multi_step(n_per_iter)
    t_compile = time.perf_counter()
    lowered = jitted.lower(state, batch)
    copts = cfg.get("compiler_options")
    compiled = lowered.compile(compiler_options=copts) if copts \
        else lowered.compile()
    t_compile = time.perf_counter() - t_compile
    try:
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        # scan body counted once (like flops) -> per-step HBM traffic
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
    except Exception:
        flops = 0.0
        bytes_accessed = 0.0

    state2, metrics = compiled(state, batch)
    state2, metrics = compiled(state2, batch)
    float(metrics["loss"])  # drain queue before the timed window
    t0 = time.perf_counter()
    for _ in range(n_iters):
        state2, metrics = compiled(state2, batch)
    float(metrics["loss"])  # ONE fetch for the whole window
    total = time.perf_counter() - t0
    secs_per_step = total / (n_iters * n_per_iter)
    mfu = perf_model.mfu(flops, secs_per_step, jax.devices()[0])
    return {
        "config": name,
        "img_sec": round(batch_size / secs_per_step, 2),
        "ms_per_step": round(secs_per_step * 1e3, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "flops_per_step_g": round(flops / 1e9, 1),
        "bytes_accessed_gb": round(bytes_accessed / 2**30, 3),
        "peak_hbm_gb": round(perf_model.peak_hbm_bytes(compiled) / 2**30, 3),
        "compile_s": round(t_compile, 1),
        "loss": float(metrics["loss"]),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", help="run a single named config, print JSON")
    ap.add_argument("--smoke", action="store_true", help="tiny CPU shapes")
    ap.add_argument("--configs", default=",".join(CONFIGS),
                    help="comma list for the sweep")
    ap.add_argument("--out", default=os.path.join(
        REPO, "perf", "onchip_r05", "conv_sweep.json"))
    ap.add_argument("--timeout", type=float, default=2700.0,
                    help="per-config subprocess budget (covers one cold "
                         "compile)")
    args = ap.parse_args()

    if args.one:
        print(json.dumps(run_one(args.one, args.smoke)), flush=True)
        return 0

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    results = []
    for name in args.configs.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--one", name]
        if args.smoke:
            cmd.append("--smoke")
        t0 = time.time()
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        log_path = os.path.join(os.path.dirname(args.out), f"{name}.log")
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=args.timeout,
                cwd=REPO, env=env,
            )
            with open(log_path, "w") as lf:
                lf.write(proc.stdout)
                lf.write("\n--- stderr ---\n")
                lf.write(proc.stderr)
            line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
                else ""
            rec = json.loads(line) if line.startswith("{") else {
                "config": name, "error": (proc.stderr or "no output")[-400:],
                "rc": proc.returncode, "log": log_path,
            }
        except subprocess.TimeoutExpired as exc:
            # keep whatever output the child produced before the kill
            with open(log_path, "w") as lf:
                for label, stream in (("stdout", exc.stdout),
                                      ("stderr", exc.stderr)):
                    lf.write(f"--- {label} (killed at timeout) ---\n")
                    if stream:
                        lf.write(stream if isinstance(stream, str)
                                 else stream.decode(errors="replace"))
                    lf.write("\n")
            rec = {"config": name, "log": log_path,
                   "error": f"timeout after {args.timeout:.0f}s"}
        except Exception as exc:  # noqa: BLE001 — record, keep sweeping
            rec = {"config": name, "error": f"{type(exc).__name__}: {exc}"}
        rec["wall_s"] = round(time.time() - t0, 1)
        results.append(rec)
        print(json.dumps(rec), flush=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
