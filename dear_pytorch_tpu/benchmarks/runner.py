"""Shared benchmark runner: the reference's measurement protocol, TPU-native.

Protocol parity (reference dear/imagenet_benchmark.py:151-172):
  - ``num_warmup_batches`` untimed steps (also absorbs jit compilation),
  - ``num_iters`` timed runs of ``num_batches_per_iter`` steps each,
  - per-iter throughput; final mean ± 1.96σ; a ``Total ... <DEV>(s): N +-C``
    line whose shape the batch driver scrapes (reference benchmarks.py:119-128).

TPU-native differences (deliberate):
  - One *process* drives all chips (SPMD); "Number of TPUs" is the device
    world, not the process count. Throughput-per-device keeps the reference's
    per-GPU meaning.
  - A timed run is jitted end-to-end; a single `block_until_ready` per timed
    run replaces per-step ``cuda.synchronize`` (which would serialize the
    pipelined schedule XLA builds).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np

from dear_pytorch_tpu.comm import backend


@dataclasses.dataclass
class BenchResult:
    unit: str                  # 'img' or 'sen'
    device: str                # 'TPU' (or 'CPU' in emulation)
    world: int
    per_device_mean: float
    per_device_conf: float     # 1.96 sigma
    iter_time_mean: float
    iter_time_conf: float
    per_iter: list[float] = dataclasses.field(default_factory=list)

    @property
    def total_mean(self) -> float:
        return self.world * self.per_device_mean

    @property
    def total_conf(self) -> float:
        return self.world * self.per_device_conf


def apply_platform_env() -> None:
    """Apply DEAR_NUM_CPU_DEVICES and place the compilation cache before
    backend init.

    Delegates to `backend._apply_platform_env` (which `backend.init` also
    runs itself, so every entry point is covered); kept as the CLI-facing
    name."""
    backend._apply_platform_env()


def log(s: str, nl: bool = True) -> None:
    """Rank-0 printing (reference dear/imagenet_benchmark.py:139-142)."""
    if backend.rank() != 0:
        return
    print(s, end="\n" if nl else "", flush=True)


def device_name() -> str:
    plat = jax.devices()[0].platform
    return {"tpu": "TPU", "cpu": "CPU", "gpu": "GPU"}.get(plat, plat.upper())


def step_flops(ts, state, batch) -> Optional[float]:
    """Per-step FLOPs from XLA cost analysis of the compiled train step
    (one AOT compile; None where the analysis reports no flops). Compute
    this BEFORE `run_timed` and pass it as ``flops_per_step`` so the
    anomaly monitor can watch live MFU; hand the same value to `log_mfu`
    to avoid a second compile."""
    cost = ts.lower(state, batch).compile().cost_analysis()
    return float(cost.get("flops", 0.0)) or None


def run_timed(
    step_fn: Callable[[], Any],
    *,
    batch_size: int,
    num_warmup_batches: int = 10,
    num_batches_per_iter: int = 10,
    num_iters: int = 5,
    unit: str = "img",
    sync: Optional[Callable[[], None]] = None,
    world: Optional[int] = None,
    metrics=None,
    steps_per_call: int = 1,
    flops_per_step: Optional[float] = None,
) -> BenchResult:
    """Run the warmup + timed-iteration protocol around ``step_fn``.

    ``step_fn`` performs one training step (async dispatch is fine);
    ``sync`` blocks until all dispatched work finished (defaults to
    `jax.effects_barrier`-free no-op — pass one!). ``world`` overrides the
    device count in the report (the scaling sweep runs on sub-meshes).
    ``metrics`` (a `utils.MetricsLogger`) receives one record per timed
    iteration plus a final summary record. ``steps_per_call`` says how many
    REAL train steps one ``step_fn()`` call performs (the scanned
    protocol) so reported step times stay per-step; ``batch_size`` must
    then be the items per CALL. ``flops_per_step`` (see `step_flops`)
    lets the run-health anomaly monitor watch live MFU per iteration
    (`health.mfu_drop`; needs a device with a known peak, i.e. TPU).
    """
    dev = device_name()
    world = backend.device_count() if world is None else world
    steps_per_call = max(int(steps_per_call), 1)

    # opt-in per-iteration hang guard: a wedged collective mid-benchmark
    # otherwise blocks forever with no diagnosis. DEAR_STEP_WATCHDOG_SECS
    # sets the heartbeat deadline (one timed iteration must finish within
    # it); on timeout the watchdog dumps open telemetry spans + thread
    # stacks and aborts with the last completed iteration number. It only
    # arms at the first timed iteration — warmup includes jit compilation.
    dog_secs = float(os.environ.get("DEAR_STEP_WATCHDOG_SECS", "0"))
    dog = None
    if dog_secs > 0:
        from dear_pytorch_tpu.resilience import StepWatchdog

        dog = StepWatchdog(dog_secs, name="bench-step-watchdog").start()
    try:
        log("Running warmup...")
        for _ in range(num_warmup_batches):
            step_fn()
        if sync is not None:
            sync()

        log("Running benchmark...")
        # run health on the timed loop: every iteration lands in the
        # flight ring and feeds the anomaly detectors (a mid-benchmark
        # step-time spike or input stall raises health.* counters that
        # end up in the TELEMETRY block); both gates are no-ops when
        # telemetry is off
        from dear_pytorch_tpu.observability import anomaly as _anomaly
        from dear_pytorch_tpu.observability import flight as _flight
        from dear_pytorch_tpu.observability import tracer as _tracer

        fl = _flight.get_recorder()
        # per-phase ring: bench.py reuses the process-global recorder
        # across models, and the end-of-run step-time gauges below must
        # not mix this phase's quantiles with the previous workload's
        fl.clear()
        tr = _tracer.get_tracer()
        monitor = None
        if tr.enabled and _anomaly.AnomalyMonitor.enabled_by_env():
            overrides = {"tracer": tr}
            if not os.environ.get("DEAR_HEALTH_WARMUP", "").strip():
                overrides["warmup"] = 2  # few timed iters: arm early
            monitor = _anomaly.AnomalyMonitor.from_env(**overrides)
        per_iter, iter_times = [], []
        for x in range(num_iters):
            if dog is not None:
                dog.beat(phase="timed", iter=x)
            t0 = time.perf_counter()
            for _ in range(num_batches_per_iter):
                step_fn()
            if sync is not None:
                sync()
            dt = time.perf_counter() - t0
            thr = batch_size * num_batches_per_iter / dt
            log(f"Iter #{x}: {thr:.1f} {unit}/sec per {dev}")
            per_iter.append(thr)
            # per REAL train step, independent of the scanned-dispatch shape
            step_time_s = dt / (num_batches_per_iter * steps_per_call)
            iter_times.append(step_time_s)
            if fl.enabled:
                fl.record((x + 1) * num_batches_per_iter * steps_per_call,
                          step_time_s=step_time_s, iter=x)
            if monitor is not None:
                mfu = None
                if flops_per_step:
                    from dear_pytorch_tpu.utils import perf_model

                    mfu = perf_model.mfu(flops_per_step, step_time_s,
                                         jax.devices()[0])
                monitor.observe(step=x, step_time_s=step_time_s,
                                counters=tr.counters(), mfu=mfu)
            if metrics is not None:
                metrics.log(
                    iter=x, **{f"{unit}_per_sec_per_device": thr},
                    step_time_s=step_time_s,
                )
    finally:
        if dog is not None:
            dog.stop()

    res = BenchResult(
        unit=unit,
        device=dev,
        world=world,
        per_device_mean=float(np.mean(per_iter)),
        per_device_conf=float(1.96 * np.std(per_iter)),
        iter_time_mean=float(np.mean(iter_times)),
        iter_time_conf=float(1.96 * np.std(iter_times)),
        per_iter=per_iter,
    )
    log(f"Iteration time: {res.iter_time_mean:.3f} +-{res.iter_time_conf:.3f}")
    log(f"{unit.capitalize()}/sec per {dev}: "
        f"{res.per_device_mean:.1f} +-{res.per_device_conf:.1f}")
    log(f"Total {unit}/sec on {res.world} {dev}(s): "
        f"{res.total_mean:.1f} +-{res.total_conf:.1f}")
    if metrics is not None:
        metrics.log(
            summary=True, world=res.world, unit=unit,
            per_device_mean=res.per_device_mean,
            per_device_conf=res.per_device_conf,
            iter_time_mean=res.iter_time_mean,
        )
    # Telemetry block: when DEAR_TELEMETRY is on, one scrape-able line per
    # run (the batch driver lifts it into reports.json) and one JSONL
    # record (read back via `read_metrics`; the dict travels as a JSON
    # string because MetricsLogger records hold scalars).
    from dear_pytorch_tpu.observability import snapshot

    snap = snapshot()
    if snap["enabled"]:
        log("TELEMETRY " + json.dumps(snap))
        if metrics is not None:
            metrics.log(kind="telemetry", telemetry=json.dumps(snap))
        # feed any prom:/stream: sinks one end-of-run snapshot
        from dear_pytorch_tpu.observability import export as _export

        gauges = {"step_time_mean_seconds": res.iter_time_mean}
        st = fl.step_time_stats() if fl.enabled else {}
        if st:
            gauges.update(step_time_p50_seconds=st["p50_s"],
                          step_time_max_seconds=st["max_s"])
        _export.write_streams(snap, gauges, tracer=tr)  # never raises
    return res


def add_common_args(parser) -> None:
    """The reference benchmarks' shared CLI surface
    (dear/imagenet_benchmark.py:24-56), minus CUDA-isms, plus the unified
    ``--mode`` switch that replaces the reference's edit-an-import-line
    backend selection (dear/imagenet_benchmark.py:14-16)."""
    parser.add_argument("--fp16", action="store_true", default=False,
                        help="bfloat16 compute (TPU mixed precision)")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="input batch size PER DEVICE")
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=5)
    parser.add_argument("--mode", type=str, default="dear",
                        choices=["dear", "dear-fused", "allreduce", "rsag",
                                 "rb", "bytescheduler", "fsdp"],
                        help="communication schedule (replaces the "
                             "reference's per-directory baselines; 'fsdp' "
                             "= ZeRO-3 re-gather-in-backward; 'dear-fused' "
                             "= dear with Pallas ring kernels fusing the "
                             "reduce-scatter into the optimizer epilogue "
                             "and the all-gather into a remote-copy ring, "
                             "ops/collective_matmul.py)")
    parser.add_argument("--partition", type=float, default=4.0,
                        help="bytescheduler partition size in MB "
                             "(reference bytescheduler --partition, "
                             "imagenet_benchmark.py:37-38)")
    parser.add_argument("--pipeline", type=str, default="none",
                        choices=["none", "native", "numpy"],
                        help="input pipeline: 'none' re-feeds one "
                             "pre-generated batch (the reference's "
                             "fixed-fake-data protocol, "
                             "imagenet_benchmark.py:97-103); 'native' "
                             "streams fresh batches from the C++ "
                             "ring-buffer producers (csrc/dear_runtime.cpp); "
                             "'numpy' uses the pure-python pipeline")
    parser.add_argument("--threshold", type=float, default=25.0,
                        help="tensor-fusion threshold in MB "
                             "(reference THRESHOLD, dear/dopt_rsag.py:37); "
                             "<=0 disables the limit (single bucket)")
    parser.add_argument("--nearby-layers", type=int, default=None,
                        help="fuse every k layers instead of by threshold")
    parser.add_argument("--compressor", type=str, default="none",
                        help="gradient compressor (reference "
                             "dear/compression.py registry)")
    parser.add_argument("--density", type=float, default=1.0,
                        help="sparsification density for topk-family "
                             "compressors")
    parser.add_argument("--momentum-correction", type=float, default=0.0,
                        help="DGC-style momentum correction coefficient "
                             "for sparse compressed training (reference "
                             "wfbp/dopt.py:769-775; disables optimizer "
                             "momentum while active)")
    parser.add_argument("--gtopk", action="store_true", default=False,
                        help="gTop-k recursive-halving sparse allreduce "
                             "(with a top-k-family --compressor)")
    parser.add_argument("--mgwfbp", action="store_true", default=False,
                        help="analytic MG-WFBP bucket sizing: measure ICI "
                             "alpha-beta, estimate layer times, merge "
                             "buckets per the INFOCOM'19 model (reference "
                             "wfbp/dopt.py:380-486)")
    parser.add_argument("--autotune", type=str, default=None,
                        choices=["bo", "wait_time", "plan"],
                        help="runtime fusion tuning: Bayesian optimization "
                             "over the threshold (reference dopt_rsag_bo), "
                             "wait-time split flags (dopt_rsag_wt), or "
                             "'plan' — the unified plan-space search over "
                             "fusion x compression x wire dtypes x mode x "
                             "remat (docs/TUNING.md; restrict axes via "
                             "DEAR_TUNE_* env)")
    parser.add_argument("--tune-steps", type=int, default=None,
                        help="drive the autotuner for this many steps "
                             "BEFORE the timed protocol (tune-then-"
                             "measure: the timed region runs the CONVERGED "
                             "config). Default: the tuner's full trial "
                             "budget for --autotune plan, 0 for bo/"
                             "wait_time (their legacy tune-while-measuring "
                             "behavior)")
    parser.add_argument("--remat-policy", type=str, default=None,
                        choices=["none", "full"],
                        help="rematerialize the whole forward during "
                             "backward at the TRAIN-STEP level "
                             "(jax.checkpoint around the loss; also a "
                             "plan-space autotuner axis). Distinct from "
                             "the GPT bench's model-level --remat, which "
                             "checkpoints per block")
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="gradient accumulation: split each per-device "
                             "batch into this many scanned microbatches; "
                             "collectives and the update run once per step")
    parser.add_argument("--scan-steps", type=int, default=1,
                        help="compile k train steps as ONE lax.scan program "
                             "per dispatch (TrainStep.multi_step): amortizes "
                             "host dispatch latency and exposes "
                             "cross-step overlap to the scheduler; requires "
                             "--pipeline none and no --autotune")
    parser.add_argument("--base-lr", type=float, default=0.01)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--optimizer", type=str, default="sgd",
                        choices=["sgd", "adamw", "lamb"],
                        help="fused shard-safe optimizer (adamw = torch "
                             "semantics, real-world BERT pretraining; lamb "
                             "= large-batch BERT with exact per-parameter "
                             "trust ratios on ZeRO shards — both beyond "
                             "the reference's SGD-only fused path); betas/"
                             "eps/weight decay via DEAR_ADAM_BETAS, "
                             "DEAR_ADAM_EPS, DEAR_WEIGHT_DECAY")
    parser.add_argument("--clip-norm", type=float, default=None,
                        help="clip gradients to this global L2 norm "
                             "(exact under sharding: shard square-norms "
                             "psum across the mesh)")
    parser.add_argument("--lr-schedule", type=str, default=None,
                        choices=["linear", "cosine", "multistep"],
                        help="lr schedule evaluated ON DEVICE from the "
                             "global step (exact under --scan-steps): "
                             "linear/cosine warmup+decay need "
                             "--total-steps; multistep uses "
                             "DEAR_LR_MILESTONES/DEAR_LR_GAMMA")
    parser.add_argument("--warmup-steps", type=int, default=0,
                        help="linear warmup length for --lr-schedule")
    parser.add_argument("--total-steps", type=int, default=None,
                        help="decay horizon for --lr-schedule "
                             "linear/cosine")
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="write a jax.profiler trace of the timed "
                             "region here")
    parser.add_argument("--metrics-file", type=str, default=None,
                        help="append per-iteration + summary records as "
                             "JSONL here (utils.MetricsLogger; replaces "
                             "the reference's log-scrape observability)")
    parser.add_argument("--mfu", action="store_true", default=False,
                        help="report model FLOPs utilization from XLA cost "
                             "analysis (the reference's nvprof FLOPs "
                             "accounting, horovod/prof.sh + "
                             "extract_profilings.py; costs one extra AOT "
                             "compile)")


def build_sp_mesh(sp: int, seq_len: int, pipeline: str,
                  seq_flag: str = "--sentence-len"):
    """dp x sp mesh for a sequence-parallel CLI run, with the shared
    validation both BERT and GPT benches need. `backend.init()` runs first
    for the (multi-host) bootstrap without fixing the axes — it is
    idempotent and another mesh may already be installed."""
    import numpy as np

    from dear_pytorch_tpu.comm.backend import DP_AXIS, SP_AXIS

    backend.init()
    devices = jax.devices()
    ndev = len(devices)
    if ndev % sp:
        raise SystemExit(f"--sp-degree {sp} does not divide the "
                         f"{ndev}-device world")
    if seq_len % sp:
        raise SystemExit(f"{seq_flag} {seq_len} must divide by "
                         f"--sp-degree {sp}")
    if pipeline != "none":
        raise SystemExit("--pipeline streaming is dp-only; use "
                         "--pipeline none with --sp-degree")
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(ndev // sp, sp), (DP_AXIS, SP_AXIS)
    )


def metrics_from_args(args):
    """`utils.MetricsLogger` for ``--metrics-file`` (None when unset); the
    single construction point shared by the CLIs."""
    if not getattr(args, "metrics_file", None):
        return None
    from dear_pytorch_tpu.utils import MetricsLogger

    return MetricsLogger(args.metrics_file)


def stage_global(tree, sharding):
    """Stage host-replicated arrays onto a (possibly multi-host) sharding.

    Single-process: plain `jax.device_put`. Multi-process: `device_put`
    onto a sharding with non-addressable devices raises, so each process
    materializes ONLY its addressable shards from the host copy
    (`make_array_from_callback`) — every host is assumed to hold the same
    full array (the synthetic-data protocol; a real loader would hand each
    host its slice instead).
    """
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)

    def put(x):  # pragma: no cover - multi-host only
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        )

    return jax.tree.map(put, tree)


def make_batch_source(args, spec, sharding, template_batch):
    """``(next_batch, close)`` for the timed loop, honoring ``--pipeline``.

    'none' returns the constant pre-staged ``template_batch`` every step
    (the reference's fixed-fake-data measurement protocol). 'native'/'numpy'
    stream fresh host batches from `runtime.Pipeline` — produced by C++
    ring-buffer threads (or the pure-numpy pipeline) while the previous
    step runs — and stage each onto the mesh via `stage_global` (multi-host
    safe: each process materializes only its addressable shards).
    """
    if args.pipeline == "none":
        return (lambda: template_batch), (lambda: None)

    import jax

    from dear_pytorch_tpu.runtime import pipeline as RP

    # 'native' raises when csrc/dear_runtime.cpp cannot be built or loaded
    pl = (RP.Pipeline(spec) if args.pipeline == "native"
          else RP.NumpyPipeline(spec))

    # stage in the template's dtypes: under --fp16 the template is bf16 and
    # staging the pipeline's f32 fields raw would double the host->device
    # bytes — exactly the transfer cost this flag exists to measure
    tmpl_dtypes = {k: v.dtype for k, v in template_batch.items()}

    def next_batch():
        host = pl.next()
        return stage_global(
            {k: v.astype(tmpl_dtypes[k], copy=False)
             for k, v in host.items()},
            sharding,
        )

    return next_batch, pl.close


def log_mfu(ts, state, batch, result: BenchResult,
            flops: Optional[float] = None) -> Optional[float]:
    """Log achieved FLOP/s + MFU for the compiled train step (enable with
    ``--mfu``). ``result.iter_time_mean`` is per REAL step under every
    protocol (run_timed's steps_per_call accounting). ``flops`` reuses a
    `step_flops` value computed before the timed run (no second AOT
    compile)."""
    from dear_pytorch_tpu.utils import perf_model

    if flops is None:
        flops = step_flops(ts, state, batch) or 0.0
    secs = result.iter_time_mean
    value = perf_model.mfu(flops, secs, jax.devices()[0])
    achieved = flops / secs if secs else 0.0
    if value:
        log(f"MFU: {100 * value:.1f}% "
            f"({flops / 1e9:.2f} GFLOP/step, {achieved / 1e12:.1f} TFLOP/s)")
    else:
        log(f"FLOP/step: {flops / 1e9:.2f} GFLOP "
            f"({achieved / 1e12:.2f} TFLOP/s; peak unknown for "
            f"{device_name()})")
    return value


def threshold_mb(args) -> Optional[float]:
    return None if args.threshold is None or args.threshold <= 0 else float(args.threshold)


def config_from_args(args, *, fp16_comm: bool = True,
                     world: Optional[int] = None):
    """CLI args -> `DearConfig` (env DEAR_* vars fill anything the CLI does
    not own, e.g. weight_decay/nesterov), with the reference's
    accepted-but-inactive warnings.

    ``world``: dp size of the mesh the step will run on. The bf16
    pre-gather cast halves AG bytes on ICI but is pure overhead when there
    is no gather traffic — the 2026-07-31 on-chip A/B measured f32 gathers
    at +4.5% BERT-Base throughput at world=1 (PERF.md round-4) — so
    world=1 disables it. None (callers that sweep worlds, e.g.
    benchmarks/scaling.py, where one config serves every cell) keeps the
    multi-chip bf16 default."""
    import warnings

    import jax.numpy as jnp

    from dear_pytorch_tpu.config import DearConfig

    use_compression = (args.compressor != "none"
                       and args.mode in ("allreduce", "dear", "dear-fused"))
    if args.compressor != "none" and not use_compression:
        # the baseline schedules accept-and-ignore the compression surface
        # (reference dear/dear_dopt.py:381-398 warning). 'dear-fused' is
        # deliberately NOT filtered here: the compressor flows to
        # build_train_step, which rejects the combination loudly at
        # plan-build time — a warned-and-dropped flag would report
        # dense-schedule timings for a run the user asked to compress.
        warnings.warn(
            f"--compressor is ignored by the {args.mode!r} schedule; "
            "use --mode allreduce or --mode dear."
        )
    if args.density < 1.0 and args.compressor == "none":
        warnings.warn(
            "--density without --compressor has no effect (dense gradients)"
        )
    return DearConfig.from_env(
        mode=args.mode,
        threshold_mb=threshold_mb(args),
        nearby_layers=args.nearby_layers,
        autotune=args.autotune,
        compressor=args.compressor if use_compression else None,
        density=args.density,
        gtopk=args.gtopk and use_compression,
        momentum_correction=(
            args.momentum_correction if use_compression else 0.0
        ),
        optimizer_name=getattr(args, "optimizer", "sgd"),
        lr=args.base_lr,
        momentum=args.momentum,
        clip_norm=args.clip_norm,
        # lr-schedule flags pass through only when the user set them, so
        # DEAR_LR_SCHEDULE / DEAR_WARMUP_STEPS / DEAR_TOTAL_STEPS env vars
        # stay live behind unset flags (from_env overrides win otherwise)
        **{k: v for k, v in {
            "lr_schedule": getattr(args, "lr_schedule", None),
            "warmup_steps": getattr(args, "warmup_steps", 0),
            "total_steps": getattr(args, "total_steps", None),
            "remat": getattr(args, "remat_policy", None),
        }.items() if v},
        # fsdp communicates both legs in gather_dtype (RS = gather transpose)
        comm_dtype=(jnp.bfloat16
                    if (args.fp16 and fp16_comm and args.mode != "fsdp")
                    else None),
        # dear mode too: halves the all-gather bytes and matches the fsdp
        # schedule's precision. bf16-compute kernels see identical inputs
        # (their own cast becomes the identity); the rare fp32-dtype
        # submodule (e.g. the BERT NSP head) sees bf16-rounded params — the
        # same values fsdp mode feeds it. Skipped at world=1 (see above).
        gather_dtype=(jnp.bfloat16
                      if (args.fp16 and fp16_comm and world != 1
                          and args.mode in ("dear", "fsdp"))
                      else None),
        rng_seed=42,
        partition_mb=args.partition,
        accum_steps=args.accum_steps,
    )


def validate_scan_steps(args) -> int:
    """Resolve --scan-steps; call IMMEDIATELY after parse_args so rejected
    combinations fail before any pipeline/tuner resources are created."""
    k = int(getattr(args, "scan_steps", 1) or 1)
    if k <= 1:
        return 1
    if args.pipeline != "none":
        raise SystemExit("--scan-steps re-feeds one constant batch inside "
                         "the scanned program; incompatible with --pipeline")
    if args.autotune:
        raise SystemExit("--scan-steps and --autotune are incompatible "
                         "(the tuner re-buckets between steps)")
    return k


def _ceil_div_keep_zero(n: int, k: int) -> int:
    return -(-n // k) if n > 0 else 0


def make_step_source(args, scan_steps: int, ts, stepper, holder,
                     next_batch):
    """(step_fn, run_timed protocol kwargs) honoring ``--scan-steps``.

    Scanned mode compiles ``scan_steps`` steps as ONE lax.scan program
    (`TrainStep.multi_step`) on the constant batch in ``holder['batch']``;
    warmup/iteration counts convert to dispatch calls by ceiling division
    (a zero warmup stays zero — cold-start measurements are a thing).
    """
    if scan_steps > 1:
        log(f"Scanned protocol: {scan_steps} steps per dispatch")
        runner_fn = ts.multi_step(scan_steps)

        def step_fn():
            holder["state"], holder["metrics"] = runner_fn(
                holder["state"], holder["batch"]
            )
    else:
        def step_fn():
            holder["state"], holder["metrics"] = stepper.step(
                holder["state"], next_batch()
            )

    kwargs = dict(
        batch_size=args.batch_size * scan_steps,
        num_warmup_batches=_ceil_div_keep_zero(
            args.num_warmup_batches, scan_steps
        ),
        num_batches_per_iter=max(
            _ceil_div_keep_zero(args.num_batches_per_iter, scan_steps), 1
        ),
        num_iters=args.num_iters,
        steps_per_call=scan_steps,
    )
    return step_fn, kwargs


def run_pretune(args, stepper, holder, next_batch) -> int:
    """Tune-then-measure: drive the autotuner to convergence BEFORE the
    warmup/timed protocol, so the timed region measures the CONVERGED
    configuration (what a deployed run would sustain) instead of mixing
    trial plans into the throughput number. Returns the steps spent.

    ``--tune-steps`` overrides the budget; by default only the 'plan'
    strategy pre-tunes (bo/wait_time keep their legacy tune-while-
    measuring behavior unless --tune-steps is set explicitly).
    """
    if not getattr(args, "autotune", None):
        return 0
    tuner = getattr(stepper, "tuner", None)
    n = getattr(args, "tune_steps", None)
    if n is None:
        if args.autotune != "plan":
            return 0
        n = getattr(tuner, "budget_steps", 0) if tuner is not None else 0
    n = int(n)
    if n <= 0:
        return 0
    log(f"Pre-tuning: up to {n} steps "
        "(tune-then-measure; the timed region runs the converged config)")
    for _ in range(n):
        holder["state"], holder["metrics"] = stepper.step(
            holder["state"], next_batch()
        )
        if tuner is not None and getattr(tuner, "finished", False):
            break
    planner = getattr(stepper, "planner", None)
    if planner is not None:
        if planner.finished:
            log(f"Converged plan config: {planner.current.describe()}")
        else:
            # the loop ran out of --tune-steps mid-search: say so — the
            # timed region will keep mixing tuner trials into the number
            log(f"Plan tuner NOT converged after {n} pre-tune steps; "
                f"current trial config: {planner.current.describe()} "
                "(timed region may include further trials)")
        snap = planner.summary()
        log("TUNE_SUMMARY " + json.dumps(snap))
    return n


def build_stepper(cfg, loss_fn, params, mesh, *, model_state=None,
                  mgwfbp=False, **extra):
    """(train_step, stepper) from a `DearConfig` — the single construction
    path shared by the CNN and BERT CLIs. ``stepper.step(state, batch)`` is
    what the timed loop calls (the AutoTuner when tuning, the TrainStep
    otherwise). ``extra`` forwards to `build_train_step` (multi-axis
    options: axis_name/mean_axes/batch_spec_fn for the sp path)."""
    from dear_pytorch_tpu.parallel import dear as D

    if mgwfbp and cfg.autotune:
        raise SystemExit("--mgwfbp and --autotune are mutually exclusive: "
                         "both own the fusion plan")
    kwargs = dict(cfg.build_kwargs(), mesh=mesh,
                  model_state_template=model_state, **extra)
    if cfg.autotune:
        from dear_pytorch_tpu.tuning import AutoTuner

        tuned = AutoTuner(
            loss_fn, params,
            strategy=cfg.autotune,
            threshold_mb=cfg.threshold_mb or 25.0,
            bound=cfg.bo_bound, max_trials=cfg.bo_trials,
            interval=cfg.bo_interval, cycle_time_s=cfg.cycle_time_s,
            log=log, **kwargs,
        )
        return tuned.ts, tuned

    plan = None
    if mgwfbp:
        from dear_pytorch_tpu.tuning import (
            estimate_layer_backward_times,
            plan_mgwfbp,
        )
        from dear_pytorch_tpu.utils import CommunicationProfiler

        alpha, beta = CommunicationProfiler(mesh).fit(
            sizes=[2 ** k for k in range(10, 21, 2)], repeats=3
        )
        log(f"MG-WFBP: measured alpha={alpha:.2e}s beta={beta:.2e}s/B")
        plan = plan_mgwfbp(
            params, mesh.shape["dp"],
            layer_times=estimate_layer_backward_times(params),
            alpha=alpha, beta=beta,
        )
        log(f"MG-WFBP plan: {plan.num_buckets} buckets")

    ts = D.build_train_step(
        loss_fn, params,
        threshold_mb=cfg.threshold_mb,
        nearby_layers=cfg.nearby_layers,
        flags=cfg.flags,
        plan=plan,
        **kwargs,
    )
    return ts, ts
