"""One run of one cell of BENCHMARK.json on the TPU this process is started on.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, `backend.init`, weights and batch on the device from the
seed, compile or cache hit, the reference check, warm-up) is timed as
``setup_s``; then ``--trace 0`` measures for ``--seconds`` with the profiler
off and reports the cell's end-to-end metrics, and ``--trace 1`` profiles a
short stretch and reports its per-layer metrics. The last line of stdout is
the one JSON object of the contract; earlier lines are a log. Refuses to run
(exit 2, no result line) unless JAX reports a TPU with the chips the cell
asks for.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def log_window(rec: dict, built: dict, chips: int, peaks: dict) -> dict:
    """The window's statistics, printed with the model-FLOPs utilization
    (a constant times the rate within a cell, so logged, not judged) and
    every step interval."""
    from perfbench import harness, steploop

    done = rec["done"]
    stats = steploop.window_metrics(done, built["tokens_per_step"], chips)
    mfu = (stats["tokens_per_s_per_chip"] * built["flops_per_step"]
           / built["tokens_per_step"] / peaks["bf16_flops_per_s"])
    harness.log(
        f"[window] {stats['intervals']} step intervals in "
        f"{done[-1] - done[0]:.2f} s: median {stats['step_ms_median']:.3f} "
        f"ms, p95 {stats['step_ms_p95']:.3f} ms, max "
        f"{stats['step_ms_max']:.3f} ms; "
        f"{stats['tokens_per_s_per_chip']:.1f} tokens/s/chip; model FLOPs "
        f"utilization {100 * mfu:.2f}% of "
        f"{peaks['bf16_flops_per_s'] / 1e12:.0f} TFLOP/s; host dispatch "
        f"median {statistics.median(rec['dispatch_s']) * 1e3:.3f} ms")
    harness.log("[window] intervals_ms: " + " ".join(
        f"{(b - a) * 1e3:.2f}" for a, b in zip(done, done[1:])))
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: a "
                         "temporary directory, removed)")
    args = ap.parse_args(argv)

    # The persistent compilation cache lives at one fixed path inside the
    # checkout unless the machine names another; the program's
    # `backend.init` then sets none of its own.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax

    # cache every program, also those that compile in under a second: the
    # second run of a cell in a checkout then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # ... and evict none: under the chip machine's 192 MiB cap
    # (JAX_COMPILATION_CACHE_MAX_SIZE) one BERT-Large run's programs push
    # each other out, and every run compiled for ten minutes (PR 27)
    jax.config.update("jax_compilation_cache_max_size", -1)

    from perfbench import cell as cells
    from perfbench import harness, xplane

    cell = cells.resolve(args.workload)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"perfbench needs a TPU; JAX reports {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chip(s); JAX reports "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = cells.peaks(dev.device_kind)
    log = harness.log
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")

    from dear_pytorch_tpu.comm import backend

    mesh = backend.init(devices=devices[:cell.chips])
    setup = {"import_and_backend_s": time.perf_counter() - _T_START}

    built = harness.build(cell, mesh, args.seed)
    t = time.perf_counter()
    reference = harness.reference_check(cell, mesh, args.seed)
    setup["reference_check_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = harness.warm_up(built, cell.traffic["warmup_steps"])
    setup["warm_up_s"] = time.perf_counter() - t
    setup.update(built["spans"])
    setup_s = time.perf_counter() - _T_START
    log("[setup] " + ", ".join(f"{k} {v:.2f}" for k, v in setup.items())
        + f"; setup_s {setup_s:.2f}")

    values = {"setup_s": setup_s,
              "peak_hbm_gb": built["peak_hbm_bytes"] / 1e9}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    extra = {}
    if args.trace:
        wanted = cell.per_layer
        rec, trace = harness.traced_stretch(
            built, cell.traffic["trace_steps"], args.trace_dir)
        run = {"cell": cell, "built": built, "record": rec, "trace": trace,
               "peaks": peaks, "setup": setup}
        for m in wanted:
            value = cells.layer_reader(m["name"])(run)
            if value is not None:
                values[m["name"]] = value
        busy_s, window_s = trace.busy_and_window_s()
        device.update(busy_s=busy_s, window_s=window_s)
        first = trace.devices[0]
        extra["breakdown"] = {
            "device_ops": xplane.top_device_ops(first),
            "idle_gaps": xplane.idle_gaps_by_host(first, trace.host_spans)}
    else:
        wanted = cell.end_to_end
        rec = harness.timed_window(built, args.seconds)
        values.update(log_window(rec, built, cell.chips, peaks))

    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    live_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"[memory] step program {built['peak_hbm_bytes'] / 1e9:.3f} GB "
        f"(memory_analysis); allocator peak_bytes_in_use {live_peak / 1e9:.3f}"
        " GB (live arrays only on this runtime)")
    # the fullest chip's peak: the larger of what the allocator saw and what
    # the running step program holds, temporaries included
    device["memory_peak_bytes"] = int(max(live_peak,
                                          built["peak_hbm_bytes"]))

    losses = rec["losses"]
    # losses[0] is the step in flight when the window opened; the rest are
    # the steps dispatched inside it, the drained last one included
    failed = sum(1 for x in losses[1:] if not math.isfinite(x))
    correct = bool(reference["ok"]
                   and harness.losses_ok(built, warm, losses))
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": correct, "attempted": rec["attempted"], "failed": failed,
        "metrics": {name: {"value": values[name], "unit": unit}
                    for name, unit in units.items() if name in values},
        "device": device, **extra}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
