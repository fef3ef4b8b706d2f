"""Analytic α-β communication/computation cost models.

The reference hard-codes α-β constants measured on its GPU clusters for
10GbE/56Gbps interconnects per worker count (reference dear/utils.py:62-88,
wfbp/dopt.py:385-400) and fits fresh ones with sklearn LinearRegression
(wfbp/dopt.py:260-285). On TPU the constants come from measuring XLA
collectives over ICI with `profiling.CommunicationProfiler` and fitting here
with a plain least-squares — no sklearn, no hard-coded tables (ICI bandwidth
is uniform enough within a pod that one (α, β) pair per topology suffices).
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def predict_allreduce_time(alpha: float, beta: float, nbytes: float) -> float:
    """t = α + β·nbytes (reference ``predict_allreduce_time_with_size``,
    dear/utils.py:151-154)."""
    return alpha + beta * nbytes


def fit_alpha_beta(
    sizes_bytes: Sequence[float], times_s: Sequence[float]
) -> tuple[float, float]:
    """Least-squares fit of t ≈ α + β·size (replaces the sklearn
    LinearRegression fit, wfbp/dopt.py:260-285). Returns (α, β), clipped to
    be non-negative."""
    A = np.vstack([np.ones(len(sizes_bytes)), np.asarray(sizes_bytes)]).T
    (alpha, beta), *_ = np.linalg.lstsq(A, np.asarray(times_s), rcond=None)
    return max(float(alpha), 0.0), max(float(beta), 0.0)


#: bf16 peak FLOP/s per chip by device-kind substring.
DEVICE_PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6": 918e12,
}


def device_peak_flops(device) -> float:
    """bf16 peak FLOP/s for a jax.Device (0.0 when unknown — callers should
    then report MFU as unavailable rather than guessing)."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in DEVICE_PEAK_FLOPS.items():
        if key in kind:
            return peak
    return 0.0


def mfu(flops_per_step: float, secs_per_step: float, device) -> float:
    """Model FLOPs utilization: achieved FLOP/s over the chip's bf16 peak
    (the accounting the reference derives from nvprof dumps,
    horovod/prof.sh:1-2 + extract_profilings.py:3-11 — here XLA cost
    analysis makes it exact and free)."""
    peak = device_peak_flops(device)
    if not (flops_per_step and peak and secs_per_step):
        return 0.0
    return flops_per_step / secs_per_step / peak


def peak_hbm_bytes(compiled) -> float:
    """Peak device memory of a compiled executable (argument + output +
    temp + generated code, less aliased bytes), from XLA's memory
    analysis. The reference has no analog — GPU peak memory there is
    whatever nvidia-smi happens to show; on TPU the compiler knows the
    exact static allocation."""
    m = compiled.memory_analysis()
    return float(
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
        - m.alias_size_in_bytes
    )


def topk_perf_model(n: int, s: float = 2.18e-9) -> float:
    """Cost model of a top-k over n elements, s·n·log2 n (reference
    dear/utils.py:95-102)."""
    if n <= 1:
        return 0.0
    return s * n * math.log2(n)


def allgather_perf_model(
    nbytes: float, world: int, alpha: float, beta: float
) -> float:
    """Ring all-gather cost: (world-1) rounds of α + β·(nbytes/world)
    (reference dear/utils.py:104-117 models allgather for the sparse path)."""
    if world <= 1:
        return 0.0
    return (world - 1) * (alpha + beta * nbytes / world)
