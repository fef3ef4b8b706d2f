"""The overlap-efficiency auditor: was the collective actually hidden?

DeAR's value claim is that reduce-scatter hides under backprop and
all-gather hides under the next forward — yet throughput alone cannot say
whether a schedule won because it overlapped communication or because it
did less of it. This module combines three measurements the repo already
produces but never joined:

  1. static schedule accounting (`counters.plan_comm_accounting`) — how
     many bytes each bucket's collective legs move per step,
  2. a measured α-β interconnect fit (`utils.perf_model` /
     `utils.profiling.CommunicationProfiler`) — what those bytes cost in
     seconds when nothing overlaps,
  3. measured step time (and measured or modeled compute time),

into one report per schedule mode:

  serial_step   = compute + comm          (nothing overlaps)
  ideal_step    = max(compute, comm)      (everything overlaps)
  exposed_comm  = clip(measured - compute, 0, comm)
  hidden_comm   = comm - exposed_comm
  overlap_efficiency = (serial - measured) / (serial - ideal)  in [0, 1]

so 1.0 means the schedule hid everything the hardware allowed and 0.0
means it serialized. A structural cross-check rides along: the compiled
HLO's per-collective *independent-compute fraction* (the share of compute
ops with no dependency path to/from the collective — what any scheduler
on any backend could overlap; scripts/overlap_report.py introduced the
metric, this module owns it now).

Per-bucket exposed/hidden attribution is proportional to each leg's
predicted α-β time — the measurement is whole-step, so the split is a
model-weighted attribution, not a per-bucket measurement (stated in the
report rather than silently implied).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

from dear_pytorch_tpu.observability import counters as CTR
from dear_pytorch_tpu.utils import perf_model

#: collective opcodes scored by the HLO structural metric
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


@dataclasses.dataclass(frozen=True)
class BucketLegReport:
    """One bucket collective leg: bytes, predicted cost, attribution."""

    bucket: int
    leg: str
    payload_bytes: int
    wire_bytes: float
    pred_time_s: float
    exposed_s: Optional[float]   # None when no measured step time was given
    hidden_s: Optional[float]


@dataclasses.dataclass(frozen=True)
class OverlapReport:
    """Overlap-efficiency audit of one schedule mode."""

    mode: str
    world: int
    num_buckets: int
    alpha: float
    beta: float
    compute_time_s: Optional[float]
    comm_time_s: float
    measured_step_s: Optional[float]
    ideal_step_s: Optional[float]
    serial_step_s: Optional[float]
    exposed_comm_s: Optional[float]
    hidden_comm_s: Optional[float]
    overlap_efficiency: Optional[float]
    flops_per_step: Optional[float]
    legs: tuple[BucketLegReport, ...]
    hlo: Optional[dict] = None    # structural metric (None when skipped)
    model_note: Optional[str] = None  # set when measurement defies the model

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["legs"] = [dataclasses.asdict(leg) for leg in self.legs]
        return d


def predict_leg_times(
    acct: CTR.CommAccounting, alpha: float, beta: float,
    *, dcn_alpha: Optional[float] = None, dcn_beta: Optional[float] = None,
) -> list[float]:
    """Predicted unoverlapped seconds for each accounting row, consistent
    with `perf_model.allgather_perf_model`: ring legs cost
    ``(world-1)·α + β·wire_bytes`` (RS and AG each run world-1 rounds of
    1/world of the payload; all-reduce's wire bytes already carry the 2×),
    root legs (reduce/broadcast) cost ``α + β·payload``.

    The hierarchical schedule's 'dcn' rows (cross-slice host exchange,
    ``num_slices > 1`` accounting) are priced LINK-AWARE with their own
    (``dcn_alpha``, ``dcn_beta``) fit — the FlexLink point: ICI and DCN
    are different links with α-β gaps of orders of magnitude, so one fit
    cannot cost both levels. They cost ``messages·α_dcn + β_dcn·wire``
    (``messages`` already counts chunks × peer slices). When no DCN fit
    is given those rows fall back to the intra-slice fit — stated
    behavior for callers without a measured DCN profile, not an
    endorsement."""
    w = acct.world
    a_d = alpha if dcn_alpha is None else float(dcn_alpha)
    b_d = beta if dcn_beta is None else float(dcn_beta)
    times = []
    for row in acct.rows:
        if row.leg == "dcn":
            times.append(row.messages * a_d + b_d * row.wire_bytes)
        elif w <= 1:
            times.append(0.0)
        elif row.leg in ("reduce_scatter", "all_gather"):
            times.append((w - 1) * alpha + beta * row.wire_bytes)
        elif row.leg == "all_reduce":
            times.append(2 * (w - 1) * alpha + beta * row.wire_bytes)
        else:  # reduce / broadcast: one full-payload transfer each
            times.append(alpha + beta * row.payload_bytes)
    return times


def hlo_collective_stats(compiled_text: str) -> dict:
    """Structural overlappability of a compiled program: for every
    collective, the fraction of compute ops (fusion/dot/convolution) with
    no dependency path to or from it. Independent compute is what a
    latency-hiding scheduler may run concurrently; a low fraction means
    the GRAPH serialized the collective and no backend can hide it."""
    from dear_pytorch_tpu.utils import hlo

    ops = hlo.parse_entry(compiled_text)
    computes = hlo.compute_ops(ops)
    if not computes:
        return {"error": "no compute ops parsed"}
    anc_of_compute = {c.name: hlo.ancestors(ops, c.name) for c in computes}

    per_kind: dict = {}
    fractions: list[float] = []
    for kind in COLLECTIVE_KINDS:
        colls = hlo.find(ops, kind)
        if not colls:
            continue
        kind_fracs = []
        for coll in colls:
            coll_anc = hlo.ancestors(ops, coll.name)
            indep = sum(
                1 for c in computes
                if c.name not in coll_anc
                and coll.name not in anc_of_compute[c.name]
            )
            kind_fracs.append(indep / len(computes))
        per_kind[kind] = {
            "count": len(colls),
            "mean_independent_compute_frac": round(
                sum(kind_fracs) / len(kind_fracs), 4),
        }
        fractions.extend(kind_fracs)
    return {
        "n_compute_ops": len(computes),
        "collectives": per_kind,
        "mean_independent_compute_frac": (
            round(sum(fractions) / len(fractions), 4) if fractions else None
        ),
    }


def _flops_of(compiled) -> Optional[float]:
    return float(compiled.cost_analysis().get("flops", 0.0)) or None


def audit_train_step(
    ts,
    state,
    batch,
    *,
    alpha: float,
    beta: float,
    mode: str = "dear",
    measured_step_s: Optional[float] = None,
    compute_time_s: Optional[float] = None,
    comm_itemsize: int = 4,
    gather_itemsize: Optional[int] = None,
    include_hlo: bool = True,
) -> OverlapReport:
    """Audit one built `TrainStep` (`parallel.build_train_step` result).

    ``alpha``/``beta`` come from a `CommunicationProfiler.fit()` on the
    target mesh (or a synthetic model in tests). ``compute_time_s`` is the
    communication-free step time — measure it as the same step on a
    one-device mesh with one device's share of the batch (what
    `report.main` does), or pass None to
    fall back to XLA-counted FLOPs over the device's known peak (TPU only;
    when neither exists the exposure split is reported as None rather
    than guessed).
    """
    acct = CTR.plan_comm_accounting(
        ts.plan, mode=mode, comm_itemsize=comm_itemsize,
        gather_itemsize=gather_itemsize,
    )
    leg_times = predict_leg_times(acct, alpha, beta)
    comm_time = sum(leg_times)

    flops = None
    hlo_stats = None
    compiled = None
    try:
        compiled = ts.lower(state, batch).compile()
    except Exception:
        pass  # audit degrades to the analytic view
    if compiled is not None:
        flops = _flops_of(compiled)
        if include_hlo:
            try:
                hlo_stats = hlo_collective_stats(compiled.as_text())
            except Exception as exc:  # pragma: no cover - parser drift
                hlo_stats = {"error": str(exc)[:200]}

    if compute_time_s is None and flops:
        peak = perf_model.device_peak_flops(
            ts.mesh.devices.flat[0] if hasattr(ts.mesh.devices, "flat")
            else ts.mesh.devices[0])
        if peak:
            compute_time_s = flops / peak

    ideal = serial = exposed = hidden = eff = None
    note = None
    if compute_time_s is not None:
        ideal = max(compute_time_s, comm_time)
        serial = compute_time_s + comm_time
        if measured_step_s is not None:
            exposed = min(max(measured_step_s - compute_time_s, 0.0),
                          comm_time)
            hidden = comm_time - exposed
            if serial > ideal:
                eff = (serial - measured_step_s) / (serial - ideal)
                eff = min(max(eff, 0.0), 1.0)
            else:
                # no communication (or no compute) to hide: the schedule
                # trivially achieves the ideal
                eff = 1.0 if comm_time == 0.0 else 0.0
            if measured_step_s < 0.95 * ideal:
                note = (
                    "measured step beat the modeled ideal: the alpha-beta "
                    "fit overestimates in-program collectives (expected on "
                    "CPU emulation, where the fit pays per-dispatch "
                    "overhead the compiled step amortizes) — treat the "
                    "efficiency as saturated, and the per-bucket split as "
                    "model-weighted only"
                )
            elif measured_step_s > serial:
                note = (
                    "measured step exceeds the serial model: compute or "
                    "comm is underestimated (efficiency clipped to 0)"
                )

    legs = []
    for row, t in zip(acct.rows, leg_times):
        if exposed is not None and comm_time > 0:
            leg_exposed = exposed * (t / comm_time)
            leg_hidden = t - leg_exposed
        else:
            leg_exposed = leg_hidden = None
        legs.append(BucketLegReport(
            bucket=row.bucket, leg=row.leg,
            payload_bytes=row.payload_bytes, wire_bytes=row.wire_bytes,
            pred_time_s=t,
            exposed_s=leg_exposed, hidden_s=leg_hidden,
        ))

    return OverlapReport(
        mode=mode, world=acct.world, num_buckets=acct.num_buckets,
        alpha=alpha, beta=beta,
        compute_time_s=compute_time_s, comm_time_s=comm_time,
        measured_step_s=measured_step_s,
        ideal_step_s=ideal, serial_step_s=serial,
        exposed_comm_s=exposed, hidden_comm_s=hidden,
        overlap_efficiency=eff, flops_per_step=flops,
        legs=tuple(legs), hlo=hlo_stats, model_note=note,
    )


def measure_step_time(ts, state, batch, *, steps: int = 10,
                      warmup: int = 3) -> tuple[float, object]:
    """Mean wall seconds per `ts.step` call. Returns ``(secs, state)`` —
    the state threads through (donation-safe). One host sync closes the
    timed window (the repo's standard protocol; a per-step sync would
    charge dispatch latency to every step)."""
    import jax

    metrics = None
    for _ in range(warmup):
        state, metrics = ts.step(state, batch)
    if metrics is not None:
        jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = ts.step(state, batch)
    jax.block_until_ready(metrics["loss"])
    return (time.perf_counter() - t0) / steps, state


def fit_interconnect(mesh, *, sizes: Optional[Sequence[int]] = None,
                     repeats: int = 5, warmup: int = 2) -> tuple[float, float]:
    """Measured (α, β) for ring all-gather traffic on ``mesh`` via
    `utils.profiling.CommunicationProfiler` — small default sweep so the
    report entry point stays interactive on the CPU emulation."""
    from dear_pytorch_tpu.utils.profiling import CommunicationProfiler

    from dear_pytorch_tpu.observability import costmodel as CM

    prof = CommunicationProfiler(mesh, collective="all_gather")
    if sizes is None:
        sizes = [2 ** k for k in range(12, 19, 2)]
    sizes_bytes, times = prof.benchmark(sizes=sizes, repeats=repeats,
                                        warmup=warmup)
    # normalization (whole-collective times -> the per-round α-β form
    # the leg model consumes) lives in the costmodel waist so offline
    # consumers (the simulator) fit recorded sweeps identically
    return CM.fit_allgather_sweep(prof.mesh.shape[prof.axis_name],
                                  sizes_bytes, times)


def fit_dcn(samples: Sequence[tuple[float, float]],
            *, min_samples: int = 4) -> tuple[float, float]:
    """(α, β) for the cross-slice DCN level — moved to
    `costmodel.fit_dcn` (the one α-β waist); this shim keeps the
    historical `overlap.fit_dcn` import path working unchanged."""
    from dear_pytorch_tpu.observability import costmodel as CM

    return CM.fit_dcn(samples, min_samples=min_samples)
