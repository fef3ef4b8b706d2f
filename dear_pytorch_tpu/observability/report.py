"""Render overlap audits and telemetry summaries; runnable entry point.

``python -m dear_pytorch_tpu.observability.report`` builds a bucketed MLP
train step per schedule mode on the 8-device emulated CPU mesh, measures
(a) per-mode step time, (b) communication-free compute time as the same
step on one device with one device's share of the batch, and (c) a live α-β
interconnect fit
(`overlap.fit_interconnect`), then prints the per-mode overlap-efficiency
report — ideal vs measured step time, exposed vs hidden communication per
bucket — and optionally writes the same content as JSON.

This is the consumer the three old logging backends never had: the same
report assembles inside `bench.py` / the benchmark CLIs as their
``telemetry`` JSON block (`observability.snapshot` + `OverlapReport
.to_dict`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

from dear_pytorch_tpu.observability.overlap import OverlapReport

_MS = 1e3


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GB"


def _opt_ms(v: Optional[float]) -> str:
    return "n/a" if v is None else f"{v * _MS:.3f} ms"


def render_text(rep: OverlapReport) -> str:
    """Human-readable overlap audit: headline ratios, then the bucket
    table, then the structural HLO cross-check."""
    lines = [
        f"== overlap audit: mode={rep.mode} "
        f"(world={rep.world}, {rep.num_buckets} buckets) ==",
        f"  interconnect fit: alpha={rep.alpha:.3e} s  "
        f"beta={rep.beta:.3e} s/B"
        + (f"  flops/step={rep.flops_per_step:.3e}"
           if rep.flops_per_step else ""),
        f"  compute {_opt_ms(rep.compute_time_s)}   "
        f"comm(unoverlapped) {_opt_ms(rep.comm_time_s)}   "
        f"measured {_opt_ms(rep.measured_step_s)}",
        f"  serial {_opt_ms(rep.serial_step_s)}   "
        f"ideal {_opt_ms(rep.ideal_step_s)}   "
        + (f"overlap efficiency {rep.overlap_efficiency * 100:.1f}%"
           if rep.overlap_efficiency is not None
           else "overlap efficiency n/a"),
        f"  exposed comm {_opt_ms(rep.exposed_comm_s)}   "
        f"hidden comm {_opt_ms(rep.hidden_comm_s)}",
        "  bucket  leg             payload      pred     exposed    hidden",
    ]
    for leg in rep.legs:
        lines.append(
            f"  {leg.bucket:>6}  {leg.leg:<14}  "
            f"{_fmt_bytes(leg.payload_bytes):>9}  "
            f"{_opt_ms(leg.pred_time_s):>9}  "
            f"{_opt_ms(leg.exposed_s):>9}  {_opt_ms(leg.hidden_s):>9}"
        )
    if rep.hlo and "collectives" in rep.hlo:
        parts = [
            f"{kind} x{v['count']} indep-frac "
            f"{v['mean_independent_compute_frac']}"
            for kind, v in rep.hlo["collectives"].items()
        ]
        mean = rep.hlo.get("mean_independent_compute_frac")
        lines.append("  HLO: " + "; ".join(parts)
                     + (f" (mean {mean})" if mean is not None else ""))
    if rep.model_note:
        lines.append(f"  NOTE: {rep.model_note}")
    return "\n".join(lines)


def render_comparison(reports: dict[str, OverlapReport]) -> str:
    """One-line-per-mode summary table — the "*why* they differ" view."""
    lines = [
        "== mode comparison ==",
        "  mode           measured     comm    exposed    hidden   overlap",
    ]
    for mode, r in reports.items():
        eff = ("n/a" if r.overlap_efficiency is None
               else f"{r.overlap_efficiency * 100:.0f}%")
        lines.append(
            f"  {mode:<13} {_opt_ms(r.measured_step_s):>9} "
            f"{_opt_ms(r.comm_time_s):>9} {_opt_ms(r.exposed_comm_s):>9} "
            f"{_opt_ms(r.hidden_comm_s):>9} {eff:>8}"
        )
    return "\n".join(lines)


def render_telemetry(snap: dict) -> str:
    """Counters + per-span aggregates from `observability.snapshot()`."""
    lines = [f"== telemetry (enabled={snap.get('enabled')}) =="]
    for k, v in sorted(snap.get("counters", {}).items()):
        lines.append(f"  counter {k} = {v:g}")
    for name, agg in sorted(snap.get("spans", {}).items()):
        lines.append(
            f"  span {name}: x{agg['count']}  "
            f"total {agg['total_us'] / 1e3:.3f} ms"
        )
    return "\n".join(lines)


def _opt_s(v: Optional[float]) -> str:
    return "n/a" if v is None else f"{v * _MS:.2f} ms"


def render_fleet_trace(attr: dict, *, max_steps: int = 8,
                       max_requests: int = 8) -> str:
    """Human-readable critical-path attribution over a merged fleet
    timeline (`critical_path.critical_path` output): the fleet step-time
    quantiles and exposed-comm fraction, the per-step straggler table,
    and the per-request hop breakdown."""
    steps = attr.get("steps") or {}
    reqs = attr.get("requests") or {}
    ssum = steps.get("summary") or {}
    rsum = reqs.get("summary") or {}
    lines = ["== fleet trace: critical path =="]
    if ssum.get("n_steps"):
        lines.append(
            f"  steps: {ssum['n_steps']}   "
            f"p50 {_opt_s(ssum.get('step_p50_s'))}   "
            f"p99 {_opt_s(ssum.get('step_p99_s'))}   "
            f"exposed-comm frac "
            + ("n/a" if ssum.get("exposed_frac") is None
               else f"{ssum['exposed_frac'] * 100:.1f}%")
            + f"   rollbacks {ssum.get('rollbacks', 0)}")
        hist = ssum.get("stragglers") or {}
        if hist:
            top = sorted(hist.items(), key=lambda kv: -kv[1])
            lines.append("  stragglers: " + ", ".join(
                f"rank {r} x{n}" for r, n in top[:6]))
        lines.append(
            "  epoch  step    step_s   straggler   exposed    hidden"
            "   longest leg")
        rows = steps.get("steps") or []
        for row in rows[:max_steps]:
            srank = row.get("straggler")
            leg = (row.get("ranks") or {}).get(str(srank), {}) \
                .get("longest_leg") or {}
            lines.append(
                f"  {row['mem_epoch']:>5}  {row['step']:>4}  "
                f"{_opt_s(row.get('step_s')):>8}  {str(srank):>9}  "
                f"{_opt_s(row.get('exposed_comm_s')):>8}  "
                f"{_opt_s(row.get('hidden_comm_s')):>8}   "
                + (f"{leg.get('name')} {_opt_s(leg.get('dur_s'))}"
                   if leg else "n/a"))
        if len(rows) > max_steps:
            lines.append(f"  ... {len(rows) - max_steps} more steps")
    if rsum.get("n_requests"):
        lines.append(
            f"  requests: {rsum['n_requests']}   "
            f"service p50 {_opt_s(rsum.get('service_p50_s'))}   "
            f"p99 {_opt_s(rsum.get('service_p99_s'))}   "
            f"redispatched {rsum.get('redispatched', 0)}   "
            f"multi-incarnation {rsum.get('multi_incarnation', 0)}")
        lines.append(
            "  request            service     queue   prefill    decode"
            "  hops  incarnations")
        rows = reqs.get("requests") or []
        for r in rows[:max_requests]:
            rid = str(r.get("request_id") or r.get("trace_id"))[:16]
            lines.append(
                f"  {rid:<16} {_opt_s(r.get('service_s')):>9} "
                f"{_opt_s(r.get('queue_s')):>9} "
                f"{_opt_s(r.get('prefill_s')):>9} "
                f"{_opt_s(r.get('decode_s')):>9}  "
                f"{len(r.get('hops') or []):>4}  "
                f"{len(r.get('incarnations') or [])}"
                + ("  (redispatched)" if r.get("redispatches") else ""))
        if len(rows) > max_requests:
            lines.append(f"  ... {len(rows) - max_requests} more requests")
    if len(lines) == 1:
        lines.append("  (no attributable spans in the timeline)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# entry point: world=N CPU-emulated audit of the schedule modes
# ---------------------------------------------------------------------------


def _mlp(n_layers: int, width: int):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), n_layers)
    params = {
        f"l{i:02d}": {"w": jax.random.normal(ks[i], (width, width)) * 0.1,
                      "b": jnp.zeros((width,))}
        for i in range(n_layers)
    }

    def loss(p, b):
        x, y = b
        for i in range(n_layers):
            x = jnp.tanh(x @ p[f"l{i:02d}"]["w"] + p[f"l{i:02d}"]["b"])
        return jnp.mean((x - y) ** 2)

    return params, loss


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="overlap-efficiency audit on the emulated CPU mesh")
    ap.add_argument("--modes", default="dear,allreduce",
                    help="comma list of schedule modes to audit")
    ap.add_argument("--world", type=int, default=8,
                    help="emulated CPU device count")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32,
                    help="global batch (split over the mesh)")
    ap.add_argument("--steps", type=int, default=10,
                    help="timed steps per mode")
    ap.add_argument("--json", default=None,
                    help="also write the full report as JSON here")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the structural HLO metric (faster)")
    args = ap.parse_args(argv)

    # Force the emulated multi-device CPU world BEFORE backend init — the
    # audit is meaningless at world=1 (no collectives in the program).
    # Through jax.config: the package import already loaded jax, so the
    # environment is read no more.
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.world)
    jax.config.update("jax_enable_compilation_cache", False)
    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"

    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.observability import configure, snapshot
    from dear_pytorch_tpu.observability import overlap as OV
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step

    if os.environ.get(T.TELEMETRY_ENV) is None:
        configure()  # in-memory: the phase breakdown below needs spans

    mesh = backend.init()
    world = mesh.size
    params, loss = _mlp(args.layers, args.width)
    batch = (jnp.zeros((args.batch, args.width)),
             jnp.zeros((args.batch, args.width)))

    def build(mode: str, mesh=mesh):
        return build_train_step(
            loss, params, mesh=mesh, mode=mode, nearby_layers=1,
            optimizer=fused_sgd(lr=0.01, momentum=0.9), donate=False,
        )

    print(f"fitting interconnect alpha-beta on {mesh} ...", flush=True)
    alpha, beta = OV.fit_interconnect(mesh)

    # communication-free compute time: the same builder on a one-device
    # mesh with one device's share of the batch (world 1 has no collectives
    # and its numerics are real) — a measured number, not a model
    ts_compute = build("dear", jax.sharding.Mesh(
        mesh.devices.reshape(-1)[:1], mesh.axis_names))
    share = jax.tree.map(lambda x: x[:max(1, x.shape[0] // world)], batch)
    compute_s, _ = OV.measure_step_time(
        ts_compute, ts_compute.init(params), share, steps=args.steps)
    print(f"compute-only step (one device, 1/{world} of the batch): "
          f"{compute_s * _MS:.3f} ms", flush=True)

    reports: dict[str, OverlapReport] = {}
    for mode in [m.strip() for m in args.modes.split(",") if m.strip()]:
        ts = build(mode)
        measured, state = OV.measure_step_time(
            ts, ts.init(params), batch, steps=args.steps)
        reports[mode] = OV.audit_train_step(
            ts, state, batch, alpha=alpha, beta=beta, mode=mode,
            measured_step_s=measured, compute_time_s=compute_s,
            include_hlo=not args.no_hlo,
        )
        print(render_text(reports[mode]), flush=True)

    if len(reports) > 1:
        print(render_comparison(reports), flush=True)
    print(render_telemetry(snapshot()), flush=True)

    if args.json:
        payload = {
            "world": world,
            "alpha": alpha,
            "beta": beta,
            "compute_time_s": compute_s,
            "modes": {m: r.to_dict() for m, r in reports.items()},
            "telemetry": snapshot(),
        }
        d = os.path.dirname(os.path.abspath(args.json))
        os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
