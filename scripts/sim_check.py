"""Simulator-validation gate: dearsim must RANK the recorded perf
history correctly, or it is not a tool anyone may plan capacity with.

Replays the archived A/B record against `observability.sim` and fails
CI when a simulated delta points the wrong way:

  perf/tuning_r07               schedule-mode ordering on bert-base-
                                shaped comm: recorded sentences/s
                                dear 2.7 > allreduce 2.4 > rb 2.0 and
                                dear 2.7 > fsdp 2.2 -> simulated step
                                time must order dear < allreduce < rb
                                and dear < fsdp.
  perf/overlap_r05              overlap structure: the recorded
                                independent-compute fraction (dear
                                0.367, fsdp 0.357, allreduce 0.025)
                                -> simulated hidden-comm fraction must
                                keep dear strictly above allreduce and
                                >= fsdp.
  perf/onchip_r04               the recorded '+4.5% on BERT from the
                                world-aware gather dtype' -> a bf16
                                gather must simulate strictly faster
                                than f32 at world 8.
  perf/serving_r08              chunked:token A/B (rps 1247.8 vs 864.3;
                                p99 3.28ms vs 5.0ms) -> simulated
                                chunked prefill must beat token-at-a-
                                time on BOTH rps and p99.
  perf/dcn_degraded_r18         degraded-DCN skip-vs-stall: the live
                                flap storm absorbed a sub-budget flap
                                with zero rollbacks and the partition
                                storm escalated to eviction+rejoin ->
                                the simulated staleness sweep must
                                rank skip over stall on both traces
                                with the same ladder shape (rollbacks,
                                skips, escalations, rejoins).
  (sdc policy)                  shadow-replay quarantine orderings on a
                                fixed corrupt-replica trace: a looser
                                shadow cadence never exposes fewer
                                corrupted responses or detects faster,
                                a bigger strike budget never
                                quarantines earlier, and the policy
                                sweep ranks the tightest cadence first.
  (storm)                       a 1000-rank / 8-slice slice-loss storm
                                must resolve to lockstep with exactly
                                one shrink epoch + one admission epoch
                                in under --storm-budget-s wall seconds.

Cells the record CANNOT validate are skipped with a printed reason,
never silently: serving tp:dense (the artifact's own summary says those
cells measure emulation overhead).

Prints one JSON verdict line (bench_gate-shaped). Exit codes: 0 ok ·
2 mis-ranked delta or storm failure · 3 unusable/missing artifacts.

Needs jax importable (builds a FusionPlan); still CPU-only and tier-1
budget friendly: `python scripts/sim_check.py --skip-storm` runs the
ranking cases in seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# bert-base-shaped synthetic plan: ~110M params with one dominant
# embedding bucket, the shape the tuning_r07 rows measured
BERT_LAYERS = [30_000_000] + [7_000_000] * 10 + [10_000_000]
WORLD = 8
COMPUTE_S = 0.012     # saturating regime — where the recorded A/Bs ran


def _load_json(path):
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _recorded_mode_rows(repo):
    """tuning_r07 bert_base sentences/s by mode (None if absent)."""
    summary = _load_json(os.path.join(repo, "perf", "tuning_r07",
                                      "summary.json"))
    if not summary:
        return None
    try:
        rows = summary["models"]["bert_base"]["rows_sen_per_sec"]
        return {m: float(v[0]) for m, v in rows.items()}
    except (KeyError, TypeError, IndexError):
        return None


def _recorded_serving(repo):
    ab = _load_json(os.path.join(repo, "perf", "serving_r08",
                                 "ab_reports.json"))
    p99 = _load_json(os.path.join(repo, "perf", "serving_r08",
                                  "ab_reports_p99.json"))
    if not ab or not p99:
        return None
    try:
        cells = ab["serve_gpt_tiny"]
        lat = p99["serve_gpt_tiny_p99_ms"]
        return {
            "rps": {k: float(next(iter(cells[k].values()))[0])
                    for k in ("chunked", "token")},
            "p99_ms": {k: float(next(iter(lat[k].values()))[0])
                       for k in ("chunked", "token")},
        }
    except (KeyError, TypeError, IndexError, StopIteration):
        return None


def check_mode_ordering(sim, checks):
    recorded = _recorded_mode_rows(REPO)
    if recorded is None:
        return "missing perf/tuning_r07/summary.json"
    # the record itself must rank the way this gate encodes (guard
    # against artifact drift making the gate vacuous)
    rec_ok = (recorded["dear"] > recorded["allreduce"] > recorded["rb"]
              and recorded["dear"] > recorded["fsdp"])
    plan = sim.synthetic_plan(BERT_LAYERS, WORLD)
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    t = {m: sim.simulate_training(plan, topo, mode=m, steps=1,
                                  jitter=0.0,
                                  compute_time_s=COMPUTE_S)["step_time_s"]
         for m in ("dear", "allreduce", "fsdp", "rb")}
    sim_ok = (t["dear"] < t["allreduce"] < t["rb"]
              and t["dear"] < t["fsdp"])
    checks.append({
        "name": "mode_ordering_tuning_r07",
        "recorded_sen_per_sec": recorded,
        "simulated_step_s": t,
        "ok": bool(rec_ok and sim_ok),
    })
    return None


def check_overlap_structure(sim, checks):
    summary = _load_json(os.path.join(REPO, "perf", "overlap_r05",
                                      "summary.json"))
    if not summary:
        return "missing perf/overlap_r05/summary.json"
    try:
        rec = {m: float(summary["hlo_world8"][m]
                        ["mean_independent_compute_frac"])
               for m in ("dear", "allreduce", "fsdp")}
    except (KeyError, TypeError, ValueError):
        return "perf/overlap_r05/summary.json missing hlo_world8 rows"
    rec_ok = rec["dear"] > rec["allreduce"] and rec["dear"] >= rec["fsdp"]
    plan = sim.synthetic_plan(BERT_LAYERS, WORLD)
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    frac = {}
    for m in ("dear", "allreduce", "fsdp"):
        rep = sim.simulate_training(plan, topo, mode=m, steps=1,
                                    jitter=0.0,
                                    compute_time_s=COMPUTE_S)["report"]
        frac[m] = rep["hidden_comm_s"] / max(rep["comm_time_s"], 1e-12)
    sim_ok = (frac["dear"] > frac["allreduce"]
              and frac["dear"] >= frac["fsdp"])
    checks.append({
        "name": "overlap_structure_r05",
        "recorded_independent_frac": rec,
        "simulated_hidden_frac": frac,
        "ok": bool(rec_ok and sim_ok),
    })
    return None


def check_gather_dtype(sim, checks):
    plan = sim.synthetic_plan(BERT_LAYERS, WORLD)
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    f32 = sim.simulate_training(plan, topo, mode="dear",
                                gather_itemsize=4, steps=1, jitter=0.0,
                                compute_time_s=COMPUTE_S)
    bf16 = sim.simulate_training(plan, topo, mode="dear",
                                 gather_itemsize=2, steps=1, jitter=0.0,
                                 compute_time_s=COMPUTE_S)
    checks.append({
        "name": "gather_dtype_bench_r04",
        "recorded": "+4.5% on BERT from the world-aware gather dtype "
                    "(PERF.md, r04)",
        "simulated_step_s": {"f32": f32["step_time_s"],
                             "bf16": bf16["step_time_s"]},
        "ok": bool(bf16["step_time_s"] < f32["step_time_s"]),
    })
    return None


def check_serving(sim, checks, skips):
    rec = _recorded_serving(REPO)
    if rec is None:
        return "missing perf/serving_r08 ab_reports"
    rec_ok = (rec["rps"]["chunked"] > rec["rps"]["token"]
              and rec["p99_ms"]["chunked"] < rec["p99_ms"]["token"])
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    trace = sim.TrafficTrace.poisson(rps=500.0, duration_s=1.0,
                                     prompt_tokens=16, decode_tokens=4,
                                     seed=3)
    chunked = sim.simulate_serving(topo, trace, prefill_chunk=4, slots=4)
    token = sim.simulate_serving(topo, trace, prefill_chunk=1, slots=4)
    sim_ok = (chunked["requests_per_s"] > token["requests_per_s"]
              and chunked["p99_s"] < token["p99_s"])
    checks.append({
        "name": "serving_chunked_vs_token_r08",
        "recorded": rec,
        "simulated": {
            "chunked": {"rps": chunked["requests_per_s"],
                        "p99_s": chunked["p99_s"]},
            "token": {"rps": token["requests_per_s"],
                      "p99_s": token["p99_s"]},
        },
        "ok": bool(rec_ok and sim_ok),
    })
    skips.append({"name": "serving_tp_vs_dense",
                  "reason": "the artifact's own summary: those cells "
                            "measure emulation overhead, not ring "
                            "transport wins"})
    return None


def check_degraded_dcn(sim, checks):
    """perf/dcn_degraded_r18: the live flap storm absorbed a 2-round
    sub-budget flap with ZERO rollbacks (skip, rung 2) and the live
    partition storm escalated a past-budget outage to eviction+rejoin
    (rung 3) — so the simulator's staleness-policy sweep must rank
    skip over stall on the same traces, with the same ladder shape."""
    rec = _load_json(os.path.join(REPO, "perf", "dcn_degraded_r18",
                                  "summary.json"))
    if rec is None:
        return "missing perf/dcn_degraded_r18/summary.json"
    try:
        flap, part = rec["flap"], rec["partition"]
        rec_ok = (flap["per_rank"]["guard_rollbacks"] == 0
                  and flap["per_rank"]["dcn_skips"] >= 1
                  and flap["per_rank"]["dcn_escalations"] == 0
                  and part["survivor_per_rank"]["dcn_escalations"] >= 1
                  and part["survivor_per_rank"]["cluster_slice_rejoins"]
                  >= 1
                  and bool(part["victim"]["rejoined"]))
        rec_staleness = int(flap["env"]["DEAR_DCN_STALENESS"])
        rec_timeout = float(flap["env"]["DEAR_DCN_TIMEOUT_SECS"])
    except (KeyError, TypeError, ValueError):
        return "perf/dcn_degraded_r18/summary.json malformed"

    topo = sim.SimTopology(
        num_slices=2, chips_per_slice=2,
        dcn=sim.LinkFit(alpha=2e-3, beta=1.0 / 2e9, source="default"))
    # the recorded flap: dcn_flap@4:2:s1 — slice 1 dark for exchange
    # attempts 4 and 5 of a 12-step run
    ranked = sim.sweep_staleness_policies(
        topo, policies=(0, rec_staleness), steps=12,
        timeout_s=rec_timeout, outages={1: [4, 5]}, ckpt_every=4)
    skip_run = next(r for r in ranked
                    if r["staleness"] == rec_staleness)
    stall_run = next(r for r in ranked if r["staleness"] == 0)
    flap_ok = (ranked[0]["staleness"] == rec_staleness
               and skip_run["rollbacks"] == 0
               and skip_run["skips"] >= 1
               and skip_run["escalations"] == 0
               and stall_run["rollbacks"] >= 1
               and skip_run["steps_per_hour"]
               > stall_run["steps_per_hour"])
    # the recorded partition, scaled to sim rounds: a past-budget
    # outage (6 rounds vs staleness 1) that ends before the run does,
    # so the evicted slice rejoins — the live storm's evict+rejoin arc
    part_kw = dict(steps=12, timeout_s=2.0,
                   outages={1: list(range(3, 9))}, ckpt_every=2)
    p_skip = sim.simulate_degraded_dcn(topo, staleness=1, **part_kw)
    p_stall = sim.simulate_degraded_dcn(topo, staleness=0, **part_kw)
    part_ok = (p_skip["escalations"] >= 1 and p_skip["rejoins"] >= 1
               and p_skip["rollbacks"] == 0
               and p_stall["rollbacks"] >= 1
               and p_skip["steps_per_hour"]
               > p_stall["steps_per_hour"])
    checks.append({
        "name": "degraded_dcn_skip_vs_stall_r18",
        "recorded": {
            "flap_rollbacks": flap["per_rank"]["guard_rollbacks"],
            "flap_skips": flap["per_rank"]["dcn_skips"],
            "partition_escalations":
                part["survivor_per_rank"]["dcn_escalations"],
            "partition_rejoined": part["victim"]["rejoined"],
        },
        "simulated": {
            "flap": {"skip": {"steps_per_hour":
                              skip_run["steps_per_hour"],
                              "rollbacks": skip_run["rollbacks"],
                              "skips": skip_run["skips"]},
                     "stall": {"steps_per_hour":
                               stall_run["steps_per_hour"],
                               "rollbacks": stall_run["rollbacks"]}},
            "partition": {"skip": {"steps_per_hour":
                                   p_skip["steps_per_hour"],
                                   "escalations": p_skip["escalations"],
                                   "rejoins": p_skip["rejoins"]},
                          "stall": {"steps_per_hour":
                                    p_stall["steps_per_hour"],
                                    "rollbacks": p_stall["rollbacks"]}},
        },
        "ok": bool(rec_ok and flap_ok and part_ok),
    })
    return None


def check_trace_calibration(sim, checks, skips):
    """perf/trace_r19: the fleet-trace calibration harvested from the
    recorded --multislice chaos storm (scripts/fleet_trace.py
    --calibration).  Two obligations, one per half of the replay
    contract:

    - parity: replaying the recorded compute-scale distribution
      (`trace_calibration=` with compute unpinned, so the fixed-point
      rebase runs) must land the simulated step p50 within 10% of the
      recorded p50 and the p99 within [0.5x, 1.5x] of the recorded p99.
      The recorded tail includes the storm's kill/stall steps — wide on
      purpose; the p50 band is the tight one.
    - ranking: the same replay with compute PINNED (no rebase — rebase
      deliberately forces every mode onto the recorded p50, which
      erases A/B structure) must preserve dear < allreduce on the mean.

    Seed is pinned: the scale distribution is 10 samples with a heavy
    rollback mass, so an unlucky resample can move the sim median into
    the tail (seen at seed 4) — the gate verifies the replay mechanism,
    not resampling luck."""
    cal_path = os.path.join(REPO, "perf", "trace_r19", "calibration.json")
    rec = _load_json(cal_path)
    if rec is None:
        skips.append({"name": "trace_calibration_r19",
                      "reason": "missing perf/trace_r19/calibration.json"})
        return None
    try:
        rec_p50 = float(rec["step_time_s"]["p50"])
        rec_p99 = float(rec["step_time_s"]["p99"])
        rec_n = int(rec["n_steps"])
    except (KeyError, TypeError, ValueError):
        return "perf/trace_r19/calibration.json malformed"
    rec_ok = rec_n >= 4 and 0.0 < rec_p50 <= rec_p99

    plan = sim.synthetic_plan(BERT_LAYERS, WORLD)
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    rep = sim.simulate_training(plan, topo, mode="dear", steps=400,
                                seed=0, trace_calibration=cal_path)
    q = rep["quantiles"]
    parity_ok = (rep["jitter_model"] == "trace-replay"
                 and abs(q["p50"] - rec_p50) <= 0.10 * rec_p50
                 and 0.5 * rec_p99 <= q["p99"] <= 1.5 * rec_p99)
    t = {m: sim.simulate_training(plan, topo, mode=m, steps=400, seed=0,
                                  compute_time_s=COMPUTE_S,
                                  trace_calibration=cal_path)
         ["step_time_s"]
         for m in ("dear", "allreduce")}
    rank_ok = t["dear"] < t["allreduce"]
    checks.append({
        "name": "trace_calibration_r19",
        "recorded_step_s": {"p50": rec_p50, "p99": rec_p99, "n": rec_n},
        "simulated_step_s": {"p50": q["p50"], "p99": q["p99"],
                             "n": q["n"]},
        "pinned_mean_s": t,
        "ok": bool(rec_ok and parity_ok and rank_ok),
    })
    return None


def check_sdc_policy(sim, checks):
    """SDC quarantine-policy orderings (resilience.sdc, PR-20): the
    shadow-replay cadence is the detection budget, so on one fixed
    corrupt-replica trace the simulator must rank it the only way
    physics allows — shadowing less often can never expose FEWER
    corrupted responses or detect FASTER, raising the strike budget can
    never quarantine EARLIER, and the policy sweep must put the
    tightest cadence (fewest exposed) first. Deterministic trace, no
    bands: pure monotonicity."""
    topo = sim.SimTopology(num_slices=1, chips_per_slice=WORLD)
    trace = sim.TrafficTrace.poisson(rps=200.0, duration_s=2.0,
                                     prompt_tokens=16, decode_tokens=4,
                                     seed=3)
    kw = dict(replicas=3, corrupt_replica=1, corrupt_at_s=0.5)
    cadence = {se: sim.simulate_sdc(topo, trace, shadow_every=se, **kw)
               for se in (1, 2, 4)}
    mono_ok = all(
        cadence[a]["exposed"] <= cadence[b]["exposed"]
        and cadence[a]["detect_s"] is not None
        and cadence[b]["detect_s"] is not None
        and cadence[a]["detect_s"] <= cadence[b]["detect_s"]
        and cadence[a]["quarantined_at_s"] is not None
        for a, b in ((1, 2), (2, 4)))
    strikes = {st: sim.simulate_sdc(topo, trace, shadow_every=2,
                                    strike_threshold=st, **kw)
               for st in (1, 2, 3)}
    strike_ok = all(
        strikes[a]["quarantined_at_s"] is not None
        and strikes[b]["quarantined_at_s"] is not None
        and strikes[a]["quarantined_at_s"]
        <= strikes[b]["quarantined_at_s"]
        for a, b in ((1, 2), (2, 3)))
    ranked = sim.sweep_sdc_policies(topo, trace,
                                    shadow_everys=(1, 2, 4),
                                    strike_thresholds=(1,), **kw)
    sweep_ok = (ranked[0]["shadow_every"] == 1
                and ranked[0]["exposed"]
                == min(r["exposed"] for r in ranked)
                and all(r["readmit_at_s"] is not None for r in ranked))
    checks.append({
        "name": "sdc_policy_orderings",
        "detect_s_by_cadence": {se: cadence[se]["detect_s"]
                                for se in cadence},
        "exposed_by_cadence": {se: cadence[se]["exposed"]
                               for se in cadence},
        "quarantine_s_by_strikes": {st: strikes[st]["quarantined_at_s"]
                                    for st in strikes},
        "ok": bool(mono_ok and strike_ok and sweep_ok),
    })
    return None


def check_storm(sim, checks, budget_s):
    t0 = time.perf_counter()
    out = sim.run_membership_storm(world=1000, ranks_per_slice=125,
                                   kill_slice=1)
    wall = time.perf_counter() - t0
    e1, e2, e3 = (out["records"][k] for k in ("e1", "e2", "e3"))
    shape_ok = (
        e1 is not None and e2 is not None and e3 is None
        and e1["delta"]["removed"] == list(range(125, 250))
        and e1["delta"]["slices"]["removed"] == [1]
        and e2["delta"]["added"] == list(range(125, 250))
        and e2["members"] == list(range(1000)))
    checks.append({
        "name": "storm_1000_ranks",
        "wall_s": round(wall, 2),
        "budget_s": budget_s,
        "lockstep": out["lockstep"],
        "errors": out["errors"],
        "ok": bool(out["lockstep"] and shape_ok and wall < budget_s),
    })
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate dearsim against the recorded perf history")
    ap.add_argument("--skip-storm", action="store_true",
                    help="skip the 1000-rank storm (runs the ranking "
                         "cases only, seconds instead of ~1 minute)")
    ap.add_argument("--storm-budget-s", type=float, default=60.0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        from dear_pytorch_tpu.observability import sim
    except Exception as exc:  # noqa: BLE001 — unusable environment
        print(json.dumps({"ok": False, "infra_error": repr(exc)}))
        return 3

    checks, skips = [], []
    for fn in (lambda: check_mode_ordering(sim, checks),
               lambda: check_overlap_structure(sim, checks),
               lambda: check_gather_dtype(sim, checks),
               lambda: check_serving(sim, checks, skips),
               lambda: check_degraded_dcn(sim, checks),
               lambda: check_trace_calibration(sim, checks, skips),
               lambda: check_sdc_policy(sim, checks)):
        try:
            infra = fn()
        except Exception as exc:  # noqa: BLE001
            print(json.dumps({"ok": False, "infra_error": repr(exc)}))
            return 3
        if infra:
            print(json.dumps({"ok": False, "infra_error": infra}))
            return 3
    if args.skip_storm:
        skips.append({"name": "storm_1000_ranks",
                      "reason": "--skip-storm"})
    else:
        try:
            check_storm(sim, checks, args.storm_budget_s)
        except Exception as exc:  # noqa: BLE001
            print(json.dumps({"ok": False, "infra_error": repr(exc)}))
            return 3

    ok = all(c["ok"] for c in checks)
    print(json.dumps({"ok": ok, "checks": checks, "skipped": skips},
                     indent=2, sort_keys=True))
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
