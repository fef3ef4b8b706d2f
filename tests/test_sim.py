"""dearsim tests: wire-byte/pricing parity between the simulator and the
static accounting, determinism of every entry point, calibration/topology
round-trips, the recorded-ordering invariants `scripts/sim_check.py`
gates on, the virtual-time transport, the tuner sim backends — and the
tier-1 headline: a 1000-rank / 8-slice membership storm that resolves
slice loss -> shrink epoch -> rejoin -> lockstep against the REAL
`ElasticCluster` protocol in seconds."""

import json
import time

import pytest

from dear_pytorch_tpu.observability import counters as CTR
from dear_pytorch_tpu.observability import overlap as OV
from dear_pytorch_tpu.observability import sim
from dear_pytorch_tpu.observability.costmodel import Calibration, LinkFit

TOPO8 = sim.SimTopology(num_slices=1, chips_per_slice=8)
# bert-base-ish element counts: comm saturates the overlap windows so
# schedule differences are visible (the regime the recorded A/Bs ran in)
LAYERS = [30_000_000] + [7_000_000] * 10 + [10_000_000]


def plan8(threshold_mb=25.0):
    return sim.synthetic_plan(LAYERS, 8, threshold_mb=threshold_mb)


# ---------------------------------------------------------------------------
# parity: the simulator prices EXACTLY what the accounting emits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", sorted(CTR.MODE_LEGS))
@pytest.mark.parametrize("compressor", [None, "eftopk", "qint8"])
@pytest.mark.parametrize("partition_mb", [None, 4.0])
def test_sim_accounting_parity(mode, compressor, partition_mb):
    """Same plan, same knobs: every simulated leg carries the accounting
    row's wire/payload bytes verbatim, and its priced duration equals
    `predict_leg_times` on a homogeneous topology — including the
    gather-shaped compressed wire factor (sparse RS wire = payload x
    (world-1), dense AG)."""
    topo = sim.SimTopology(num_slices=2, chips_per_slice=8)
    plan = sim.synthetic_plan([8_000_000, 3_000_000, 2_000_000], 16,
                              threshold_mb=16.0)
    acct = CTR.plan_comm_accounting(
        plan, mode=mode, compressor=compressor, density=0.01,
        num_slices=2, dcn_partition_mb=partition_mb)
    want = OV.predict_leg_times(acct, topo.ici.alpha, topo.ici.beta)
    got = [sim._price_row_topo(r, topo, acct.world) for r in acct.rows]
    assert got == pytest.approx(want, abs=0.0, rel=1e-12)

    rep = sim.simulate_training(
        plan, topo, mode=mode, compressor=compressor, density=0.01,
        partition_mb=partition_mb, steps=1, jitter=0.0)["report"]
    assert [(l["bucket"], l["leg"], l["wire_bytes"], l["payload_bytes"])
            for l in rep["legs"]] == \
           [(r.bucket, r.leg, r.wire_bytes, r.payload_bytes)
            for r in acct.rows]
    assert rep["legs"] and all(
        l["pred_time_s"] == pytest.approx(t, rel=1e-12)
        for l, t in zip(rep["legs"], want))


def test_compressed_gather_shaped_wire_parity():
    """The compressed-RS wire model is gather-shaped (wire = compressed
    payload x (world-1), NOT ring-scaled) and the AG stays dense — the
    simulator must inherit both from the accounting, not re-derive."""
    plan = plan8()
    dense = CTR.plan_comm_accounting(plan, mode="dear")
    sparse = CTR.plan_comm_accounting(plan, mode="dear",
                                      compressor="eftopk", density=0.01)
    rep = sim.simulate_training(plan, TOPO8, mode="dear",
                                compressor="eftopk", density=0.01,
                                steps=1, jitter=0.0)["report"]
    by_leg = {}
    for l in rep["legs"]:
        by_leg.setdefault(l["leg"], 0)
        by_leg[l["leg"]] += l["wire_bytes"]
    rs_sparse = sum(r.wire_bytes for r in sparse.rows
                    if r.leg == "reduce_scatter")
    ag_dense = sum(r.wire_bytes for r in dense.rows
                   if r.leg == "all_gather")
    assert by_leg["reduce_scatter"] == rs_sparse
    assert by_leg["all_gather"] == ag_dense  # AG unaffected by compression
    # and the gather shape itself: wire = payload x (world - 1)
    for r in sparse.rows:
        if r.leg == "reduce_scatter":
            assert r.wire_bytes == r.payload_bytes * (plan.world - 1)


def test_heterogeneous_link_prices_at_slowest():
    """A degraded slice drags every ICI leg to its rate (synchronous
    ring = slowest link), never below the healthy price."""
    slow = LinkFit(alpha=1e-4, beta=1.0 / 4e9)
    topo_bad = sim.SimTopology(num_slices=2, chips_per_slice=4,
                               ici_overrides=((1, slow),))
    topo_ok = sim.SimTopology(num_slices=2, chips_per_slice=4)
    plan = sim.synthetic_plan([4_000_000], 8)
    acct = CTR.plan_comm_accounting(plan, mode="dear")
    for row in acct.rows:
        bad = sim._price_row_topo(row, topo_bad, acct.world)
        ok = sim._price_row_topo(row, topo_ok, acct.world)
        assert bad == sim._price_row(row, acct.world, slow)
        assert bad > ok


# ---------------------------------------------------------------------------
# determinism + artifact shape
# ---------------------------------------------------------------------------


def test_training_sim_deterministic_and_seed_sensitive():
    a = sim.simulate_training(plan8(), TOPO8, mode="dear", steps=16, seed=7)
    b = sim.simulate_training(plan8(), TOPO8, mode="dear", steps=16, seed=7)
    c = sim.simulate_training(plan8(), TOPO8, mode="dear", steps=16, seed=8)
    assert a == b
    assert a["quantiles"] != c["quantiles"]


def test_training_sim_emits_overlap_report_shape():
    """`report.py` must render simulated runs like live ones: the dict
    is a faithful `OverlapReport.to_dict()`."""
    out = sim.simulate_training(plan8(), TOPO8, mode="dear", steps=4)
    rep = out["report"]
    for key in ("mode", "world", "num_buckets", "alpha", "beta",
                "compute_time_s", "comm_time_s", "measured_step_s",
                "ideal_step_s", "serial_step_s", "exposed_comm_s",
                "hidden_comm_s", "overlap_efficiency", "legs"):
        assert key in rep, key
    # exposed + hidden partitions each leg's predicted duration
    for l in rep["legs"]:
        assert l["exposed_s"] + l["hidden_s"] == \
            pytest.approx(l["pred_time_s"], rel=1e-9)
    assert 0.0 <= rep["overlap_efficiency"] <= 1.0
    # ... and the live renderer accepts it verbatim
    from dear_pytorch_tpu.observability import report as R
    rendered = R.render_text(OV.OverlapReport(**{
        **rep, "legs": tuple(OV.BucketLegReport(**l) for l in rep["legs"]),
    }))
    assert "dear" in rendered
    assert out["quantiles"]["n"] == 4


def test_recorded_mode_ordering_reproduced():
    """The structural invariants behind the archived A/Bs
    (perf/tuning_r07: dear 2.7 > allreduce 2.4 > rb 2.0; fsdp 2.2):
    decoupled AG overlaps the next forward, fsdp's gather blocks it,
    rb moves more wire — so simulated step time must order
    dear < allreduce < rb and dear < fsdp."""
    plan = plan8()
    t = {m: sim.simulate_training(plan, TOPO8, mode=m, steps=1,
                                  jitter=0.0,
                                  compute_time_s=0.012)["step_time_s"]
         for m in ("dear", "allreduce", "fsdp", "rb")}
    assert t["dear"] < t["allreduce"] < t["rb"]
    assert t["dear"] < t["fsdp"] <= t["rb"]


def test_gather_dtype_speedup_reproduced():
    """BENCH_r04's recorded '+4.5% on BERT from the world-aware gather
    dtype': a bf16 gather must price strictly faster at world 8."""
    plan = plan8()
    f32 = sim.simulate_training(plan, TOPO8, mode="dear",
                                gather_itemsize=4, steps=1, jitter=0.0,
                                compute_time_s=0.012)
    bf16 = sim.simulate_training(plan, TOPO8, mode="dear",
                                 gather_itemsize=2, steps=1, jitter=0.0,
                                 compute_time_s=0.012)
    assert bf16["wire_bytes_per_step"] < f32["wire_bytes_per_step"]
    assert bf16["step_time_s"] < f32["step_time_s"]


def test_trace_calibration_replay_reproduces_recorded_quantiles():
    """perf/trace_r19's fleet-trace calibration (harvested by
    scripts/fleet_trace.py from the recorded --multislice chaos storm)
    replayed through the sim: with compute unpinned the fixed-point
    rebase must land the simulated p50 within 10% of the recorded p50
    and the p99 within [0.5x, 1.5x] of the recorded p99 (the tail is
    the storm's kill/stall mass — it must EMERGE from the replayed
    scale distribution, it is never fit); with compute pinned the same
    replay must preserve dear < allreduce.  Mirrors
    scripts/sim_check.py check_trace_calibration."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cal_path = os.path.join(repo, "perf", "trace_r19", "calibration.json")
    with open(cal_path) as f:
        rec = json.load(f)["step_time_s"]
    plan = plan8()
    rep = sim.simulate_training(plan, TOPO8, mode="dear", steps=400,
                                seed=0, trace_calibration=cal_path)
    assert rep["jitter_model"] == "trace-replay"
    q = rep["quantiles"]
    assert abs(q["p50"] - rec["p50"]) <= 0.10 * rec["p50"]
    assert 0.5 * rec["p99"] <= q["p99"] <= 1.5 * rec["p99"]
    # pinned compute skips the rebase (which would force both modes
    # onto the recorded p50) — the replay must keep the recorded A/B
    t = {m: sim.simulate_training(plan, TOPO8, mode=m, steps=400,
                                  seed=0, compute_time_s=0.012,
                                  trace_calibration=cal_path)
         ["step_time_s"]
         for m in ("dear", "allreduce")}
    assert t["dear"] < t["allreduce"]


def test_multislice_partition_tradeoff_visible():
    """Bigger DCN partitions -> fewer messages -> less α cost: the axis
    `PlanTuner(sim)` searches must actually move the objective."""
    topo = sim.SimTopology(num_slices=2, chips_per_slice=8,
                           dcn=LinkFit(alpha=1e-4, beta=1.0 / 5e9))
    plan = sim.synthetic_plan(LAYERS, 16)
    fine = sim.simulate_training(plan, topo, mode="dear",
                                 partition_mb=1.0, steps=1, jitter=0.0)
    coarse = sim.simulate_training(plan, topo, mode="dear",
                                   partition_mb=64.0, steps=1, jitter=0.0)
    assert coarse["step_time_s"] < fine["step_time_s"]


# ---------------------------------------------------------------------------
# degraded-mode DCN: skip-vs-stall policy replay
# ---------------------------------------------------------------------------

DCN_TOPO = sim.SimTopology(num_slices=2, chips_per_slice=2,
                           dcn=LinkFit(alpha=2e-3, beta=1.0 / 2e9))


def test_price_degraded_round_bounds():
    from dear_pytorch_tpu.observability.costmodel import (
        price_degraded_round,
    )
    fit = LinkFit(alpha=1e-3, beta=1.0 / 1e9)
    healthy = price_degraded_round(fit, 8 * 2**20, timeout_s=3.0)
    assert healthy == pytest.approx(1e-3 + 8 * 2**20 / 1e9)
    # chunking at partition_mb pays one α per chunk
    chunked = price_degraded_round(fit, 8 * 2**20, timeout_s=3.0,
                                   partition_mb=1.0)
    assert chunked == pytest.approx(8e-3 + 8 * 2**20 / 1e9)
    # an outage charges exactly the retry budget — the bounded cost of
    # deciding to skip, regardless of payload
    assert price_degraded_round(fit, 8 * 2**20, timeout_s=3.0,
                                outage=True) == 3.0


def test_degraded_dcn_sim_deterministic():
    kw = dict(staleness=2, steps=12, timeout_s=3.0, outages={1: [4, 5]})
    a = sim.simulate_degraded_dcn(DCN_TOPO, **kw)
    b = sim.simulate_degraded_dcn(DCN_TOPO, **kw)
    assert a == b


def test_degraded_dcn_flap_skip_beats_stall():
    """The recorded flap-storm fact (perf/dcn_degraded_r18): a
    sub-budget flap costs zero rollbacks under the ladder, while
    strict mode pays a rollback per flapped exchange — and the sweep
    ranks the skip policy first."""
    kw = dict(steps=12, timeout_s=3.0, outages={1: [4, 5]},
              ckpt_every=4)
    ranked = sim.sweep_staleness_policies(DCN_TOPO, policies=(0, 2),
                                          **kw)
    skip = next(r for r in ranked if r["staleness"] == 2)
    stall = next(r for r in ranked if r["staleness"] == 0)
    assert ranked[0]["staleness"] == 2
    assert skip["finished"] and stall["finished"]
    assert skip["rollbacks"] == 0 and skip["skips"] == 2
    assert skip["escalations"] == 0
    assert stall["rollbacks"] >= 1
    assert skip["steps_per_hour"] > stall["steps_per_hour"]


def test_degraded_dcn_partition_walks_the_ladder():
    """A past-budget outage escalates to eviction (rung 3), trains on
    without the slice, and readmits it when the outage ends — no
    rollbacks anywhere on the degraded path."""
    kw = dict(steps=12, timeout_s=2.0, outages={1: list(range(3, 9))},
              ckpt_every=2)
    deg = sim.simulate_degraded_dcn(DCN_TOPO, staleness=1, **kw)
    strict = sim.simulate_degraded_dcn(DCN_TOPO, staleness=0, **kw)
    assert deg["finished"]
    assert deg["rollbacks"] == 0
    assert deg["escalations"] == 1 and deg["rejoins"] == 1
    # skips stop accruing once the slice is evicted
    assert deg["skips"] == 2
    assert strict["rollbacks"] >= 6
    assert deg["steps_per_hour"] > strict["steps_per_hour"]


# ---------------------------------------------------------------------------
# topology / calibration round-trips
# ---------------------------------------------------------------------------


def test_topology_roundtrip(tmp_path):
    topo = sim.SimTopology(
        num_slices=4, chips_per_slice=16, replicas=3,
        ici=LinkFit(alpha=2e-6, beta=1.0 / 90e9, source="measured"),
        dcn=LinkFit(alpha=1e-4, beta=1.0 / 6e9),
        ici_overrides=((2, LinkFit(alpha=1e-5, beta=1.0 / 10e9)),),
        dcn_overrides=((0, LinkFit(alpha=2e-4, beta=1.0 / 3e9)),))
    again = sim.SimTopology.from_dict(topo.to_dict())
    assert again.to_dict() == topo.to_dict()
    assert again.world == 64
    p = tmp_path / "topo.json"
    p.write_text(json.dumps(topo.to_dict()))
    assert sim.load_topology(str(p)).to_dict() == topo.to_dict()
    assert sim.load_topology(json.dumps(topo.to_dict())).world == 64


def test_topology_from_calibration_artifact(tmp_path):
    """`--calibration perf/...json` style: an artifact embedding a
    calibration block seeds the topology's fits."""
    calib = Calibration(ici=LinkFit(alpha=3e-6, beta=1.0 / 80e9),
                        dcn=LinkFit(alpha=2e-4, beta=1.0 / 4e9))
    p = tmp_path / "artifact.json"
    p.write_text(json.dumps({"run": "r99",
                             "calibration": calib.to_dict()}))
    from dear_pytorch_tpu.observability.costmodel import load_calibration
    topo = sim.SimTopology.from_calibration(load_calibration(str(p)),
                                            num_slices=2)
    assert topo.ici.alpha == 3e-6
    assert topo.dcn.beta == 1.0 / 4e9


# ---------------------------------------------------------------------------
# serving fleet
# ---------------------------------------------------------------------------


def _trace():
    return sim.TrafficTrace.poisson(rps=500.0, duration_s=1.0,
                                    prompt_tokens=16, decode_tokens=4,
                                    seed=3)


def test_serving_sim_deterministic_and_episode_shaped():
    tr = _trace()
    a = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4)
    b = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4)
    assert a == b
    for key in ("p50_s", "p99_s", "requests", "requests_per_s", "ticks",
                "wall_s"):
        assert key in a, key
    assert a["requests"] == len(tr.requests)


def test_serving_chunked_beats_token_on_p99_and_rps():
    """serving_r08's recorded chunked:token win (rps 1247.8 vs 864.3,
    p99 3.28ms vs 5.0ms) is structural: chunked prefill needs fewer
    engine ticks per request."""
    tr = _trace()
    chunked = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4)
    token = sim.simulate_serving(TOPO8, tr, prefill_chunk=1, slots=4)
    assert chunked["p99_s"] < token["p99_s"]
    assert chunked["requests_per_s"] > token["requests_per_s"]


def test_serving_tp_ring_priced_per_tick():
    tr = _trace()
    base = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4)
    tp = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4,
                              tp_decode=True, weight_bytes=2e6,
                              n_projections=4)
    assert tp["p99_s"] > base["p99_s"]


def test_phase_priced_sim_matches_recorded_serving_episode():
    """Parity against the RECORDED serving_r08 A/B cells (ISSUE-17
    satellite): feed the chunk-1 and chunk-4 cells' measured
    seconds-per-tick into a live `AdmissionController`'s split-phase
    EWMAs, convert through `phase_ticks_from_admission`, and replay the
    tuner's exact workload — the phase-priced sim must land within 35%
    of each recorded wall and price the chunked:token speedup STRICTLY
    closer to the recorded ratio than the one-blended-tick model does."""
    import os

    from dear_pytorch_tpu.serving.admission import AdmissionController

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "perf", "serving_r08",
                           "summary.json")) as f:
        eps = json.load(f)["episodes"]
    rec_tok = eps["1/2/bf16/False/False"]
    rec_chk = eps["4/2/bf16/False/False"]
    t_tok = rec_tok["wall_s"] / rec_tok["ticks"]   # s per engine tick
    t_chk = rec_chk["wall_s"] / rec_chk["ticks"]
    # the serve_tune episode workload: 24 requests, prompts 4..16,
    # 4 new tokens, all pending at t=0, slots=2 (scripts/serve_tune.py)
    trace = sim.TrafficTrace(requests=tuple(
        (0.0, 4 + (i * 5) % 13, 4) for i in range(24)))

    def arm(chunk, tick):
        adm = AdmissionController(max_depth=64)
        adm.complete(prefill_tokens=chunk, prefill_s=tick,
                     decode_tokens=1, decode_s=tick)
        pt, dt = sim.phase_ticks_from_admission(adm, chunk)
        assert pt == pytest.approx(tick) and dt == pytest.approx(tick)
        return sim.simulate_serving(TOPO8, trace, prefill_chunk=chunk,
                                    slots=2, prefill_tick_s=pt,
                                    decode_tick_s=dt)

    sim_tok = arm(1, t_tok)
    sim_chk = arm(4, t_chk)
    assert sim_tok["ticks"] == 337 and sim_chk["ticks"] == 165
    assert sim_tok["wall_s"] == pytest.approx(rec_tok["wall_s"], rel=0.35)
    assert sim_chk["wall_s"] == pytest.approx(rec_chk["wall_s"], rel=0.35)
    rec_ratio = rec_tok["wall_s"] / rec_chk["wall_s"]
    sim_ratio = sim_tok["wall_s"] / sim_chk["wall_s"]
    assert sim_ratio > 1.0                  # chunked wins, as recorded
    # the blended-tick model prices both phases identically, so its
    # ratio is fixed at total-ticks/total-ticks regardless of the tick
    blend_tok = sim.simulate_serving(TOPO8, trace, prefill_chunk=1,
                                     slots=2)
    blend_chk = sim.simulate_serving(TOPO8, trace, prefill_chunk=4,
                                     slots=2)
    blend_ratio = blend_tok["wall_s"] / blend_chk["wall_s"]
    assert abs(sim_ratio - rec_ratio) < abs(blend_ratio - rec_ratio)


def test_serving_autoscaler_relieves_backlog():
    tr = sim.TrafficTrace.poisson(rps=900.0, duration_s=1.5,
                                  prompt_tokens=16, decode_tokens=4,
                                  seed=5)
    fixed = sim.simulate_serving(TOPO8, tr, prefill_chunk=4, slots=4,
                                 replicas=1)
    auto = sim.simulate_serving(
        TOPO8, tr, prefill_chunk=4, slots=4, replicas=1,
        autoscale={"min": 1, "max": 4, "up_q": 2.0, "down_q": 0.5,
                   "interval_s": 0.25})
    assert auto["scale_events"] > 0
    assert auto["p99_s"] < fixed["p99_s"]


# ---------------------------------------------------------------------------
# SimTransport: virtual time under the real protocol's access pattern
# ---------------------------------------------------------------------------


def test_sim_transport_kv_semantics():
    from dear_pytorch_tpu.resilience.cluster import PeerTimeout

    st = sim.SimTransport()
    st.attach()
    st.set("ns/a/1/k", "v")
    assert st.get("ns/a/1/k", 5.0) == "v"
    with pytest.raises(PeerTimeout):
        st.get("ns/missing", 0.05)       # sub-min-park probe: no hang
    assert st.decide_once("ns/d", "first") == "first"
    assert st.decide_once("ns/d", "second") == "first"
    st.set("ns/a/2/k", "w")
    assert st.list_prefix("ns/a") == ["1", "2"]
    st.prune_prefix("ns/a")
    assert st.list_prefix("ns/a") == []
    st.detach()


def test_sim_transport_virtual_timeout_advances_clock():
    """A lone parked actor's timeout advances virtual time without
    burning real time."""
    from dear_pytorch_tpu.resilience.cluster import PeerTimeout

    st = sim.SimTransport(quantum_s=1.0)
    st.attach()
    t0 = time.perf_counter()
    with pytest.raises(PeerTimeout):
        st.get("never", 300.0)
    real = time.perf_counter() - t0
    assert st.now_s >= 300.0
    assert real < 5.0
    assert st.advances >= 1
    st.detach()


# ---------------------------------------------------------------------------
# the headline: 1000-rank / 8-slice storm, tier-1 time
# ---------------------------------------------------------------------------


def _assert_storm_records(out, world, victims, kill_slice):
    e1, e2, e3 = (out["records"][k] for k in ("e1", "e2", "e3"))
    assert out["errors"] == {}
    assert out["stuck_threads"] == []
    assert out["lockstep"] is True
    # decided/e1: one shrink epoch removing exactly the victim slice
    assert e1["delta"]["removed"] == victims
    assert e1["delta"]["added"] == []
    assert e1["delta"]["slices"]["removed"] == [kill_slice]
    assert len(e1["members"]) == world - len(victims)
    assert not (set(victims) & set(e1["members"]))
    # decided/e2: the relaunched slice admitted back in one epoch
    assert e2["delta"]["added"] == victims
    assert e2["delta"]["removed"] == []
    assert e2["delta"]["slices"]["added"] == [kill_slice]
    assert e2["members"] == list(range(world))
    # no third transition: shrink -> rejoin, nothing else
    assert e3 is None


def test_membership_storm_small_world():
    """Protocol shape at a size that runs in milliseconds — the same
    decision-record sequence the live `--multislice` chaos gate
    asserts (slice SIGKILL -> one shrink epoch -> rejoin -> lockstep)."""
    out = sim.run_membership_storm(world=16, ranks_per_slice=4,
                                   kill_slice=2)
    _assert_storm_records(out, 16, list(range(8, 12)), 2)


def test_membership_storm_1000_ranks_resolves_in_tier1_time():
    """The acceptance gate: a 1000-rank / 8-slice world survives a full
    slice SIGKILL and returns to lockstep — one shrink epoch, one
    admission epoch, every rank's final exchange agreeing — within a
    bounded number of the protocol's own detection windows (the protocol
    runs unmodified; only the transport's clock is virtual).

    The bound is on what the simulator counts, not on this box's clock:
    the storm is 1000 Python threads' bookkeeping, 70 s of CPU alone and
    100 s and more of wall when six test workers share the cores (the old
    ``wall < 60`` failed on every tree for that reason). Ten runs, quiet
    and loaded, resolved in 61,500 to 61,801 virtual seconds, 123 to 124
    detection windows of ``world / 2`` seconds, with 6,686 to 21,336 clock
    advances (those do grow with contention: waiters park in smaller
    groups). A handful more windows spent anywhere is a protocol
    regression; a clock that advances without resolving is a livelock."""
    world = 1000
    t0, c0 = time.perf_counter(), time.process_time()
    out = sim.run_membership_storm(world=world, ranks_per_slice=125,
                                   kill_slice=1)
    spent = (f"{time.perf_counter() - t0:.1f} s wall, "
             f"{time.process_time() - c0:.1f} s CPU")
    _assert_storm_records(out, world, list(range(125, 250)), 1)
    windows = out["virtual_s"] / (world / 2.0)
    assert windows <= 130.0, (
        f"storm took {windows:.1f} detection windows (gate: 130; {spent})")
    assert out["clock_advances"] <= 100_000, (
        f"{out['clock_advances']} clock advances (gate: 100,000; {spent})")


# ---------------------------------------------------------------------------
# tuner sim backends
# ---------------------------------------------------------------------------


def test_tune_plan_sim_prefers_cheaper_wire():
    from dear_pytorch_tpu.tuning.planspace import PlanSpace

    space = PlanSpace(modes=("dear", "dear-fused"),
                      threshold_bound=(1.0, 64.0), compressors=(None,),
                      comm_dtypes=(None, "bf16"),
                      gather_dtypes=(None, "bf16"), remats=(None,))
    out = sim.tune_plan_sim(
        space, lambda thr: plan8(max(thr, 1.0)), TOPO8,
        compute_time_s=0.012, max_trials=6, budget_steps=800)
    assert out["finished"]
    assert out["virtual_steps"] > 0
    # bf16 wire halves the dominant β term — the search must find it
    best = out["best"]
    assert best["comm_dtype"] == "bf16" or best["gather_dtype"] == "bf16"


def test_tune_serve_sim_runs_real_serve_tuner():
    from dear_pytorch_tpu.tuning.planspace import ServeSpace

    space = ServeSpace(chunk_bound=(1, 16), slots=(2, 4),
                       kv_dtypes=(None,), flash=(False,), tp=(False,),
                       world=8, ring_len=8)
    out = sim.tune_serve_sim(space, TOPO8, _trace(), max_trials=6)
    assert out["best_p99_s"] is not None
    assert out["episodes"]
    # the winner can't be worse than the worst episode it explored
    assert out["best_p99_s"] <= max(e["p99_s"]
                                    for e in out["episodes"].values())


def test_tune_fleet_sim_searches_replicas_and_autoscale():
    trace = sim.TrafficTrace.poisson(rps=800.0, duration_s=1.0,
                                     prompt_tokens=16, decode_tokens=4,
                                     seed=4)
    out = sim.tune_fleet_sim(sim.FleetSpace(replicas=(1, 2, 4)), TOPO8,
                             trace, max_trials=6,
                             cost_per_replica_s=0.01)
    assert out["best"]["replicas"] in (1, 2, 4)
    assert out["best_objective"] is not None
    # a 1-replica no-autoscale fleet drowns at this rate — the search
    # must leave the default corner
    assert not (out["best"]["replicas"] == 1
                and not out["best"]["autoscale"])


def test_fleet_space_interface_contract():
    space = sim.FleetSpace(replicas=(1, 2), max_replicas=2)
    cfgs = space.configs()
    assert all(space.feasible(c) is None for c in cfgs)
    assert space.feasible(sim.FleetConfig(replicas=4)) is not None
    d = space.default_config()
    assert d.key() == (1, False)
    assert "R=1" in d.describe()


def test_virtual_clock_is_perf_counter_shaped():
    clock = sim.VirtualClock()
    assert clock() == 0.0
    clock.advance(2.5)
    assert clock() == 2.5
