"""SDC sentinel (`resilience.sdc`): per-bucket fingerprint voting,
replay-based blame, and the durable quarantine ledger.

The detection premise is DeAR-specific: post-reduce bucket state is
replica-identical by construction, so an exact uint32 checksum per
bucket — computed IN-PROGRAM by the compiled step and gathered only at
health-sync cadence — turns silent per-host corruption into a minority
vote localized to (rank, bucket). The red/green test here pins the
sensitivity ordering the subsystem exists for: a one-ulp weight flip
that the loss-bits desync sentinel cannot see for multiple steps moves
the bucket fingerprint on the very first corrupt step.

Blame and quarantine are pure-python (transport-backed) and tested
directly; the full arc — vote, rollback replay, conviction, rc-75
drain, fresh-host backfill, probation readmission, and the serving
shadow-replay twin — runs as `scripts/chaos_check.py --sdc`, gated
three-consecutive-green below.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dear_pytorch_tpu.resilience import inject as INJ
from dear_pytorch_tpu.resilience import sdc
from dear_pytorch_tpu.resilience.cluster import LocalTransport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fp(*words):
    return sdc.encode_fingerprints(np.asarray(words, dtype=np.uint32))


# -- the fingerprint vote -----------------------------------------------------


def test_vote_localizes_minority_to_rank_and_bucket():
    clean = _fp(10, 20, 30)
    bad = _fp(10, 21, 30)
    assert sdc.vote({0: clean, 1: bad, 2: clean}) == [(1, 1)]


def test_vote_needs_three_voters_to_blame():
    # with two voters a disagreement is detectable but not attributable
    assert sdc.vote({0: _fp(1), 1: _fp(2)}) == []
    # abstainers (empty fingerprint) don't count toward the quorum
    assert sdc.vote({0: _fp(1), 1: _fp(2), 2: ""}) == []


def test_vote_requires_strict_majority_per_bucket():
    # three-way split: nobody holds a majority, nobody is blamed
    assert sdc.vote({0: _fp(1), 1: _fp(2), 2: _fp(3)}) == []


def test_vote_shape_stragglers_abstain():
    # a mid-rescale rank with a different bucket count must not poison
    # the vote; with it abstaining only 2 comparable voters remain
    assert sdc.vote({0: _fp(1, 2), 1: _fp(1, 2, 3), 2: _fp(1, 9)}) == []
    # with 3 comparable voters the straggler is simply ignored
    assert sdc.vote(
        {0: _fp(1, 2), 1: _fp(1, 2, 3), 2: _fp(1, 9), 3: _fp(1, 2)}
    ) == [(2, 1)]


def test_fingerprint_roundtrip_and_reference_checksum():
    words = np.asarray([0, 1, 0xFFFFFFFF], dtype=np.uint32)
    enc = sdc.encode_fingerprints(words)
    assert isinstance(enc, str) and enc
    # the host-side reference agrees with itself across layouts
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert sdc.fingerprint_array(a) == sdc.fingerprint_array(a.ravel())
    b = a.copy()
    b[1, 2] = np.float32(np.frombuffer(
        (np.frombuffer(b[1, 2].tobytes(), np.uint32) | 1).tobytes(),
        np.float32)[0])
    assert sdc.fingerprint_array(a) != sdc.fingerprint_array(b)


# -- the quarantine ledger ----------------------------------------------------


def test_ledger_strikeout_quarantines_and_readmit_clears():
    led = sdc.SdcLedger(LocalTransport(), strike_threshold=3)
    assert not led.strike("h1", rank=1, bucket=0, step=5)["quarantined"]
    assert not led.strike("h1", rank=1, bucket=2, step=9)["quarantined"]
    st = led.strike("h1", rank=1, bucket=1, step=12)
    assert st["quarantined"] and st["strikes"] == 3
    assert led.quarantined("h1")
    kinds = [e["kind"] for e in led.events("h1")]
    assert kinds == ["strike", "strike", "strike", "quarantine"]
    assert led.quarantined_hosts() == ["h1"]
    st = led.readmit("h1", proof="selftest")
    assert not st["quarantined"] and st["strikes"] == 0
    # strike history restarts after readmission
    assert not led.strike("h1", rank=1, bucket=0, step=40)["quarantined"]


def test_ledger_conviction_is_idempotent_while_quarantined():
    led = sdc.SdcLedger(LocalTransport(), strike_threshold=3)
    st = led.convict("h2", rank=2, bucket=1, step=7)
    assert st["quarantined"] and st["convicted"]
    led.convict("h2", rank=2, bucket=1, step=8)
    assert len(led.events("h2")) == 1  # no-op while already quarantined
    led.readmit("h2")
    led.convict("h2", rank=2, bucket=0, step=30)  # re-offence lands
    assert [e["kind"] for e in led.events("h2")] == [
        "conviction", "readmit", "conviction"]


def test_ledger_replicated_writers_dedupe_first_writer_wins():
    # every rank appends the same deterministic vote outcome: one record
    t = LocalTransport()
    a = sdc.SdcLedger(t, strike_threshold=3)
    b = sdc.SdcLedger(t, strike_threshold=3)
    a.convict("h3", rank=1, bucket=0, step=5)
    b.convict("h3", rank=1, bucket=0, step=5)
    assert len(a.events("h3")) == 1
    # a genuinely different record (a real race) lands as its own event
    b.readmit("h3")
    a.strike("h3", rank=1, bucket=0, step=9)
    b.strike("h3", rank=2, bucket=1, step=9)
    assert len([e for e in a.events("h3") if e["kind"] == "strike"]) == 2


# -- the replay arbiter -------------------------------------------------------


def _sentinel(host="h-self", transport=None):
    led = sdc.SdcLedger(transport or LocalTransport(), strike_threshold=2)
    return sdc.SdcSentinel(host=host, ledger=led), led


def test_replay_reproduction_convicts():
    s, led = _sentinel()
    hosts = {0: "h0", 1: "h1", 2: "h2"}
    acts = s.note_votes([(1, 0)], hosts, step=5)
    assert acts["opened"] == ["h1"] and not acts["convicted"]
    assert not led.quarantined("h1")  # one vote is suspicion, not proof
    # the coordinated rollback re-ran the window; same minority again
    acts = s.note_votes([(1, 0)], hosts, step=5)
    assert acts["convicted"] == ["h1"]
    assert led.quarantined("h1")
    ev = [e for e in led.events("h1") if e["kind"] == "conviction"][0]
    assert ev["rank"] == 1 and ev["bucket"] == 0 and ev["step"] == 5


def test_clean_replay_is_a_strike_not_a_conviction():
    s, led = _sentinel()
    hosts = {0: "h0", 1: "h1", 2: "h2"}
    s.note_votes([(1, 2)], hosts, step=5)
    acts = s.note_votes([], hosts, step=5)
    assert acts["struck"] == ["h1"] and not acts["convicted"]
    st = led.state("h1")
    assert st["strikes"] == 1 and not st["quarantined"]
    # strikes accumulate across separate transients to a strikeout
    s.note_votes([(1, 2)], hosts, step=9)
    acts = s.note_votes([], hosts, step=9)
    assert acts["convicted"] == ["h1"]  # threshold=2 crossed
    assert led.quarantined("h1")


def test_undecidable_sync_keeps_the_case_pending():
    # a sync too thin to vote (shrink mid-flight) must not read as a
    # clean replay — the open case waits for the next decidable vote
    s, led = _sentinel()
    hosts = {0: "h0", 1: "h1", 2: "h2"}
    s.note_votes([(1, 0)], hosts, step=5)
    acts = s.note_votes([], hosts, step=6, voted=False)
    assert acts == {"opened": [], "convicted": [], "struck": []}
    assert "h1" in s.open_cases
    acts = s.note_votes([(1, 0)], hosts, step=5)
    assert acts["convicted"] == ["h1"]


def test_own_conviction_requests_drain():
    s, led = _sentinel(host="h1")
    hosts = {0: "h0", 1: "h1", 2: "h2"}
    s.note_votes([(1, 0)], hosts, step=5)
    assert not s.drain_requested
    s.note_votes([(1, 0)], hosts, step=5)
    assert s.drain_requested


# -- the fault: a flip the loss-bits sentinel cannot see ----------------------


def test_flip_grammar_arms_persistent_faults():
    faults = INJ.parse_faults("flip@5:2:r1,flip_logits@3:r0")
    assert faults[0] == INJ.Fault(kind="flip", step=5, arg=2.0, rank=1)
    assert faults[1] == INJ.Fault(kind="flip_logits", step=3, rank=0)
    inj = INJ.FaultInjector(faults, own_rank=1)
    assert inj.flip_bucket_for(4) is None
    assert inj.flip_bucket_for(5) == 2
    # a stuck lane, not a hiccup: armed for every later attempt — the
    # post-rollback replay reproduces it and the arbiter convicts
    assert inj.flip_bucket_for(6) == 2
    other = INJ.FaultInjector(faults, own_rank=0)
    assert other.flip_bucket_for(5) is None  # rank-targeted
    assert other.corrupt_tokens(3, [4, 5]) == [5, 5]
    assert other.corrupt_tokens(4, [4, 5]) == [5, 5]  # persistent


def test_fingerprint_catches_what_loss_bits_miss(mesh, monkeypatch):
    """The red/green sensitivity ordering: a one-ulp flip of a real
    weight leaves the loss BITWISE IDENTICAL for several steps (the
    desync sentinel is blind) while the exact per-bucket checksum
    diverges on the first corrupt step — and the 3-voter minority vote
    localizes it to (rank, flipped bucket)."""
    import jax

    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step
    from tests.test_dear_numerics import _data, _loss_fn, _mlp_params

    monkeypatch.setenv("DEAR_SDC", "1")  # resolved at build time
    params = _mlp_params(jax.random.PRNGKey(0))
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, threshold_mb=0.0008, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9))
    clean = ts.init(params)
    # `flip_state_bucket` SETS the low mantissa bit (idempotent `|=`), so
    # on an element whose bit is already 1 it corrupts nothing — and for
    # PRNGKey(0) under this JAX the target is such an element
    # (0x3d96fb4d): the step-0 fingerprints then agree, which is what
    # this test failed on, alone or under six workers, on every tree.
    # Clear the bit in the shared starting state so that the injected
    # corruption is a real one by construction.
    b0 = ts.plan.buckets[0]
    words = np.array(jax.device_get(clean.buffers[0])).view(np.uint32)
    words[b0.size - 1] &= ~np.uint32(1)
    clean = clean._replace(buffers=(jax.device_put(
        words.view(np.float32), clean.buffers[0].sharding),)
        + clean.buffers[1:])
    dirty = clean
    batches = [_data(jax.random.PRNGKey(100 + i)) for i in range(4)]
    loss_blind_steps = 0
    flipped_bucket = None
    for i, batch in enumerate(batches):
        clean, mc = ts.step(clean, batch)
        dirty, flipped_bucket, idx = INJ.flip_state_bucket(
            dirty, 0, ts.plan)
        assert idx == ts.plan.buckets[flipped_bucket].size - 1
        dirty, md = ts.step(dirty, batch)
        fc = np.asarray(jax.device_get(mc["sdc_fp"]))
        fd = np.asarray(jax.device_get(md["sdc_fp"]))
        # caught within ONE check interval, localized to the bucket
        assert (fc != fd).any(), f"fingerprint blind at step {i}"
        assert (fc != fd)[flipped_bucket]
        lc = np.asarray(jax.device_get(mc["loss"]))
        ld = np.asarray(jax.device_get(md["loss"]))
        if lc.tobytes() == ld.tobytes():
            loss_blind_steps += 1
        suspects = sdc.vote({
            0: sdc.encode_fingerprints(fc),
            1: sdc.encode_fingerprints(fd),
            2: sdc.encode_fingerprints(fc)})
        assert (1, int(flipped_bucket)) in suspects
        assert all(r == 1 for r, _ in suspects)
    # ...while the loss-bits sentinel misses the corruption for >= K
    # steps (one-ulp perturbations drown in the float32 reductions)
    assert loss_blind_steps >= 2, (
        f"loss bits diverged too fast ({loss_blind_steps} blind steps) "
        "— the fingerprint no longer demonstrates extra sensitivity")


def test_flip_state_bucket_is_idempotent():
    import jax  # noqa: F401 — flip_state_bucket device_gets

    class _S:
        def __init__(self, buffers):
            self.buffers = buffers

        def _replace(self, buffers):
            return _S(buffers)

    buf = np.arange(8, dtype=np.float32)
    s1, b, idx = INJ.flip_state_bucket(_S((buf,)), 0, None)
    assert (b, idx) == (0, 7)
    s2, _, _ = INJ.flip_state_bucket(s1, 0, None)
    one = np.asarray(s1.buffers[0])
    two = np.asarray(s2.buffers[0])
    assert one.tobytes() == two.tobytes()  # |=, not XOR: replay-stable
    assert one.tobytes() != buf.tobytes()


# -- host identity: strikes follow the HOST, not the rank ---------------------


def _supervisor(tmp_path, **kw):
    from launch.supervisor import ElasticSupervisor

    env = {"DEAR_SDC": "1", "PATH": os.environ.get("PATH", "")}
    return ElasticSupervisor(
        2, [sys.executable, "-c", "pass"],
        elastic_dir=str(tmp_path / "elastic"), env=env, **kw)


def test_supervisor_charges_strikes_to_the_host_across_incarnations(
        tmp_path):
    sup = _supervisor(tmp_path)
    host = sup._seat_host(0)
    assert host  # minted once
    # the seat keeps its host across relaunches while the host is clean:
    # a respawned rank INHERITS the ledger state its hardware earned
    assert sup._seat_host(0) == host
    led = sup.ledger()
    led.strike(host, rank=0, bucket=0, step=5)
    led.strike(host, rank=0, bucket=0, step=9)
    assert sup._seat_host(0) == host  # struck but not out: same host
    assert led.state(host)["strikes"] == 2
    led.strike(host, rank=0, bucket=1, step=13)  # threshold (default 3)
    assert led.quarantined(host)
    # quarantined: the seat is re-seated on a FRESH host, never the
    # convicted one — and probation for the old host is kicked off
    sup._probation_done.add(host)  # keep the unit test subprocess-free
    fresh = sup._seat_host(0)
    assert fresh != host
    assert ("sdc_reseat", 0) in sup.events
    # the fresh host starts clean while the old host's record persists
    assert not led.quarantined(fresh)
    assert led.quarantined(host)
    # identity is durable: a restarted supervisor reads the same pool
    sup2 = _supervisor(tmp_path)
    assert sup2._seat_host(0) == fresh
    assert sup2._seat_host(1) not in (host, fresh)


def test_probation_gate_blocks_until_selftest_passes(tmp_path):
    led = sdc.ledger_from_dir(str(tmp_path / "sdc"))
    led.convict("badhost", rank=1, bucket=0, step=5)
    # a clean host passes straight through, no self-test
    assert sdc.probation_gate(led, "cleanhost")
    # the quarantined host must pass the known-answer burn-in, which
    # writes its own readmit record (steps=2 keeps the test fast)
    assert sdc.probation_gate(led, "badhost", steps=2)
    assert not led.quarantined("badhost")
    assert [e["kind"] for e in led.events("badhost")] == [
        "conviction", "readmit"]


def test_scale_policy_caps_capacity_by_quarantined_hosts(tmp_path):
    from dear_pytorch_tpu.resilience.scale import ScalePolicy

    cap = tmp_path / "capacity.json"
    cap.write_text(json.dumps({"target_world": 3}))
    pol = ScalePolicy(capacity_file=str(cap), hysteresis_s=0.0,
                      max_world=3)
    # while a host sits in the ledger the usable pool is smaller: the
    # backfill that would re-seat it is HELD (this is what makes
    # quarantine deadlock-free only together with drain-time probation)
    for _ in range(3):
        d = pol.decide(live_world=2, live_ranks=(0, 2), quarantined=1)
        assert d is None
    # readmission lifts the cap and the backfill proceeds
    decisions = [pol.decide(live_world=2, live_ranks=(0, 2), quarantined=0)
                 for _ in range(3)]
    ups = [d for d in decisions if d is not None]
    assert ups and ups[0].kind == "scale_up" and ups[0].count == 1


# -- serving-side quality gauge ----------------------------------------------


def test_held_out_headroom_scores_real_eval_not_just_finiteness():
    from dear_pytorch_tpu.serving.weights import held_out_headroom

    rng = np.random.default_rng(0)
    good = {"w": rng.standard_normal((32, 32)).astype(np.float32) * 0.02}
    h = held_out_headroom(good)
    assert 0.5 < h <= 1.0  # near-uniform prediction reads high
    # NaN poisoning reads 0.0 (everything the old placeholder caught)
    poisoned = {"w": good["w"].copy()}
    poisoned["w"][0, 0] = np.nan
    assert held_out_headroom(poisoned) == 0.0
    # finite but value-damaged weights move the gauge DOWN — the
    # sensitivity the finite-fraction placeholder lacked by construction
    damaged = {"w": good["w"] * 1e4}
    assert held_out_headroom(damaged) < h
    # the gauge is a real NLL eval: a confidently-wrong forward scores 0
    # while a uniform one scores ~1, with ALL-FINITE params in both
    def confident_wrong(params, ctx):
        logits = np.full(32, -10.0)
        logits[0] = 10.0
        return logits
    assert held_out_headroom(good, apply_fn=confident_wrong) == 0.0
    assert held_out_headroom(
        good, apply_fn=lambda p, c: np.zeros(32)) > 0.99


# -- offline policy search ----------------------------------------------------


def test_simulate_sdc_models_the_full_quarantine_arc():
    from dear_pytorch_tpu.observability import sim

    topo = sim.SimTopology(num_slices=1, chips_per_slice=8)
    trace = sim.TrafficTrace.poisson(rps=100.0, duration_s=1.5,
                                     prompt_tokens=16, decode_tokens=4,
                                     seed=3)
    out = sim.simulate_sdc(topo, trace, replicas=3, shadow_every=2,
                           strike_threshold=1, corrupt_replica=1,
                           corrupt_at_s=0.3, probation_s=0.5)
    # the arc: corruption starts, the shadow replay detects, the culprit
    # quarantines, probation readmits — in that order
    assert out["detect_s"] is not None and out["detect_s"] >= 0.0
    assert out["quarantined_at_s"] is not None
    assert out["readmit_at_s"] is not None
    assert out["readmit_at_s"] > out["quarantined_at_s"] >= 0.3
    # exposure is bounded (possibly zero: the detecting shadow can land
    # on the culprit before it serves a corrupt primary) and the
    # policy's overhead is priced, not free
    assert 0 <= out["exposed"] < out["requests"]
    assert out["mismatches"] >= 1
    assert out["shadows"] > 0 and out["arbiters"] >= 1
    # zero-drop: fencing re-dispatches, it never loses requests
    assert out["requests"] >= len(trace.requests)
    # a tighter cadence can only expose fewer corrupted responses
    tight = sim.simulate_sdc(topo, trace, replicas=3, shadow_every=1,
                             strike_threshold=1, corrupt_replica=1,
                             corrupt_at_s=0.3, probation_s=0.5)
    assert tight["exposed"] <= out["exposed"]


# -- the acceptance storm: three consecutive greens ---------------------------


@pytest.mark.timeout(1300, method="signal")
def test_chaos_check_sdc_storm_three_consecutive(tmp_path):
    """scripts/chaos_check.py --sdc, 3/3 consecutive (ISSUE-20
    acceptance): the fingerprint vote localizes the flipped bucket to
    the injected rank, the rollback replay convicts, the supervisor
    quarantine-drains the host and backfills the seat on a FRESH host
    while probation readmits the old one, no corrupt step is reachable
    from any published checkpoint, and the serving leg catches a
    post-signing token corruption via the router's shadow replay into
    the same ledger — with the quarantine capacity cap holding the
    backfill until readmission and zero dropped requests throughout.
    Three consecutive runs guard against vote/drain races that a single
    green would leave latent."""
    script = os.path.join(REPO, "scripts", "chaos_check.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for attempt in range(3):
        proc = subprocess.run(
            [sys.executable, script, "--sdc", "--checkpoint-every", "4",
             "--workdir", str(tmp_path / f"run{attempt}")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=420)
        assert proc.returncode == 0, (
            f"run {attempt}: " + proc.stdout[-3000:])
        assert "CHAOS CHECK PASSED" in proc.stdout, (
            f"run {attempt}: " + proc.stdout[-3000:])
