"""Chaos check: zero-loss-of-progress recovery under a multi-fault storm.

One short guarded training run (CPU world=8 emulation, tiny MLP) absorbs —
via `dear_pytorch_tpu.resilience` fault injection — a NaN-poisoned batch, a
raised step exception, a corrupted newest checkpoint, and a SIGTERM
preemption; then a simulated relaunch resumes and finishes. Asserts:

  - every fault fired and every recovery landed (3 rollbacks, checksum
    fallback past the corrupted checkpoint, a verified emergency save),
  - the relaunch resumes EXACTLY at the emergency checkpoint's step
    (zero loss of progress since the save),
  - the chaos run's final loss is at least as converged as the fault-free
    run one rollback window earlier (faults cost at most the replayed
    window, never the run),
  - a separate injected hang fires the step watchdog, whose report names
    the last-good checkpointed step.

CI entry: tests/test_resilience.py drives `run()` in-process under the
tier-1 marker scheme. Standalone:

  python scripts/chaos_check.py [--steps 20] [--workdir /tmp/chaos]

Prints one JSON summary line; exit 0 iff every assertion held.

**Multi-process mode** (``--procs 2``): the fault storm runs through the
2-process launcher env contract (JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES / JAX_PROCESS_ID — the same contract
launch/cpu_cluster.sh and tests/test_multiprocess.py speak). Each rank
trains an independent replica with a per-host checkpoint directory
(``DEAR_CKPT_SHARED=0``) and absorbs RANK-TARGETED faults — a NaN on
rank 1, a raised exception on rank 0, a corrupted newest checkpoint on
rank 0 — and every recovery must be a `resilience.cluster` consensus:
the parent asserts that all ranks rolled back to IDENTICAL steps (the
corrupted-checkpoint rollback landing on the newest commonly verified
step) and finished in lockstep. Driven by
tests/test_resilience.py::test_chaos_check_two_process_storm in tier-1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_SCRIPTS = os.path.dirname(os.path.abspath(__file__))
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

# the storm parents' shared fleet-pump / capacity / loader helpers —
# one module, composed by --serve, --autoscale, and --online instead of
# per-storm copies
import chaos_common as CC  # noqa: E402


# -- tiny deterministic workload (mirrors the test suite's MLP scale) ---------


def _mlp_params(key):
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    return {
        "dense": {"kernel": jax.random.normal(k1, (12, 32)) * 0.1,
                  "bias": jnp.zeros((32,))},
        "out": {"kernel": jax.random.normal(k2, (32, 4)) * 0.1,
                "bias": jnp.zeros((4,))},
    }


def _loss_fn(params, batch):
    import jax
    import jax.numpy as jnp

    x, y = batch
    h = jnp.tanh(x @ params["dense"]["kernel"] + params["dense"]["bias"])
    logits = h @ params["out"]["kernel"] + params["out"]["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(logp * jax.nn.one_hot(y, 4), axis=-1))


def _data(key, n=64):
    """Learnable task: labels come from a fixed random teacher, so the
    loss decreases monotonically enough for the rollback-window tolerance
    comparison to be meaningful."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(key, (n, 12))
    teacher = jax.random.normal(jax.random.PRNGKey(42), (12, 4))
    return x, jnp.argmax(x @ teacher, axis=-1)


_check = CC.check  # every storm phase asserts through the shared helper


def run(steps: int = 20, checkpoint_every: int = 4,
        workdir: str | None = None) -> dict:
    """Run every chaos phase; returns the summary dict (key ``passed``)."""
    import tempfile

    import jax
    import numpy as np

    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step
    from dear_pytorch_tpu.resilience import (
        Fault, FaultInjector, PreemptionHandler, StepWatchdog,
    )
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer

    backend.init()
    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_")
    failures: list[str] = []

    # a live tracer so recovery counters are assertable; restored on exit
    prev_tracer = T._tracer
    tracer = T.Tracer([T.MemoryExporter()])
    T.set_tracer(tracer)
    try:
        params = _mlp_params(jax.random.PRNGKey(0))
        ts = build_train_step(
            _loss_fn, params, threshold_mb=0.0008, donate=False,
            optimizer=fused_sgd(lr=0.05, momentum=0.9),
        )
        batches = [_data(jax.random.PRNGKey(100 + i))
                   for i in range(4 * steps)]

        def guarded(subdir, **kw):
            kw.setdefault("check_every", 1)
            kw.setdefault("checkpoint_every", checkpoint_every)
            return GuardedTrainer(ts, os.path.join(workdir, subdir),
                                  params, **kw)

        # -- phase 1: fault-free reference ---------------------------------
        tr = guarded("clean")
        state = ts.init(params)
        clean_losses = []
        for b in batches[:steps]:
            state, m = tr.step(state, b)
            clean_losses.append(float(m["loss"]))

        # -- phase 2: the storm --------------------------------------------
        # attempts: nan@6 (rollback), exc@9 (rollback), ckpt_corrupt@13
        # (newest checkpoint poisoned on disk), nan@14 (rollback must fall
        # back PAST the corrupted checkpoint), preempt@17 (SIGTERM ->
        # emergency save -> exit)
        inj = FaultInjector([
            Fault(kind="nan", step=6),
            Fault(kind="exc", step=9),
            Fault(kind="ckpt_corrupt", step=13),
            Fault(kind="nan", step=14),
            Fault(kind="preempt", step=17),
        ])
        chaos_dir = os.path.join(workdir, "chaos")
        rollbacks = []
        preempted_at = None
        with PreemptionHandler() as pre:
            tr = guarded("chaos", injector=inj, preemption=pre)
            tr.on_rollback = lambda n, at: rollbacks.append((n, at))
            state = ts.init(params)
            for b in batches:
                state, m = tr.step(state, b)
                if m.get("preempted"):
                    preempted_at = int(jax.device_get(state.step))
                    break
        counters = tracer.counters()
        _check(inj.pending == 0, "every scheduled fault fired", failures)
        _check(len(rollbacks) == 3,
               f"3 rollbacks (nan, exc, nan-past-corruption); got "
               f"{rollbacks}", failures)
        _check(counters.get("ckpt.corrupt_detected", 0) >= 1,
               "checksum manifest caught the corrupted checkpoint",
               failures)
        _check(len(rollbacks) == 3 and rollbacks[2][1] < rollbacks[1][1]
               + 2 * checkpoint_every,
               "third rollback fell back past the corrupted newest "
               "checkpoint", failures)
        _check(preempted_at is not None
               and counters.get("guard.preempt_saves", 0) == 1,
               "SIGTERM produced exactly one emergency save", failures)

        # -- phase 3: simulated relaunch -----------------------------------
        resumed_at = ckpt.latest_valid_step(chaos_dir)
        _check(resumed_at == preempted_at,
               f"relaunch resumes at the emergency checkpoint "
               f"(step {preempted_at}): zero loss of progress", failures)
        state = ckpt.restore_checkpoint(chaos_dir, ts,
                                        template=ts.init(params))
        tr = guarded("chaos")
        tr.steps_seen = int(resumed_at or 0)
        chaos_losses = []
        bi = steps
        while int(jax.device_get(state.step)) < steps:
            state, m = tr.step(state, batches[bi])
            bi += 1
            if not m.get("rolled_back"):
                chaos_losses.append(float(m["loss"]))
        chaos_final = chaos_losses[-1]
        # rollback-window tolerance: the chaos run reached the same update
        # count, so it must be at least as converged as the clean run one
        # checkpoint window earlier
        ref = clean_losses[steps - 1 - checkpoint_every]
        _check(np.isfinite(chaos_final) and chaos_final <= ref + 1e-6,
               f"final chaos loss {chaos_final:.4f} within rollback-window "
               f"tolerance of fault-free run (<= {ref:.4f})", failures)

        # -- phase 4: watchdog on a hung step ------------------------------
        inj = FaultInjector([Fault(kind="hang", step=3, arg=0.8)])
        tr = guarded("hang", injector=inj, checkpoint_every=2)
        state = ts.init(params)
        for b in batches[:2]:
            state, _ = tr.step(state, b)  # step-2 checkpoint
        fired = []
        with StepWatchdog(0.25, on_timeout=fired.append,
                          poll_s=0.02) as dog:
            tr._watchdog = dog
            dog.beat(step=2, last_good_step=2)
            state, _ = tr.step(state, batches[2])  # hangs 0.8s
        _check(len(fired) == 1, "watchdog fired on the injected hang",
               failures)
        _check(bool(fired) and
               fired[0].beat_info.get("last_good_step") == 2,
               "watchdog report names the last-good step (2)", failures)

        summary = {
            "passed": not failures,
            "steps": steps,
            "clean_final_loss": round(clean_losses[-1], 4),
            "chaos_final_loss": round(chaos_final, 4),
            "tolerance_ref_loss": round(ref, 4),
            "rollbacks": rollbacks,
            "preempted_at": preempted_at,
            "resumed_at": resumed_at,
            "faults_injected": int(counters.get("faults.injected", 0)),
            "guard_counters": {k: v for k, v in tracer.counters().items()
                               if k.startswith(("guard.", "ckpt.",
                                                "faults.", "watchdog."))},
            "failures": failures,
        }
        return summary
    finally:
        T.set_tracer(prev_tracer)


def run_worker(steps: int, checkpoint_every: int, workdir: str) -> dict:
    """One rank of the multi-process storm (spawned by `run_procs` with
    the launcher env contract already in the environment). Independent
    replica, per-host checkpoints, rank-targeted faults, consensus
    recovery — every rollback must land on the same step on every rank."""
    os.environ["DEAR_CKPT_SHARED"] = "0"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step
    from dear_pytorch_tpu.resilience import Fault, FaultInjector
    from dear_pytorch_tpu.resilience import cluster as CL
    from dear_pytorch_tpu.utils.guard import GuardedTrainer

    backend.init()  # joins the cluster from the launcher env contract
    pid, n = jax.process_index(), jax.process_count()
    failures: list[str] = []
    tracer = T.Tracer([T.MemoryExporter()])
    T.set_tracer(tracer)

    mesh = jax.sharding.Mesh(np.asarray(jax.local_devices()), ("dp",))
    params = _mlp_params(jax.random.PRNGKey(0))
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, threshold_mb=0.0008, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
    )
    batches = [_data(jax.random.PRNGKey(100 + i)) for i in range(steps + 4)]

    # the storm, rank-targeted: nan on rank 1 only; a raised exception on
    # rank 0 only; rank 0's newest checkpoint corrupted on ITS OWN disk,
    # so the following (everywhere) nan forces a consensus restore past a
    # view only one host has.
    inj = FaultInjector([
        Fault(kind="nan", step=5, rank=1),
        Fault(kind="exc", step=8, rank=0),
        Fault(kind="ckpt_corrupt", step=3 * checkpoint_every + 1, rank=0),
        Fault(kind="nan", step=3 * checkpoint_every + 2),
    ])
    tr = GuardedTrainer(
        ts, os.path.join(workdir, f"rank{pid}"), params,
        check_every=1, checkpoint_every=checkpoint_every, injector=inj,
    )
    _check(tr._coordinated, "guard auto-coordinates across processes",
           failures)
    rollbacks = []
    tr.on_rollback = lambda c, at: rollbacks.append(at)
    state = ts.init(params)
    losses = []
    for b in batches[:steps]:
        state, m = tr.step(state, b)
        if not m.get("rolled_back"):
            losses.append(float(m["loss"]))
    counters = tracer.counters()

    _check(inj.pending == 0, "every scheduled fault fired or was skipped",
           failures)
    _check(len(rollbacks) == 3,
           f"3 coordinated rollbacks (remote nan, remote exc, "
           f"nan-past-corruption); got {rollbacks}", failures)
    _check(counters.get("cluster.consensus_restores", 0) >= 3,
           "every restore went through cluster consensus", failures)
    if pid == 0:
        _check(counters.get("ckpt.corrupt_detected", 0) >= 1,
               "rank 0's checksum walk caught its corrupted checkpoint",
               failures)
    _check(bool(losses) and np.isfinite(losses[-1]),
           "storm run finished with a finite loss", failures)

    # cross-rank consistency: every rank saw identical rollback steps and
    # finished on identical losses (host-level exchange: no device
    # collectives, so this works on any cluster jax.distributed joins)
    co = CL.ClusterCoordinator(namespace="chaos-verify")
    views = co.exchange("verdict", json.dumps(
        {"rollbacks": rollbacks, "final_loss": losses[-1] if losses else None}
    ))
    parsed = [json.loads(v) for v in views]
    _check(all(p["rollbacks"] == parsed[0]["rollbacks"] for p in parsed),
           f"identical rollback steps on every rank: "
           f"{[p['rollbacks'] for p in parsed]}", failures)
    _check(all(p["final_loss"] is not None and
               abs(p["final_loss"] - parsed[0]["final_loss"]) < 1e-6
               for p in parsed),
           "replicas finished in lockstep (identical final loss)", failures)

    summary = {
        "passed": not failures,
        "rank": pid,
        "nprocs": n,
        "rollbacks": rollbacks,
        "final_loss": losses[-1] if losses else None,
        "fired": [f.kind for f in inj.fired],
        "skipped": [f.kind for f in inj.skipped],
        "cluster_counters": {k: v for k, v in counters.items()
                             if k.startswith(("cluster.", "guard.",
                                              "ckpt.", "faults."))},
        "failures": failures,
    }
    print("CHAOS_MP " + json.dumps(summary), flush=True)
    return summary


def run_procs(nprocs: int, steps: int, checkpoint_every: int,
              workdir: str | None) -> dict:
    """Parent of the multi-process storm: spawns ``nprocs`` workers with
    the launcher env contract and aggregates their verdicts."""
    import socket
    import subprocess
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_mp_")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(nprocs):
        env = dict(os.environ)
        env.pop("DEAR_DISABLE_DISTRIBUTED", None)
        env.pop("DEAR_TRACE_RANK", None)
        env.pop("DEAR_NUM_CPU_DEVICES", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(nprocs)
        env["JAX_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--steps", str(steps),
             "--checkpoint-every", str(checkpoint_every),
             "--workdir", workdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    outs, timed_out = [], False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            out, _ = p.communicate()
        outs.append(out)
    per_rank, failures = [], []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        line = next((ln for ln in out.splitlines()
                     if ln.startswith("CHAOS_MP ")), None)
        if timed_out or p.returncode != 0 or line is None:
            failures.append(f"rank {pid} failed (rc={p.returncode}, "
                            f"timed_out={timed_out}): {out[-1500:]}")
            continue
        rank_summary = json.loads(line[len("CHAOS_MP "):])
        per_rank.append(rank_summary)
        if not rank_summary["passed"]:
            failures.append(f"rank {pid}: {rank_summary['failures']}")
    if per_rank and not all(r["rollbacks"] == per_rank[0]["rollbacks"]
                            for r in per_rank):
        failures.append(
            f"ranks disagree on rollback steps: "
            f"{[r['rollbacks'] for r in per_rank]}")
    return {"passed": not failures, "procs": nprocs, "steps": steps,
            "per_rank": per_rank, "failures": failures}


def run_worker_elastic(checkpoint_every: int, workdir: str) -> dict:
    """One rank of the ELASTIC storm (spawned by `run_elastic` under
    `launch/supervisor.py`'s rejoin env contract). No ``jax.distributed``:
    membership, recovery, and the final lockstep verdict all run over the
    supervisor's `FileTransport` store, which outlives rank death. The
    scheduled victim SIGKILLs itself mid-run; survivors shrink the
    membership, rescale the fusion plan, reshard the pipeline, and
    continue; the supervisor's relaunch comes back through
    `ElasticCluster.rejoin` + `GuardedTrainer.elastic_resume`. Each final
    rank writes a ``verdict_rank<r>.json`` the parent gate asserts on."""
    import importlib.util
    import json

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ["DEAR_CKPT_SHARED"] = "0"
    import jax

    jax.config.update("jax_num_cpu_devices", 4)

    from dear_pytorch_tpu.observability import flight as FL
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience import membership as M
    from dear_pytorch_tpu.runtime import build as RB
    from dear_pytorch_tpu.runtime import pipeline as P
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer

    import numpy as np

    # the one shared elastic-worker harness (tests/mp_worker.py uses the
    # same one): rejoin handshake + transition hook + kill/step loop
    eh_spec = importlib.util.spec_from_file_location(
        "dear_elastic_harness",
        os.path.join(REPO, "tests", "elastic_harness.py"))
    EH = importlib.util.module_from_spec(eh_spec)
    eh_spec.loader.exec_module(EH)

    cluster = M.ElasticCluster.from_env(max_candidates=256)
    rejoining = M.ElasticCluster.rejoining_by_env()
    rank, world0 = cluster.rank, cluster.world
    kr, ka = os.environ["DEAR_CHAOS_ELASTIC_KILL"].split(":")
    kill_rank, kill_at = int(kr), int(ka)
    post_steps = int(os.environ.get("DEAR_CHAOS_ELASTIC_POST", "4"))
    ckpt_dir = os.path.join(workdir, f"rank{rank}", "ckpts")
    tracer = T.get_tracer()

    params = _mlp_params(jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:cluster.world]),
                             ("dp",))
    tuner = AutoTuner(
        _loss_fn, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
    )
    # batch 12*world-divisible rows: _data(n=12) shards over 3 AND 2
    spec = P.SyntheticSpec((
        P.Field("x", (12, 12), RB.KIND_NORMAL_F32, 0.0, 1.0),
    ))
    pipe = P.NumpyPipeline(spec, seed=123, shard=cluster.index,
                           num_shards=cluster.world)

    guard = GuardedTrainer(
        tuner.ts, ckpt_dir, params,
        check_every=1, checkpoint_every=checkpoint_every, max_keep=1000,
        max_recoveries=8, coordinator=cluster, pipeline=pipe,
    )
    EH.attach_elastic(guard, tuner)
    rollback_steps = []
    guard.on_rollback = lambda c, at: rollback_steps.append(at)

    resumed_at = None
    t_target = None
    if rejoining:
        state, resumed_at, _ = EH.reenter(cluster, tuner, guard, ckpt_dir)
        t_target = guard.steps_seen + post_steps
    else:
        state = tuner.init(params)

    # n=12 batch rows shard evenly over world 3 AND the post-shrink world 2
    state, m = EH.run_loop(
        cluster, guard, pipe, state,
        lambda i: _data(jax.random.PRNGKey(100 + i), n=12), tracer,
        rejoining=rejoining, kill=(kill_rank, kill_at),
        post=post_steps, t_target=t_target,
    )
    counters = tracer.counters()
    ring = FL.get_recorder().dump()["records"]
    verdict = {
        "rank": rank,
        "rejoined": bool(rejoining),
        "epoch": cluster.epoch,
        "members": list(cluster.members),
        "resumed_at": resumed_at,
        "rollback_steps": rollback_steps,
        "final_step": int(jax.device_get(state.step)),
        "final_loss": float(m.get("loss", float("nan"))),
        "steps_seen": guard.steps_seen,
        "plan_world": guard.ts.plan.world,
        "plan_epoch": guard.ts.plan.epoch,
        "pipe_shard": [pipe.shard, pipe.num_shards],
        "flight_epoch": (ring[-1].get("mem_epoch") if ring else None),
        "sidecar_epoch": ckpt.read_mem_epoch(ckpt_dir,
                                             guard._last_good_step or -1),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("cluster.", "guard.", "pipeline.",
                                      "autotune.", "ckpt."))},
    }
    # the lockstep verdict is itself a member-scoped collective
    views = cluster.exchange("chaos.verdict", json.dumps(
        [verdict["final_step"], verdict["final_loss"], verdict["epoch"]]))
    verdict["lockstep"] = all(
        json.loads(v) == json.loads(views[0]) for v in views)
    with open(os.path.join(workdir, f"verdict_rank{rank}.json.tmp"),
              "w") as f:
        json.dump(verdict, f)
    os.replace(os.path.join(workdir, f"verdict_rank{rank}.json.tmp"),
               os.path.join(workdir, f"verdict_rank{rank}.json"))
    print(f"CHAOS_EL rank={rank}/{world0} " + json.dumps(verdict),
          flush=True)
    return verdict


def run_elastic(nprocs: int, checkpoint_every: int,
                workdir: str | None) -> dict:
    """Parent of the elastic storm: drive `launch/supervisor.py`'s
    `ElasticSupervisor` over ``nprocs`` ranks of `run_worker_elastic`,
    SIGKILL one rank mid-run (the victim self-kills on a deterministic
    step), and gate on: survivors commit a smaller membership epoch and
    continue >= N steps with zero loss of progress past the newest
    commonly-valid checkpoint; the relaunched rank rejoins at a later
    epoch; every member finishes in lockstep; the reconfig/rejoin
    counters and epoch-stamped flight rows are visible in the exported
    telemetry."""
    import importlib.util
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_el_")
    kill_rank, kill_at = nprocs - 1, 5
    post_steps = 4
    spec = importlib.util.spec_from_file_location(
        "dear_launch_supervisor",
        os.path.join(REPO, "launch", "supervisor.py"))
    sup_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sup_mod)

    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_FLIGHT"] = "8"
    env["DEAR_CHAOS_ELASTIC_KILL"] = f"{kill_rank}:{kill_at}"
    env["DEAR_CHAOS_ELASTIC_POST"] = str(post_steps)
    # a peer's post-transition XLA recompile must not read as a death
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "30")
    sup = sup_mod.ElasticSupervisor(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker", "--elastic",
         "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=os.path.join(workdir, "elastic"), env=env,
        max_relaunches=1,
    ).start()
    rc = sup.wait(deadline_s=400)

    failures: list[str] = []
    _check(rc == 0, f"supervisor exits 0 (got {rc})", failures)
    _check(sup.relaunches.get(kill_rank) == 1
           and all(n == 0 for r, n in sup.relaunches.items()
                   if r != kill_rank),
           f"exactly the killed rank was relaunched ({sup.relaunches})",
           failures)
    verdicts = {}
    for r in range(nprocs):
        path = os.path.join(workdir, f"verdict_rank{r}.json")
        if not os.path.exists(path):
            failures.append(f"rank {r} wrote no verdict")
            continue
        with open(path) as f:
            verdicts[r] = json.load(f)
    summary = {"passed": False, "procs": nprocs, "workdir": workdir,
               "verdicts": verdicts, "failures": failures}
    if len(verdicts) != nprocs:
        return summary

    expect_restore = (kill_at - 1) - (kill_at - 1) % checkpoint_every
    for r, v in verdicts.items():
        _check(v["epoch"] == 2 and v["members"] == list(range(nprocs)),
               f"rank {r} ends at epoch 2, full membership "
               f"(epoch {v['epoch']}, members {v['members']})", failures)
        _check(v["lockstep"], f"rank {r} finished in lockstep", failures)
        _check(v["plan_world"] == nprocs and v["plan_epoch"] == 2,
               f"rank {r} trains the rescaled epoch-stamped plan "
               f"(world {v['plan_world']}, epoch {v['plan_epoch']})",
               failures)
        _check(v["pipe_shard"][1] == nprocs,
               f"rank {r} pipeline resharded over the full membership",
               failures)
        _check(v["flight_epoch"] == 2,
               f"rank {r} flight rows are epoch-stamped "
               f"({v['flight_epoch']})", failures)
        _check(v["sidecar_epoch"] == 2,
               f"rank {r} newest checkpoint sidecar carries the epoch "
               f"({v['sidecar_epoch']})", failures)
        _check(v["final_step"] >= expect_restore + post_steps
               and v["final_step"] == verdicts[0]["final_step"],
               f"rank {r} continued past the transitions to step "
               f"{v['final_step']}", failures)
    survivors = [v for r, v in verdicts.items() if r != kill_rank]
    for v in survivors:
        c = v["counters"]
        _check(c.get("cluster.reconfigs", 0) >= 1,
               f"rank {v['rank']} committed a reconfiguration", failures)
        _check(c.get("cluster.rejoins", 0) >= 1,
               f"rank {v['rank']} admitted the relaunched rank", failures)
        _check(c.get("guard.membership_changes", 0) >= 2,
               f"rank {v['rank']} guard saw both transitions", failures)
        _check(c.get("autotune.rescales", 0) >= 2,
               f"rank {v['rank']} rescaled the plan per transition",
               failures)
        _check(c.get("pipeline.reshards", 0) >= 2
               and c.get("pipeline.resumes", 0) >= 1,
               f"rank {v['rank']} pipeline resharded + resumed", failures)
        # zero loss of progress: every rollback landed exactly on the
        # newest commonly-valid checkpoint, never older
        _check(bool(v["rollback_steps"])
               and all(s == expect_restore for s in v["rollback_steps"]),
               f"rank {v['rank']} rollbacks landed on the newest common "
               f"checkpoint {expect_restore} ({v['rollback_steps']})",
               failures)
    rv = verdicts[kill_rank]
    _check(rv["rejoined"] and rv["resumed_at"] == expect_restore,
           f"relaunched rank rejoined and resumed at the fleet-agreed "
           f"step ({rv['resumed_at']})", failures)
    summary["passed"] = not failures
    summary["failures"] = failures
    return summary


def run_worker_sdc(checkpoint_every: int, workdir: str) -> dict:
    """One rank of the SDC storm (spawned — and re-seated after a
    quarantine — by `launch/supervisor.py`). Mirrors `run_worker_elastic`
    with the fingerprint sentinel armed (``DEAR_SDC=1``): rank 1 carries
    a persistent ``flip`` fault (a low bit in a bucket's padded tail —
    invisible to wire checksums and the loss-bits sentinel), the
    fingerprint vote localizes it, the coordinated rollback replays it,
    the conviction drains this rank via planned shrink, and the process
    exits `resilience.sdc.QUARANTINE_RC` after writing an
    ``sdc_exit_rank<r>.json`` forensics record. The supervisor's
    backfill re-enters on a FRESH host through the normal rejoin path
    (minus the fault: a new host does not inherit the stuck lane)."""
    import importlib.util
    import json

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ["DEAR_CKPT_SHARED"] = "0"
    import jax

    jax.config.update("jax_num_cpu_devices", 4)

    import numpy as np

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience import inject as INJ
    from dear_pytorch_tpu.resilience import membership as M
    from dear_pytorch_tpu.resilience import sdc as SDC
    from dear_pytorch_tpu.runtime import build as RB
    from dear_pytorch_tpu.runtime import pipeline as P
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer

    eh_spec = importlib.util.spec_from_file_location(
        "dear_elastic_harness",
        os.path.join(REPO, "tests", "elastic_harness.py"))
    EH = importlib.util.module_from_spec(eh_spec)
    eh_spec.loader.exec_module(EH)

    rejoining = M.ElasticCluster.rejoining_by_env()
    if rejoining:
        # the backfilled seat runs on a FRESH host (the supervisor
        # minted a new DEAR_SDC_HOST): the stuck-lane flip belongs to
        # the quarantined hardware, not to the rank id — re-arming it
        # here would corrupt the fresh host too
        os.environ.pop(INJ.FAULT_ENV, None)
    cluster = M.ElasticCluster.from_env(max_candidates=256)
    rank, world0 = cluster.rank, cluster.world
    post_steps = int(os.environ.get("DEAR_CHAOS_ELASTIC_POST", "4"))
    ckpt_dir = os.path.join(workdir, f"rank{rank}", "ckpts")
    tracer = T.get_tracer()

    # rank-targeted SDC fault: own_rank comes from the supervisor
    # contract (jax.process_index() is 0 on every rank here)
    raw = os.environ.get(INJ.FAULT_ENV, "").strip()
    injector = (INJ.FaultInjector(INJ.parse_faults(raw), own_rank=rank)
                if raw else None)

    params = _mlp_params(jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:cluster.world]),
                             ("dp",))
    tuner = AutoTuner(
        _loss_fn, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
    )
    spec = P.SyntheticSpec((
        P.Field("x", (12, 12), RB.KIND_NORMAL_F32, 0.0, 1.0),
    ))
    pipe = P.NumpyPipeline(spec, seed=123, shard=cluster.index,
                           num_shards=cluster.world)

    guard = GuardedTrainer(
        tuner.ts, ckpt_dir, params,
        check_every=1, checkpoint_every=checkpoint_every, max_keep=1000,
        max_recoveries=16, coordinator=cluster, pipeline=pipe,
        injector=injector,
    )
    EH.attach_elastic(guard, tuner)
    rollback_steps = []
    guard.on_rollback = lambda c, at: rollback_steps.append(at)
    sentinel = guard._sdc

    resumed_at = None
    t_target = None
    if rejoining:
        state, resumed_at, _ = EH.reenter(cluster, tuner, guard, ckpt_dir)
        t_target = guard.steps_seen + post_steps
    else:
        state = tuner.init(params)

    def _expected_flip_bucket(plan, requested=0):
        # mirror inject.flip_state_bucket's clamp so the parent can
        # assert the vote localized the EXACT bucket flipped
        buckets = list(getattr(plan, "buckets", None) or ())
        if not buckets:
            return None
        return min(max(int(requested), 0), len(buckets) - 1)

    try:
        # nobody self-SIGKILLs in this storm (the sentinel evicts the
        # convicted rank); the sentinel's drain raises out of the loop
        state, m = EH.run_loop(
            cluster, guard, pipe, state,
            lambda i: _data(jax.random.PRNGKey(100 + i), n=12), tracer,
            rejoining=rejoining, kill=(-1, 10**9), post=post_steps,
            t_target=t_target,
        )
    except SDC.SdcQuarantined as exc:
        counters = tracer.counters()
        record = {
            "rank": rank,
            "host": sentinel.host if sentinel is not None else "",
            "reason": str(exc),
            "expected_flip_bucket": _expected_flip_bucket(
                getattr(guard.ts, "plan", None)),
            "ckpt_steps": [int(s) for s in ckpt.valid_steps(ckpt_dir)],
            "rollback_steps": rollback_steps,
            "counters": {k: v for k, v in counters.items()
                         if k.startswith(("sdc.", "faults.", "guard.",
                                          "cluster."))},
        }
        tmp = os.path.join(workdir, f"sdc_exit_rank{rank}.json.tmp")
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, os.path.join(workdir, f"sdc_exit_rank{rank}.json"))
        print(f"CHAOS_SDC_QUARANTINED rank={rank} " + json.dumps(record),
              flush=True)
        sys.exit(SDC.QUARANTINE_RC)

    counters = tracer.counters()
    verdict = {
        "rank": rank,
        "rejoined": bool(rejoining),
        "host": sentinel.host if sentinel is not None else "",
        "epoch": cluster.epoch,
        "members": list(cluster.members),
        "resumed_at": resumed_at,
        "rollback_steps": rollback_steps,
        "final_step": int(jax.device_get(state.step)),
        "final_loss": float(m.get("loss", float("nan"))),
        "steps_seen": guard.steps_seen,
        "plan_world": guard.ts.plan.world,
        "sdc_convicted": (sorted(sentinel.convicted)
                          if sentinel is not None else []),
        "ckpt_steps": [int(s) for s in ckpt.valid_steps(ckpt_dir)],
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("cluster.", "guard.", "sdc.",
                                      "faults."))},
    }
    views = cluster.exchange("chaos.verdict", json.dumps(
        [verdict["final_step"], verdict["final_loss"], verdict["epoch"]]))
    verdict["lockstep"] = all(
        json.loads(v) == json.loads(views[0]) for v in views)
    with open(os.path.join(workdir, f"verdict_rank{rank}.json.tmp"),
              "w") as f:
        json.dump(verdict, f)
    os.replace(os.path.join(workdir, f"verdict_rank{rank}.json.tmp"),
               os.path.join(workdir, f"verdict_rank{rank}.json"))
    print(f"CHAOS_SDC rank={rank}/{world0} " + json.dumps(verdict),
          flush=True)
    return verdict


def run_sdc(checkpoint_every: int, workdir: str | None) -> dict:  # noqa: C901
    #                                 — one storm, on purpose in one narrative
    """Parent of the SDC storm — the silent-data-corruption acceptance
    gate, in two legs sharing one ledger design:

    **Training leg.** 3 supervised ranks train with the fingerprint
    sentinel armed while rank 1 carries a persistent ``flip`` fault (one
    low bit in a bucket's padded tail: wire checksums re-sign it, the
    loss-bits sentinel is deterministically blind). Gates: the vote
    localizes (rank 1, the flipped bucket) within one check interval;
    the rollback replay reproduces it and convicts; the convicted rank
    drains via planned shrink and exits rc 75; the supervisor re-seats
    the rank on a FRESH host (the quarantined host never re-seated) and
    launches the old host's probation self-test, which readmits it; the
    backfill rejoins and every member finishes in lockstep; no corrupt
    step was ever checkpointed.

    **Serving leg.** A 3-replica supervised fleet serves closed-loop
    traffic with the router's shadow replay on every response; replica 1
    corrupts tokens AFTER response signing (``flip_logits`` — the
    checksum verifies). Gates: the exact-token vote catches it, the
    third-replica arbiter convicts replica 1 into the same ledger shape,
    the router fences it (zero dropped requests), the drained seat's
    backfill is HELD by the quarantine capacity cap until the probation
    self-test readmits the host, then serving resumes at full strength.
    """
    import signal
    import subprocess
    import tempfile
    import threading
    import time

    from dear_pytorch_tpu.resilience import sdc as SDC

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_sdc_")
    os.makedirs(workdir, exist_ok=True)
    failures: list[str] = []
    nprocs, flip_at, post_steps = 3, 5, 4
    sup_mod = CC.load_supervisor()

    # -- leg 1: training — fingerprint vote, replay blame, quarantine -----
    train_dir = os.path.join(workdir, "train")
    os.makedirs(train_dir, exist_ok=True)
    elastic_dir = os.path.join(train_dir, "elastic")
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_SDC"] = "1"
    env["DEAR_CHAOS_ELASTIC_POST"] = str(post_steps)
    # rank 1's stuck lane: a persistent low-bit flip in a padded bucket
    # tail from attempt `flip_at` on — every downstream checksum
    # re-signs the corrupt bytes, only the cross-rank fingerprint vote
    # can see them
    env["DEAR_FAULTS"] = f"flip@{flip_at}:0:r1"
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "30")
    sup = sup_mod.ElasticSupervisor(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker", "--sdc",
         "--checkpoint-every", str(checkpoint_every),
         "--workdir", train_dir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=1,
    ).start()
    rc = sup.wait(deadline_s=420)

    _check(rc == 0, f"supervisor exits 0 (got {rc})", failures)
    _check(("sdc_quarantine", 1) in sup.events,
           "the convicted rank exited through the quarantine drain "
           f"(rc 75) ({sup.events})", failures)
    _check(("sdc_reseat", 1) in sup.events,
           "the supervisor re-seated rank 1 on a fresh host "
           "(quarantined host never re-seated)", failures)
    exit_path = os.path.join(train_dir, "sdc_exit_rank1.json")
    exit_rec = None
    if _check(os.path.exists(exit_path),
              "the quarantined incarnation wrote its forensics record",
              failures):
        with open(exit_path) as f:
            exit_rec = json.load(f)
    verdicts = {}
    for r in range(nprocs):
        path = os.path.join(train_dir, f"verdict_rank{r}.json")
        if not os.path.exists(path):
            failures.append(f"rank {r} wrote no verdict")
            continue
        with open(path) as f:
            verdicts[r] = json.load(f)
    summary = {"passed": False, "workdir": workdir, "verdicts": verdicts,
               "failures": failures}
    if len(verdicts) != nprocs or exit_rec is None:
        return summary

    bad_host = exit_rec["host"]
    flip_bucket = exit_rec["expected_flip_bucket"]
    ledger = SDC.ledger_from_dir(os.path.join(elastic_dir, "sdc"))
    events = ledger.events(bad_host)
    convictions = [e for e in events if e.get("kind") == "conviction"]
    _check(bool(convictions),
           f"the ledger convicted host {bad_host} ({events})", failures)
    if convictions:
        c = convictions[0]
        _check(c.get("rank") == 1,
               f"blame localized to the injected rank ({c})", failures)
        _check(flip_bucket is not None and c.get("bucket") == flip_bucket,
               f"the vote localized the flipped bucket (ledger "
               f"{c.get('bucket')}, flipped {flip_bucket})", failures)
    _check(exit_rec["counters"].get("faults.sdc_flips", 0) >= 2,
           "the flip fired on the original attempt AND the replay — the "
           "deterministic fault reproduced "
           f"({exit_rec['counters'].get('faults.sdc_flips', 0)} firings)",
           failures)
    # zero corrupted steps reachable from anything published: the
    # quarantined incarnation's newest persisted checkpoint predates the
    # first corrupt attempt (saves were fenced from the conviction on)
    _check(all(s < flip_at for s in exit_rec["ckpt_steps"]),
           f"no corrupt step was ever checkpointed "
           f"({exit_rec['ckpt_steps']} all < {flip_at})", failures)
    expect_restore = (flip_at - 1) - (flip_at - 1) % checkpoint_every
    _check(bool(exit_rec["rollback_steps"])
           and all(s == expect_restore
                   for s in exit_rec["rollback_steps"]),
           f"the replay re-ran from the last verified checkpoint "
           f"{expect_restore} ({exit_rec['rollback_steps']})", failures)
    _check(("sdc_probation", bad_host) in sup.events,
           f"the probation self-test launched for {bad_host}", failures)
    _check(("sdc_readmit", bad_host) in sup.events,
           f"host {bad_host} passed the known-answer self-test and was "
           "readmitted", failures)
    _check(not ledger.quarantined(bad_host),
           "the ledger shows the readmission", failures)
    for r, v in verdicts.items():
        _check(v["epoch"] == 2 and v["members"] == list(range(nprocs)),
               f"rank {r} ends at epoch 2, full membership "
               f"(epoch {v['epoch']}, members {v['members']})", failures)
        _check(v["lockstep"], f"rank {r} finished in lockstep", failures)
        _check(v["final_step"] >= expect_restore + post_steps
               and v["final_step"] == verdicts[0]["final_step"],
               f"rank {r} continued past quarantine + rejoin to step "
               f"{v['final_step']}", failures)
        # the backfilled seat restores through `reenter` (consensus
        # restore, not a guard rollback) so its list may be empty; every
        # rollback that DID happen must land on the verified checkpoint
        _check((bool(v["rollback_steps"]) or r == 1)
               and all(s == expect_restore for s in v["rollback_steps"]),
               f"rank {r} rollbacks all landed on the newest verified "
               f"checkpoint {expect_restore} ({v['rollback_steps']})",
               failures)
    survivors = [verdicts[r] for r in range(nprocs) if r != 1]
    for v in survivors:
        c = v["counters"]
        _check(c.get("sdc.votes", 0) >= 1
               and c.get("cluster.sdc_suspects_detected", 0) >= 1,
               f"rank {v['rank']} voted and detected the divergence "
               "within one check interval", failures)
        _check(v["sdc_convicted"] == [bad_host],
               f"rank {v['rank']} convicted exactly the injected host "
               f"({v['sdc_convicted']})", failures)
    # the ledger write is first-writer-wins: exactly ONE rank's
    # convict() lands (and counts) — and every rank races, including
    # the corrupt one (whose counters live in its rc-75 exit record,
    # not a survivor verdict). The fleet-wide total is what matters.
    fleet_counters = [v["counters"] for v in survivors]
    fleet_counters.append(exit_rec["counters"])
    _check(sum(c.get("sdc.convictions", 0)
               for c in fleet_counters) >= 1
           and sum(c.get("sdc.quarantines", 0)
                   for c in fleet_counters) >= 1,
           "the fleet recorded the conviction + quarantine", failures)
    rv = verdicts[1]
    _check(rv["rejoined"] and rv["resumed_at"] == expect_restore,
           f"the backfilled seat rejoined and resumed at the "
           f"fleet-agreed step ({rv['resumed_at']})", failures)
    _check(bool(rv["host"]) and rv["host"] != bad_host,
           f"the backfill landed on a FRESH host "
           f"({rv['host']} != {bad_host})", failures)
    _check(rv["counters"].get("sdc.votes", 0) >= 1,
           "the fingerprint exchange survived the shrink/rejoin epochs "
           "(the backfilled rank votes again)", failures)

    # -- leg 2: serving — shadow replay, arbiter, fence, held backfill ----
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.resilience.scale import ScalePolicy
    from dear_pytorch_tpu.serving.admission import (
        AdmissionController, SheddingError,
    )
    from dear_pytorch_tpu.serving.router import ReplicaRouter

    serve_root = os.path.join(workdir, "serve")
    os.makedirs(serve_root, exist_ok=True)
    serve_dir = os.path.join(serve_root, "fleet")
    store_dir = os.path.join(serve_root, "store")
    serve_elastic = os.path.join(serve_root, "elastic")
    capacity = os.path.join(serve_root, "capacity.json")
    write_capacity = CC.capacity_writer(capacity)
    write_capacity({"target_world": 3})

    env2 = dict(os.environ)
    env2.pop("DEAR_NUM_CPU_DEVICES", None)
    env2.pop("DEAR_TRACE_RANK", None)
    env2["PYTHONPATH"] = REPO + os.pathsep + env2.get("PYTHONPATH", "")
    env2["JAX_PLATFORMS"] = "cpu"
    env2["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env2["DEAR_TELEMETRY"] = "1"
    env2["DEAR_SDC"] = "1"
    env2["DEAR_SERVE_DIR"] = serve_dir
    env2["DEAR_SERVE_STORE"] = store_dir
    env2["DEAR_SERVE_SLOTS"] = "4"
    env2["DEAR_SERVE_DEADLINE"] = "600"
    env2["DEAR_SERVE_PREFILL_CHUNK"] = "4"
    # replica 1's stuck lane: token flips AFTER response signing from
    # its 3rd response on — the wire checksum verifies; only the shadow
    # replay's exact-token vote can see it
    env2["DEAR_FAULTS"] = "flip_logits@3:r1"

    pub = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--serve-publish", "--version", "1", "--workdir", serve_root],
        env=env2, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    _check(pub.returncode == 0,
           f"weight v1 published: {pub.stdout[-800:]}", failures)

    policy = ScalePolicy(capacity_file=capacity, hysteresis_s=0.5,
                         max_world=3)
    sup2 = sup_mod.ElasticSupervisor(
        3,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--serve-replica", "--workdir", serve_root],
        elastic_dir=serve_elastic, env=env2,
        max_relaunches=2, relaunch_window_s=120.0, policy=policy,
    ).start()

    ledger2 = SDC.ledger_from_dir(os.path.join(serve_elastic, "sdc"))
    sdc_hits: list[tuple] = []

    def on_sdc(rank, host):
        # the conviction callback is the operator hook: the stuck lane
        # stays with the quarantined HOST, so the relaunch env sheds the
        # fault (a backfill is a fresh/readmitted host), and the
        # quarantined seat drains for backfill
        sdc_hits.append((rank, host))
        sup2.base_env.pop("DEAR_FAULTS", None)
        write_capacity({"target_world": 3, "drain": [rank]})

    prev_tracer = T._tracer
    T.set_tracer(T.Tracer([T.MemoryExporter()]))
    admission = AdmissionController(max_depth=16)
    router = ReplicaRouter(serve_dir, admission=admission,
                           slots_per_replica=4, health_timeout_s=5.0,
                           shadow_every=1, sdc_ledger=ledger2,
                           on_sdc=on_sdc).start()
    fleet = CC.FleetPump([sup2], failures, deadline_s=300.0)
    pump = fleet.pump
    stop = threading.Event()
    client_failures: list[str] = []

    def client():
        i = 0
        while not stop.is_set():
            prompt = [(i * 7 + k) % 61 for k in range(4 + i % 3)]
            try:
                rid = router.submit(prompt, max_new_tokens=3,
                                    deadline_s=60.0)
            except SheddingError:
                time.sleep(0.1)
                continue
            try:
                router.result(rid, timeout=180.0)
            except TimeoutError:
                client_failures.append(f"serve req {i}: no response")
            i += 1
            time.sleep(0.05)

    th = threading.Thread(target=client, daemon=True)
    try:
        _check(pump(lambda: len(router.healthy_replicas()) >= 3,
                    "3 replicas healthy", 180.0),
               "serving fleet of 3 replicas is up", failures)
        th.start()
        _check(pump(lambda: router.sdc_convictions,
                    "shadow replay convicts", 180.0),
               "the shadow-replay arbiter convicted the corrupting "
               "replica", failures)
        convicted = list(router.sdc_convictions)
        _check(bool(convicted) and convicted[0][0] == 1,
               f"the conviction localized to the injected replica "
               f"({convicted})", failures)
        bad_serve_host = convicted[0][1] if convicted else ""
        evs2 = ledger2.events(bad_serve_host)
        _check(any(e.get("kind") == "conviction"
                   and e.get("source") == "serving_shadow"
                   for e in evs2),
               f"the serving conviction landed in the shared ledger "
               f"shape ({evs2})", failures)
        _check(1 not in router.healthy_replicas(),
               "the convicted replica is fenced from dispatch", failures)
        _check(pump(lambda: ("drained", 1) in sup2.events
                    or ("drained_dirty", 1) in sup2.events,
                    "quarantined replica drained", 120.0),
               "the quarantined seat drained for backfill", failures)

        def spawns_of_1():
            # every path that would re-seat rank 1: a policy scale-up
            # backfill or an exit-code relaunch
            return sum(1 for e in sup2.events
                       if e in (("scale_up", 1), ("relaunch", 1)))

        spawns_at_drain = spawns_of_1()
        _check(pump(lambda: ("sdc_readmit", bad_serve_host)
                    in sup2.events, "probation readmit", 120.0),
               f"the serving host {bad_serve_host} passed probation and "
               "was readmitted", failures)
        _check(spawns_of_1() == spawns_at_drain,
               "the quarantine capacity cap HELD the backfill until "
               "readmission (no re-seat while quarantined)", failures)
        _check(pump(lambda: spawns_of_1() > spawns_at_drain
                    and 1 in router.healthy_replicas(),
                    "backfill after readmit", 180.0),
               "the readmitted seat was backfilled and serves again",
               failures)
        before = len(router.completed)
        _check(pump(lambda: len(router.completed) > before,
                    "traffic after quarantine", 60.0),
               "responses completed after the conviction (continuous "
               "serving)", failures)
        stop.set()
        th.join(timeout=240)
        _check(pump(lambda: not router.open_requests(),
                    "all accepted requests answered", 120.0),
               "zero dropped requests across the conviction "
               f"(open={sorted(router.open_requests())})", failures)
        _check(not client_failures,
               f"no client timed out ({client_failures[:4]})", failures)
        stats = router.stats()
        _check(stats["shadow_replays"] >= 3
               and stats["shadow_verified"] >= 1,
               f"shadow replays ran and verified clean responses "
               f"(replays={stats['shadow_replays']}, "
               f"verified={stats['shadow_verified']})", failures)
        _check(stats["shadow_mismatches"] >= 1,
               "the post-signing corruption was caught by the "
               "exact-token vote "
               f"(mismatches={stats['shadow_mismatches']})", failures)
    finally:
        stop.set()
        sup2.policy = None  # shutdown must not be 'lost capacity'
        sup2.kill_all(signal.SIGTERM)  # drain path: clean exits
        t_end = time.monotonic() + 60.0
        while sup2.poll() and time.monotonic() < t_end:
            time.sleep(0.1)
        if sup2._procs:
            sup2.kill_all(signal.SIGKILL)
        serve_stats = router.stats()
        router.close()
        counters2 = T.get_tracer().counters()
        T.set_tracer(prev_tracer)

    summary.update({
        "passed": not failures,
        "failures": failures,
        "bad_train_host": bad_host,
        "sdc_hits": sdc_hits,
        "serve_stats": {k: serve_stats.get(k) for k in (
            "completed", "shadow_replays", "shadow_verified",
            "shadow_mismatches", "shadow_skipped", "sdc_convictions")},
        "sdc_counters": {k: v for k, v in sorted(counters2.items())
                         if k.startswith("sdc.")},
    })
    return summary


def _load_harness():
    import importlib.util

    eh_spec = importlib.util.spec_from_file_location(
        "dear_elastic_harness",
        os.path.join(REPO, "tests", "elastic_harness.py"))
    EH = importlib.util.module_from_spec(eh_spec)
    eh_spec.loader.exec_module(EH)
    return EH


def _newest_remote_store(remote_root: str, *, skip_rank=None):
    """The replica store holding the newest committed upload (states are
    replica-identical across ranks, so any store hydrates any rank)."""
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    best, best_step = None, -1
    try:
        names = sorted(os.listdir(remote_root))
    except OSError:
        return None, None
    for name in names:
        if skip_rank is not None and name == f"rank{skip_rank}":
            continue
        store = LocalObjectStore(os.path.join(remote_root, name))
        steps = ckpt.remote_steps(store)
        if steps and steps[0] > best_step:
            best, best_step = store, steps[0]
    return best, (best_step if best is not None else None)


def run_worker_autoscale(checkpoint_every: int, workdir: str) -> dict:
    """One rank of the AUTOSCALE storm (spawned — possibly mid-run, as a
    scale-up or backfill — by `launch/supervisor.py` under the rejoin env
    contract). Mirrors `run_worker_elastic` plus the continuous-training
    service pieces: a `PreemptionHandler` with the spot grace window (a
    policy drain SIGTERM becomes an emergency save + planned shrink), a
    `CheckpointStreamer` uploading every committed checkpoint to this
    rank's object store, and — for a scale-from-zero spawn with no local
    checkpoints — hydration from a fleet replica's remote tier before the
    consensus restore. The loop runs until membership epoch
    ``DEAR_CHAOS_AUTO_EPOCHS`` commits, plus a lockstep runout."""
    import json

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ["DEAR_CKPT_SHARED"] = "0"
    import jax

    jax.config.update("jax_num_cpu_devices", 4)

    import numpy as np

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience import PreemptionHandler
    from dear_pytorch_tpu.resilience import membership as M
    from dear_pytorch_tpu.runtime import build as RB
    from dear_pytorch_tpu.runtime import pipeline as P
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    EH = _load_harness()
    cluster = M.ElasticCluster.from_env(max_candidates=256)
    rejoining = M.ElasticCluster.rejoining_by_env()
    rank = cluster.rank
    kr, ke, kx = os.environ["DEAR_CHAOS_AUTO_KILL"].split(":")
    kill = (int(kr), int(ke), int(kx))
    target_epoch = int(os.environ.get("DEAR_CHAOS_AUTO_EPOCHS", "5"))
    post = int(os.environ.get("DEAR_CHAOS_AUTO_POST", "3"))
    remote_root = os.environ["DEAR_CHAOS_REMOTE"]
    ckpt_dir = os.path.join(workdir, f"rank{rank}", "ckpts")
    tracer = T.get_tracer()

    params = _mlp_params(jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:min(cluster.world, 3)]), ("dp",))
    tuner = AutoTuner(
        _loss_fn, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
    )
    # batch rows divide every world this storm visits (2 and 3)
    spec = P.SyntheticSpec((
        P.Field("x", (12, 12), RB.KIND_NORMAL_F32, 0.0, 1.0),
    ))
    pipe = P.NumpyPipeline(spec, seed=123, shard=cluster.index,
                           num_shards=cluster.world)
    store = LocalObjectStore(os.path.join(remote_root, f"rank{rank}"))
    streamer = ckpt.CheckpointStreamer(
        ckpt_dir, store, upload_every=1, pin_last=4)
    pre = PreemptionHandler().install()
    guard = GuardedTrainer(
        tuner.ts, ckpt_dir, params,
        check_every=1, checkpoint_every=checkpoint_every, max_keep=1000,
        max_recoveries=8, coordinator=cluster, pipeline=pipe,
        preemption=pre, streamer=streamer,
    )
    EH.attach_elastic(guard, tuner)
    rollback_steps = []
    guard.on_rollback = lambda c, at: rollback_steps.append(at)

    resumed_at = last_epoch = None
    if rejoining:
        hydrate, _ = _newest_remote_store(remote_root, skip_rank=rank)
        state, resumed_at, last_epoch = EH.reenter(
            cluster, tuner, guard, ckpt_dir, hydrate_store=hydrate)
    else:
        state = tuner.init(params)

    state, m = EH.run_autoscale_loop(
        cluster, guard, pipe, state,
        lambda i: _data(jax.random.PRNGKey(100 + i), n=12),
        rejoining=rejoining, target_epoch=target_epoch, post=post,
        kill=kill)
    drained = bool(m.get("preempted"))
    streamer.flush(20.0)
    streamer.close()
    counters = tracer.counters()
    verdict = {
        "rank": rank,
        "pid": os.getpid(),
        "rejoined": bool(rejoining),
        "scale_up_join": bool(cluster.joining),
        "drained": drained,
        "grace_remaining": pre.remaining(),
        "epoch": cluster.epoch,
        "members": list(cluster.members),
        "resumed_at": resumed_at,
        "rollback_steps": rollback_steps,
        "final_step": int(jax.device_get(state.step)),
        "final_loss": float(m.get("loss", float("nan"))),
        "steps_seen": guard.steps_seen,
        "plan_world": guard.ts.plan.world,
        "plan_epoch": guard.ts.plan.epoch,
        "pipe_shard": [pipe.shard, pipe.num_shards],
        "uploaded": sorted(streamer.uploaded),
        "upload_failed": sorted(streamer.failed),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("cluster.", "guard.", "pipeline.",
                                      "autotune.", "ckpt."))},
    }
    if not drained:
        # the lockstep verdict is itself a member-scoped collective; a
        # drained rank exits OUTSIDE the lockstep and skips it
        views = cluster.exchange("chaos.verdict", json.dumps(
            [verdict["final_step"], round(verdict["final_loss"], 9),
             verdict["epoch"]]))
        verdict["lockstep"] = all(
            json.loads(v) == json.loads(views[0]) for v in views)
    path = os.path.join(workdir, f"verdict_rank{rank}.{os.getpid()}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(verdict, f)
    os.replace(path + ".tmp", path)
    print(f"CHAOS_AUTO rank={rank} " + json.dumps(verdict), flush=True)
    return verdict


def run_cold_start(workdir: str) -> dict:
    """Scale-from-zero restore gate: on a machine with NO local
    checkpoints, restore from the remote tier alone (sha256-reverified
    download), land exactly on the newest uploaded step, and train one
    live step on the restored state."""
    import json

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    import jax

    jax.config.update("jax_num_cpu_devices", 4)

    import numpy as np

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience.membership import MembershipView
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt

    failures: list[str] = []
    remote_root = os.environ["DEAR_CHAOS_REMOTE"]
    store, newest = _newest_remote_store(remote_root)
    _check(store is not None, "a remote tier with uploads exists", failures)
    local = os.path.join(workdir, "cold", "ckpts")
    step = ckpt.restore_from_object_store(store, local)
    _check(step == newest,
           f"cold start restored the NEWEST uploaded step ({newest}); "
           f"got {step}", failures)
    _check(step is not None and ckpt.verify_checkpoint(local, step),
           "downloaded checkpoint passes local checksum verification",
           failures)
    meta = ckpt.read_sidecar(local, step) or {}
    desc = meta.get("plan_desc") or {}
    world = int(desc.get("world", 1))
    epoch = int(desc.get("epoch", 0))
    _check(ckpt.read_pipeline_state(local, step) is not None,
           "the remote sidecar carries the pipeline position", failures)

    params = _mlp_params(jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:world]), ("dp",))
    tuner = AutoTuner(
        _loss_fn, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
    )
    if epoch:
        tuner.rescale(MembershipView(
            epoch=epoch, members=tuple(range(world)), rank=0, index=0,
            world=world))
    try:
        state = ckpt.restore_checkpoint(local, tuner.ts, step=step,
                                        template=tuner.init(params))
    except ckpt.PlanMismatchError:
        state = ckpt.elastic_restore(local, tuner.ts, step=step)
    _check(int(jax.device_get(state.step)) == step,
           "restored state sits exactly at the uploaded step "
           "(zero loss of progress past the remote tier)", failures)
    state, m = tuner.step(state, _data(jax.random.PRNGKey(999), n=12))
    _check(np.isfinite(float(m["loss"])),
           "cold-started state trains a live step", failures)
    counters = T.get_tracer().counters()
    verdict = {
        "passed": not failures,
        "restored_step": step,
        "newest_uploaded": newest,
        "plan_world": world,
        "plan_epoch": epoch,
        "remote_restores": counters.get("ckpt.remote_restores", 0),
        "failures": failures,
    }
    path = os.path.join(workdir, "cold_verdict.json")
    with open(path + ".tmp", "w") as f:
        json.dump(verdict, f)
    os.replace(path + ".tmp", path)
    print("CHAOS_COLD " + json.dumps(verdict), flush=True)
    return verdict


def run_autoscale(checkpoint_every: int, workdir: str | None) -> dict:
    """Parent of the autoscale storm — the continuous-training-service
    acceptance gate. A 2-rank supervised fleet:

      1. streams checkpoints to its per-rank object stores, then receives
         a capacity-UP hint (watched capacity file -> `ScalePolicy`) —
         the supervisor spawns a brand-new rank 2 and the fleet commits a
         scale-UP epoch (e1, signed +[2] in the decision record);
      2. rank 1 is SIGKILLed (abrupt loss -> e2 shrink), relaunched by
         the sliding-window budget, and readmitted (e3);
      3. the capacity file drains rank 0 (spot-style SIGTERM): planned
         shrink inside the preemption grace window (e4), then the policy
         backfills it while capacity still wants world 3 (e5);
      4. the fleet finishes in lockstep at epoch 5; the gate then
         machine-checks the steps-per-hour SLO through
         `scripts/bench_gate.py --slo`, asserts zero loss of progress
         past the newest uploaded checkpoint, and spawns a scale-from-
         zero cold-start worker that restores from the remote tier alone.

    The parent stays jax-free: it watches the durable decision records
    (`{ns}/decided/e*` — the signed world-delta commits) to sequence its
    phases, exactly as an external operator would."""
    import subprocess
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_auto_")
    elastic_dir = os.path.join(workdir, "elastic")
    remote_root = os.path.join(workdir, "remote")
    os.makedirs(remote_root, exist_ok=True)
    capacity = os.path.join(workdir, "capacity.json")
    write_capacity = CC.capacity_writer(capacity)
    write_capacity({"target_world": 2})

    sup_mod = CC.load_supervisor()
    from dear_pytorch_tpu.resilience.scale import ScalePolicy

    kill_rank, drain_rank, target_epoch, post = 1, 0, 5, 3
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_FLIGHT"] = "8"
    env["DEAR_CHAOS_AUTO_KILL"] = f"{kill_rank}:1:2"  # after the scale-up
    env["DEAR_CHAOS_AUTO_EPOCHS"] = str(target_epoch)
    env["DEAR_CHAOS_AUTO_POST"] = str(post)
    env["DEAR_CHAOS_REMOTE"] = remote_root
    env["DEAR_PREEMPT_GRACE_S"] = "30"
    # a peer's post-transition XLA recompile must not read as a death
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "30")
    policy = ScalePolicy(capacity_file=capacity, hysteresis_s=0.5,
                         max_world=3)
    sup = sup_mod.ElasticSupervisor(
        2,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--autoscale", "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=2, relaunch_window_s=120.0, policy=policy,
    ).start()

    decided = CC.decided_reader(elastic_dir)
    phase = [0]

    def _phases():
        if (phase[0] == 0
                and _newest_remote_store(remote_root)[0] is not None):
            # the fleet is streaming checkpoints: capacity-UP hint
            write_capacity({"target_world": 3})
            phase[0] = 1
        elif phase[0] == 1 and decided(3) is not None:
            # scale-up (e1), SIGKILL shrink (e2), and rejoin (e3) all
            # committed: now the spot-style drain of rank 0
            write_capacity({"target_world": 3, "drain": [drain_rank]})
            phase[0] = 2

    rc, elapsed_s = CC.run_fleet(sup, deadline_s=420.0, on_poll=_phases)

    failures: list[str] = []
    _check(rc == 0, f"supervisor fleet exits clean (got rc={rc})", failures)
    _check(sup.relaunches.get(kill_rank) == 1,
           f"the SIGKILLed rank was relaunched once within its window "
           f"budget ({sup.relaunches})", failures)
    kinds = [d.kind for d in policy.decisions]
    _check(kinds.count("scale_up") >= 2 and "drain" in kinds,
           f"policy decided capacity-up, drain, and backfill ({kinds})",
           failures)
    _check(("drained", drain_rank) in sup.events,
           f"rank {drain_rank} drained CLEANLY on SIGTERM "
           f"(events {sup.events})", failures)

    # the signed world-delta decision records tell the capacity story
    expect_delta = {
        1: {"added": [2], "removed": []},
        2: {"added": [], "removed": [kill_rank]},
        3: {"added": [kill_rank], "removed": []},
        4: {"added": [], "removed": [drain_rank]},
        5: {"added": [drain_rank], "removed": []},
    }
    for e, want in expect_delta.items():
        rec = decided(e)
        _check(isinstance(rec, dict) and rec.get("delta") == want,
               f"decision record e{e} carries the signed delta {want} "
               f"(got {rec})", failures)
    rec5 = decided(5)
    _check(isinstance(rec5, dict) and rec5.get("members") == [0, 1, 2],
           f"epoch-5 record commits the full world ({rec5})", failures)

    # newest verdict per rank (churned ranks write one per life)
    lives, finals = CC.collect_verdicts(workdir)
    summary = {"passed": False, "workdir": workdir, "rc": rc,
               "elapsed_s": round(elapsed_s, 1),
               "policy_decisions": kinds, "finals": finals,
               "failures": failures}
    if sorted(finals) != [0, 1, 2]:
        failures.append(f"expected final verdicts from ranks 0-2, got "
                        f"{sorted(finals)}")
        return summary

    for r, v in sorted(finals.items()):
        _check(v["epoch"] == target_epoch
               and v["members"] == [0, 1, 2],
               f"rank {r} ends at epoch {target_epoch}, full membership "
               f"(epoch {v['epoch']}, members {v['members']})", failures)
        _check(v.get("lockstep"), f"rank {r} finished in lockstep",
               failures)
        _check(v["plan_world"] == 3 and v["plan_epoch"] == target_epoch,
               f"rank {r} trains the rescaled epoch-stamped plan "
               f"(world {v['plan_world']}, epoch {v['plan_epoch']})",
               failures)
        _check(v["pipe_shard"][1] == 3,
               f"rank {r} pipeline resharded over the full membership",
               failures)
        _check(bool(v["uploaded"]) and not v["upload_failed"],
               f"rank {r} streamed checkpoints to its remote tier "
               f"({v['uploaded']}, failed {v['upload_failed']})", failures)
    # the scale-up admission is visible in the first-life counters of the
    # original members, and as cluster.scale_ups on at least one of them
    merged: dict = {}
    for vs in lives.values():
        for v in vs:
            for k, n in v.get("counters", {}).items():
                merged[k] = merged.get(k, 0) + n
    _check(merged.get("cluster.scale_ups", 0) >= 1,
           f"a scale-UP admission was counted (cluster.scale_ups="
           f"{merged.get('cluster.scale_ups', 0)})", failures)
    _check(merged.get("cluster.reconfigs", 0) >= 2,
           "both shrinks (SIGKILL + planned drain) committed", failures)
    _check(merged.get("cluster.rejoins", 0) >= 3,
           "scale-up, relaunch, and backfill admissions all counted",
           failures)
    _check(merged.get("ckpt.uploads", 0) >= 3,
           f"checkpoint streaming uploaded throughout "
           f"(ckpt.uploads={merged.get('ckpt.uploads', 0)})", failures)
    fresh_life = [v for vs in lives.values() for v in vs
                  if v.get("scale_up_join")]
    _check(bool(fresh_life),
           "the brand-new rank hydrated from the remote tier and joined "
           "with no sidecar epoch", failures)
    drained_life = [v for vs in lives.values() for v in vs
                    if v.get("drained")]
    _check(len(drained_life) == 1
           and drained_life[0]["rank"] == drain_rank
           and (drained_life[0]["grace_remaining"] or 0) > 0,
           "exactly the drained rank exited via the planned-shrink path "
           "inside its grace window", failures)

    # zero loss of progress past the newest uploaded checkpoint
    _, newest_uploaded = _newest_remote_store(remote_root)
    final_step = finals[0]["final_step"]
    _check(newest_uploaded is not None
           and final_step >= newest_uploaded,
           f"final step {final_step} >= newest uploaded checkpoint "
           f"{newest_uploaded} (zero loss past the remote tier)", failures)

    # the machine-checked service contract: steps/hour despite churn,
    # through the bench gate's absolute SLO floor
    slo_floor = float(os.environ.get("DEAR_CHAOS_SLO_STEPS_PER_HOUR", "50"))
    steps_per_hour = final_step * 3600.0 / max(elapsed_s, 1e-9)
    CC.slo_gate(
        os.path.join(workdir, "autoscale_contract.json"),
        "steps_per_hour", round(steps_per_hour, 2),
        [{"metric": "final_step", "value": final_step},
         {"metric": "ckpt_uploads", "value": merged.get("ckpt.uploads", 0)}],
        [f"steps_per_hour={slo_floor}"], failures,
        f"bench_gate --slo holds the steps/hour contract "
        f"({steps_per_hour:.0f}/h vs floor {slo_floor:.0f}/h)")

    # scale-from-zero: a machine with NO local state restores from the
    # remote tier alone
    cold = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--cold-start", "--workdir", workdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120,
    )
    _check(cold.returncode == 0,
           f"cold-start worker exits 0: {cold.stdout[-1500:]}", failures)
    cold_verdict = {}
    try:
        with open(os.path.join(workdir, "cold_verdict.json")) as f:
            cold_verdict = json.load(f)
    except (OSError, ValueError):
        failures.append("cold-start worker wrote no verdict")
    _check(bool(cold_verdict.get("passed")),
           f"cold-start restore from the remote tier alone "
           f"({cold_verdict.get('failures')})", failures)

    summary.update({
        "passed": not failures,
        "steps_per_hour": round(steps_per_hour, 2),
        "newest_uploaded": newest_uploaded,
        "cold": cold_verdict,
        "merged_counters": {k: v for k, v in sorted(merged.items())
                            if k.startswith(("cluster.", "ckpt."))},
        "failures": failures,
    })
    return summary


# -- the multi-slice storm -----------------------------------------------------


def run_worker_multislice(checkpoint_every: int, workdir: str) -> dict:
    """One rank of the MULTISLICE storm: a 2-slice x 4-rank fleet where
    every rank trains the HIERARCHICAL schedule — per-bucket RS+AG over
    its local 2-device ICI mesh inside the jitted step, cross-slice
    gradient averaging over the shared `FileTransport` DCN exchanger
    between the backward and update programs (`comm.dcn`). The four
    ranks of a slice are lockstep replicas of that slice's data shard;
    membership is SLICE-granular (``DEAR_ELASTIC_RANKS_PER_SLICE``, the
    supervisor contract). The scheduled victim slice SIGKILLs all its
    ranks at one attempt; survivors must commit exactly ONE shrink
    epoch, renormalize the DCN leg, and train degraded; the relaunched
    slice hydrates from the remote tier and readmits as one epoch at
    the barrier. A slice-targeted ``dcn_slow`` fault turns the
    surviving slice into a straggler the fleet must tolerate.

    ``DEAR_CHAOS_MULTI_MODE`` selects the storm's fault story:

    * ``kill`` (default) — the SIGKILL narrative above;
    * ``flap`` — NO kill: a fixed-step degraded-mode run
      (``DEAR_CHAOS_MULTI_STEPS``) under a sub-budget ``dcn_flap``
      transient, where the ladder's skip-don't-stall rung must absorb
      every dropped exchange without a single guard rollback;
    * ``partition`` — NO SIGKILL either: a past-budget
      ``dcn_partition`` starves the victim slice until its own
      bounded-staleness clock trips ``DcnSelfEvict`` — the process
      exits 70, the supervisor relaunches it with the rejoin flag, and
      the relaunched life STRIPS the one-shot dcn_flap/dcn_partition
      faults from ``DEAR_FAULTS`` so the armed outage does not re-fire
      on the rejoined slice."""
    import json

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ["DEAR_CKPT_SHARED"] = "0"
    import jax

    jax.config.update("jax_num_cpu_devices", 2)

    import numpy as np

    from dear_pytorch_tpu.comm.dcn import DcnExchanger, DcnSelfEvict
    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience import membership as M
    from dear_pytorch_tpu.resilience.cluster import FileTransport
    from dear_pytorch_tpu.resilience.inject import (
        FaultInjector, parse_faults,
    )
    from dear_pytorch_tpu.runtime import build as RB
    from dear_pytorch_tpu.runtime import pipeline as P
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    EH = _load_harness()
    cluster = M.ElasticCluster.from_env(max_candidates=256)
    rejoining = M.ElasticCluster.rejoining_by_env()
    rank, my_slice = cluster.rank, cluster.slice_of(cluster.rank)
    mode = os.environ.get("DEAR_CHAOS_MULTI_MODE", "kill")
    if mode == "kill":
        ks, ka = os.environ["DEAR_CHAOS_MULTI_KILL"].split(":")
        kill_slice, kill_at = int(ks), int(ka)
    else:
        kill_slice, kill_at = -1, 1
    target_epoch = int(os.environ.get("DEAR_CHAOS_MULTI_EPOCHS", "2"))
    post = int(os.environ.get("DEAR_CHAOS_MULTI_POST", "3"))
    remote_root = os.environ["DEAR_CHAOS_REMOTE"]
    ckpt_dir = os.path.join(workdir, f"rank{rank}", "ckpts")
    tracer = T.get_tracer()

    faults_spec = os.environ.get("DEAR_FAULTS", "").strip()
    if rejoining and faults_spec:
        # a relaunched life must not re-arm the one-shot outage that
        # evicted it — a fresh injector would fire dcn_flap/dcn_partition
        # again at ITS exchange N and thrash the rejoined slice forever
        faults_spec = ",".join(
            f for f in faults_spec.split(",")
            if f.split("@", 1)[0] not in ("dcn_flap", "dcn_partition"))
    injector = None
    if faults_spec:
        injector = FaultInjector(
            parse_faults(faults_spec),
            own_rank=rank, own_slice=my_slice)
    # a rejoiner's exchanger starts at the INITIAL view; admission hands
    # it the committed slice set through AutoTuner.rescale (reenter)
    dcn = DcnExchanger(
        FileTransport(os.path.join(workdir, "dcn")),
        local_slices=(my_slice,), slices=cluster.slices,
        partition_mb=0.0005, injector=injector)

    params = _mlp_params(jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2), ("slice", "ici"))
    tuner = AutoTuner(
        _loss_fn, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
        axis_name="ici", dcn=dcn, dcn_slice_axis="slice",
    )
    view0 = cluster.view()
    spec = P.SyntheticSpec((
        P.Field("x", (8, 12), RB.KIND_NORMAL_F32, 0.0, 1.0),
    ))
    pipe = P.NumpyPipeline(spec, seed=123, shard=view0.data_shard,
                           num_shards=view0.data_world)
    store = LocalObjectStore(os.path.join(remote_root, f"rank{rank}"))
    streamer = ckpt.CheckpointStreamer(
        ckpt_dir, store, upload_every=1, pin_last=4)
    guard = GuardedTrainer(
        tuner.ts, ckpt_dir, params,
        check_every=1, checkpoint_every=checkpoint_every, max_keep=1000,
        max_recoveries=12, coordinator=cluster, pipeline=pipe,
        streamer=streamer,
    )
    base_hook = EH.attach_elastic(guard, tuner)
    transitions = []

    def on_change(view):
        base_hook(view)
        transitions.append({"epoch": view.epoch,
                            "slices": list(view.slices),
                            "steps_seen": guard.steps_seen})
    guard.on_membership_change = on_change
    rollback_steps = []
    guard.on_rollback = lambda c, at: rollback_steps.append(at)

    def batch_at(i):
        # the GLOBAL batch, deterministically sliced to the CURRENT live
        # slice set: this slice's rows shard over its ICI axis, and
        # degraded mode (one live slice) trains the full batch
        x, y = _data(jax.random.PRNGKey(100 + i), n=8)
        view = cluster.view()
        per = 8 // max(view.data_world, 1)
        k = view.data_shard
        return (x[k * per:(k + 1) * per], y[k * per:(k + 1) * per])

    resumed_at = last_epoch = None
    if rejoining:
        hydrate, _ = _newest_remote_store(remote_root, skip_rank=rank)
        state, resumed_at, last_epoch = EH.reenter(
            cluster, tuner, guard, ckpt_dir, hydrate_store=hydrate)
    else:
        state = tuner.init(params)

    if mode == "flap":
        # fixed-step degraded-mode run: NO membership churn expected —
        # the sub-budget transient must be absorbed entirely by the
        # ladder's skip rung, with zero guard rollbacks
        steps = int(os.environ.get("DEAR_CHAOS_MULTI_STEPS", "12"))
        m = {}
        while guard.steps_seen < steps:
            i = guard.steps_seen
            pipe.next()
            state, m = guard.step(state, batch_at(i))
    else:
        kill = ((rank, 0, kill_at - 1) if my_slice == kill_slice
                else (-1, 0, 0))
        try:
            state, m = EH.run_autoscale_loop(
                cluster, guard, pipe, state, batch_at,
                rejoining=rejoining, target_epoch=target_epoch, post=post,
                kill=kill, deadline_s=420.0)
        except DcnSelfEvict as exc:
            # rung 3, local side: the bounded-staleness clock says WE are
            # the partitioned slice. Flush what we have, leave a durable
            # marker for the parent gate, and exit nonzero so the
            # supervisor relaunches this rank through slice-gated rejoin.
            streamer.flush(20.0)
            streamer.close()
            doc = {"rank": rank, "slice": my_slice, "pid": os.getpid(),
                   "steps_seen": guard.steps_seen, "reason": str(exc)}
            path = os.path.join(
                workdir, f"selfevict_rank{rank}.{os.getpid()}.json")
            with open(path + ".tmp", "w") as f:
                json.dump(doc, f)
            os.replace(path + ".tmp", path)
            print(f"CHAOS_MULTI rank={rank} SELF-EVICT "
                  + json.dumps(doc), flush=True)
            raise SystemExit(70)
    streamer.flush(20.0)
    streamer.close()
    counters = tracer.counters()
    verdict = {
        "rank": rank,
        "slice": my_slice,
        "pid": os.getpid(),
        "rejoined": bool(rejoining),
        "epoch": cluster.epoch,
        "members": list(cluster.members),
        "slices": list(cluster.slices),
        "transitions": transitions,
        "resumed_at": resumed_at,
        "last_epoch": last_epoch,
        "rollback_steps": rollback_steps,
        "final_step": int(jax.device_get(state.step)),
        "final_loss": float(m.get("loss", float("nan"))),
        "steps_seen": guard.steps_seen,
        "plan_world": guard.ts.plan.world,
        "plan_epoch": guard.ts.plan.epoch,
        "pipe_shard": [pipe.shard, pipe.num_shards],
        "dcn_slices": list(dcn.slices),
        "dcn_samples": len(dcn.samples()),
        "uploaded": sorted(streamer.uploaded),
        "upload_failed": sorted(streamer.failed),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("cluster.", "guard.", "pipeline.",
                                      "autotune.", "ckpt.", "dcn.",
                                      "faults."))},
    }
    # the lockstep verdict is itself a member-scoped collective
    views = cluster.exchange("chaos.verdict", json.dumps(
        [verdict["final_step"], round(verdict["final_loss"], 9),
         verdict["epoch"], verdict["slices"]]))
    verdict["lockstep"] = all(
        json.loads(v) == json.loads(views[0]) for v in views)
    path = os.path.join(workdir, f"verdict_rank{rank}.{os.getpid()}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(verdict, f)
    os.replace(path + ".tmp", path)
    print(f"CHAOS_MULTI rank={rank} " + json.dumps(verdict), flush=True)
    return verdict


def run_multislice(checkpoint_every: int, workdir: str | None) -> dict:
    """Parent of the multislice storm — the hierarchical-training
    acceptance gate (ROADMAP item 2, robustness half). A 2-slice x
    4-rank supervised fleet trains the two-level RS+AG(ICI) + DCN
    schedule while streaming checkpoints to per-rank object stores;
    then:

      1. the whole of slice 1 is SIGKILLed at one attempt — the gate
         asserts it commits as exactly ONE membership epoch (e1, signed
         slice-shaped delta ``slices.removed == [1]``), never as 4
         rank-death events;
      2. the surviving slice renormalizes the cross-slice leg
         (``dcn.renorms``) and keeps training DEGRADED — steps must
         advance between the shrink and the rejoin — while a
         slice-targeted ``dcn_slow`` fault makes it a straggler;
      3. the supervisor's per-rank relaunches come back through the
         SLICE-GATED admission: all four ranks readmit as ONE epoch
         (e2, ``slices.added == [1]``) at the barrier, hydrated from
         the remote tier;
      4. the fleet finishes in lockstep at full membership with zero
         loss of progress past the newest uploaded checkpoint.

    The parent stays jax-free and sequences off the durable decision
    records, exactly as an external slice-pool operator would."""
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_multi_")
    elastic_dir = os.path.join(workdir, "elastic")
    remote_root = os.path.join(workdir, "remote")
    os.makedirs(remote_root, exist_ok=True)
    sup_mod = CC.load_supervisor()

    nslices, rps = 2, 4
    nprocs = nslices * rps
    kill_slice, kill_at, target_epoch, post = 1, 5, 2, 3
    victims = list(range(kill_slice * rps, (kill_slice + 1) * rps))
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_CHAOS_MULTI_KILL"] = f"{kill_slice}:{kill_at}"
    env["DEAR_CHAOS_MULTI_EPOCHS"] = str(target_epoch)
    env["DEAR_CHAOS_MULTI_POST"] = str(post)
    env["DEAR_CHAOS_REMOTE"] = remote_root
    # the straggler-slice fault: slice 0 (the SURVIVOR) gets a armed
    # 30ms DCN latency from its 6th exchange on — degraded-mode and
    # post-rejoin training must absorb it
    env["DEAR_FAULTS"] = "dcn_slow@6:0.03:s0"
    # a dead slice must fail the step (and hand recovery to membership)
    # well before the health sync deadline would expire
    env["DEAR_DCN_TIMEOUT_SECS"] = "20"
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "45")
    sup = sup_mod.ElasticSupervisor(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--multislice", "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=1, relaunch_window_s=300.0,
        ranks_per_slice=rps,
    ).start()

    decided = CC.decided_reader(elastic_dir)
    rc, elapsed_s = CC.run_fleet(sup, deadline_s=540.0)

    failures: list[str] = []
    _check(rc == 0, f"supervisor fleet exits clean (got rc={rc})",
           failures)
    _check(all(sup.relaunches.get(r, 0) == 1 for r in victims)
           and all(sup.relaunches.get(r, 0) == 0 for r in range(rps)),
           f"exactly the killed slice's ranks were relaunched "
           f"({sup.relaunches})", failures)

    # the slice-shaped signed deltas ARE the capacity story: one shrink
    # epoch for the whole slice loss, one admission epoch for the whole
    # slice rejoin, nothing else
    rec1, rec2, rec3 = decided(1), decided(2), decided(3)
    _check(isinstance(rec1, dict)
           and rec1.get("delta", {}).get("removed") == victims
           and rec1.get("delta", {}).get("slices")
           == {"added": [], "removed": [kill_slice]},
           f"e1 commits the WHOLE slice loss as one membership event "
           f"(got {rec1})", failures)
    _check(isinstance(rec2, dict)
           and rec2.get("delta", {}).get("added") == victims
           and rec2.get("delta", {}).get("slices")
           == {"added": [kill_slice], "removed": []}
           and rec2.get("members") == list(range(nprocs)),
           f"e2 readmits the relaunched slice as one epoch at full "
           f"membership (got {rec2})", failures)
    _check(rec3 is None,
           f"no spurious membership epochs past e{target_epoch} "
           f"(e3 = {rec3})", failures)

    # newest verdict per rank (the killed slice writes one per life)
    _lives, finals = CC.collect_verdicts(workdir)
    summary = {"passed": False, "workdir": workdir, "rc": rc,
               "elapsed_s": round(elapsed_s, 1), "finals": finals,
               "failures": failures}
    if sorted(finals) != list(range(nprocs)):
        failures.append(f"expected final verdicts from ranks 0-"
                        f"{nprocs - 1}, got {sorted(finals)}")
        return summary

    expect_restore = (kill_at - 1) - (kill_at - 1) % checkpoint_every
    for r, v in sorted(finals.items()):
        _check(v["epoch"] == target_epoch
               and v["members"] == list(range(nprocs))
               and v["slices"] == [0, 1],
               f"rank {r} ends at epoch {target_epoch}, both slices "
               f"live (epoch {v['epoch']}, slices {v['slices']})",
               failures)
        _check(v.get("lockstep"), f"rank {r} finished in lockstep",
               failures)
        _check(v["plan_world"] == 2 and v["plan_epoch"] == target_epoch,
               f"rank {r}'s plan keeps the FIXED intra-slice world and "
               f"the committed epoch (world {v['plan_world']}, epoch "
               f"{v['plan_epoch']})", failures)
        _check(v["pipe_shard"][1] == nslices
               and v["pipe_shard"][0] == v["slice"],
               f"rank {r} pipeline sharded at SLICE granularity "
               f"({v['pipe_shard']})", failures)
        _check(v["dcn_slices"] == [0, 1],
               f"rank {r}'s DCN leg ends renormalized to both slices "
               f"({v['dcn_slices']})", failures)
        _check(v["counters"].get("dcn.exchanges", 0) > 0,
               f"rank {r} exchanged gradients over the DCN leg",
               failures)
        _check(bool(v["uploaded"]) and not v["upload_failed"],
               f"rank {r} streamed checkpoints to its remote tier "
               f"({v['uploaded']}, failed {v['upload_failed']})",
               failures)
    survivors = [v for r, v in finals.items() if r not in victims]
    for v in survivors:
        c = v["counters"]
        _check(c.get("cluster.slice_losses", 0) == 1
               and c.get("cluster.slice_rejoins", 0) == 1
               and c.get("cluster.reconfigs", 0) == 1,
               f"rank {v['rank']} saw exactly one slice loss and one "
               f"slice rejoin ({c})", failures)
        _check(c.get("dcn.renorms", 0) >= 2,
               f"rank {v['rank']} renormalized the DCN leg at both "
               f"transitions (dcn.renorms={c.get('dcn.renorms', 0)})",
               failures)
        _check(bool(v["rollback_steps"])
               and min(v["rollback_steps"]) >= expect_restore,
               f"rank {v['rank']} rollbacks never went past the newest "
               f"common checkpoint {expect_restore} "
               f"({v['rollback_steps']})", failures)
        shrink = [t for t in v["transitions"]
                  if t["slices"] == [1 - kill_slice]]
        rejoin = [t for t in v["transitions"] if t["slices"] == [0, 1]]
        _check(bool(shrink) and bool(rejoin)
               and rejoin[0]["steps_seen"] > shrink[0]["steps_seen"],
               f"rank {v['rank']} trained DEGRADED on the surviving "
               f"slice between shrink and rejoin "
               f"({v['transitions']})", failures)
    rejoined = [v for r, v in finals.items() if r in victims]
    _check(all(v["rejoined"] for v in rejoined),
           "every relaunched rank of the lost slice came back through "
           "rejoin", failures)
    # the straggler fault landed on the surviving slice only
    slow_fired = sum(v["counters"].get("faults.injected", 0)
                     for v in survivors)
    _check(slow_fired == rps,
           f"dcn_slow fired on every surviving-slice rank "
           f"(faults.injected={slow_fired}, want {rps})", failures)

    # zero loss of progress past the newest uploaded checkpoint
    _, newest_uploaded = _newest_remote_store(remote_root)
    final_step = finals[0]["final_step"]
    _check(newest_uploaded is not None
           and final_step >= newest_uploaded,
           f"final step {final_step} >= newest uploaded checkpoint "
           f"{newest_uploaded} (zero loss past the remote tier)",
           failures)

    summary.update({
        "passed": not failures,
        "newest_uploaded": newest_uploaded,
        "failures": failures,
    })
    return summary


def run_multislice_flap(checkpoint_every: int, workdir: str | None) -> dict:
    """Parent of the DCN flap storm — the degraded-mode acceptance gate
    (ISSUE 18, rung 2 of the ladder). A 2-slice x 2-rank supervised
    fleet trains the hierarchical schedule in BOUNDED-STALENESS mode
    (``DEAR_DCN_STALENESS=2``) while a sub-budget ``dcn_flap`` suppresses
    the victim slice's publishes on alternating exchanges and a
    ``dcn_slow`` straggler fault drags the other slice; the gate asserts:

      1. ZERO guard rollbacks on EVERY rank — the transient is absorbed
         entirely by retry + skip-with-error-feedback, never by the
         recovery machinery (the acceptance bar that separates degraded
         mode from the strict-mode rollback story);
      2. zero membership epochs, zero relaunches — nobody was evicted
         for a transient inside the staleness budget;
      3. the ladder actually engaged: every rank skipped at least one
         absent peer (``dcn.skips``), the flapped slice carried its
         unmerged partial as an error-feedback residual
         (``dcn.residual_carries``), and nobody escalated;
      4. the fleet finishes in lockstep at the exact step target, and
         ``bench_gate --slo`` holds the steps/hour floor — degraded
         rounds cost bounded retry budget, not stalls.
    """
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_flap_")
    elastic_dir = os.path.join(workdir, "elastic")
    remote_root = os.path.join(workdir, "remote")
    os.makedirs(remote_root, exist_ok=True)
    sup_mod = CC.load_supervisor()

    nslices, rps, steps = 2, 2, 12
    nprocs = nslices * rps
    flap_slice = 1
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_CHAOS_MULTI_MODE"] = "flap"
    env["DEAR_CHAOS_MULTI_STEPS"] = str(steps)
    env["DEAR_CHAOS_REMOTE"] = remote_root
    # the canonical sub-budget transient: exchanges 4 and 6 of the
    # victim slice are suppressed (staleness never exceeds 1 < budget 2),
    # plus a 30ms straggler on the survivor side from exchange 8
    env["DEAR_FAULTS"] = (f"dcn_flap@4:2:s{flap_slice},"
                          f"dcn_slow@8:0.03:s{1 - flap_slice}")
    env["DEAR_DCN_STALENESS"] = "2"
    env["DEAR_DCN_RETRIES"] = "1"
    env["DEAR_DCN_TIMEOUT_SECS"] = "3"
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "45")
    sup = sup_mod.ElasticSupervisor(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--multislice", "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=0, ranks_per_slice=rps,
    ).start()

    decided = CC.decided_reader(elastic_dir)
    rc, elapsed_s = CC.run_fleet(sup, deadline_s=300.0)

    failures: list[str] = []
    _check(rc == 0, f"supervisor fleet exits clean (got rc={rc})",
           failures)
    _check(all(n == 0 for n in sup.relaunches.values()),
           f"no rank was relaunched under the sub-budget flap "
           f"({sup.relaunches})", failures)
    _check(decided(1) is None,
           f"zero membership epochs: a sub-budget transient never "
           f"reaches the eviction rung (e1 = {decided(1)})", failures)

    _lives, finals = CC.collect_verdicts(workdir)
    summary = {"passed": False, "workdir": workdir, "rc": rc,
               "elapsed_s": round(elapsed_s, 1), "finals": finals,
               "failures": failures}
    if sorted(finals) != list(range(nprocs)):
        failures.append(f"expected final verdicts from ranks 0-"
                        f"{nprocs - 1}, got {sorted(finals)}")
        return summary

    for r, v in sorted(finals.items()):
        c = v["counters"]
        _check(c.get("guard.rollbacks", 0) == 0
               and not v["rollback_steps"],
               f"rank {r}: ZERO guard rollbacks under the sub-budget "
               f"flap (rollbacks={c.get('guard.rollbacks', 0)}, "
               f"steps={v['rollback_steps']})", failures)
        _check(v["steps_seen"] == steps and v["final_step"] == steps,
               f"rank {r} finished the exact step target "
               f"({v['steps_seen']}/{steps})", failures)
        _check(v.get("lockstep"), f"rank {r} finished in lockstep",
               failures)
        _check(not v["transitions"],
               f"rank {r} saw no membership transitions "
               f"({v['transitions']})", failures)
        _check(c.get("dcn.degraded_rounds", 0) > 0
               and c.get("dcn.skips", 0) >= 1,
               f"rank {r} trained through degraded rounds by SKIPPING "
               f"the absent slice (degraded_rounds="
               f"{c.get('dcn.degraded_rounds', 0)}, "
               f"skips={c.get('dcn.skips', 0)})", failures)
        _check(c.get("dcn.escalations", 0) == 0
               and c.get("dcn.self_evicts", 0) == 0,
               f"rank {r}: the ladder never escalated a SUB-budget "
               f"transient ({c})", failures)
    flapped = [v for r, v in finals.items()
               if v["slice"] == flap_slice]
    _check(all(v["counters"].get("dcn.residual_carries", 0) >= 1
               for v in flapped),
           "the flapped slice carried its unmerged partial as an "
           "error-feedback residual on every rank", failures)
    flap_fired = sum(v["counters"].get("faults.injected", 0)
                     for v in flapped)
    _check(flap_fired >= rps,
           f"dcn_flap armed on every flapped-slice rank "
           f"(faults.injected={flap_fired}, want >= {rps})", failures)

    # the service contract: degraded rounds are priced in bounded retry
    # budget, so throughput holds an absolute floor even while flapping
    slo_floor = float(os.environ.get("DEAR_CHAOS_FLAP_SLO", "50"))
    final_step = finals[0]["final_step"]
    steps_per_hour = final_step * 3600.0 / max(elapsed_s, 1e-9)
    CC.slo_gate(
        os.path.join(workdir, "flap_contract.json"),
        "steps_per_hour", round(steps_per_hour, 2),
        [{"metric": "final_step", "value": final_step},
         {"metric": "dcn_skips",
          "value": sum(v["counters"].get("dcn.skips", 0)
                       for v in finals.values())}],
        [f"steps_per_hour={slo_floor}"], failures,
        f"bench_gate --slo holds the steps/hour contract while "
        f"flapping ({steps_per_hour:.0f}/h vs floor {slo_floor:.0f}/h)")

    summary.update({
        "passed": not failures,
        "steps_per_hour": round(steps_per_hour, 2),
        "failures": failures,
    })
    return summary


def run_multislice_degraded(checkpoint_every: int,
                            workdir: str | None) -> dict:
    """Parent of the sustained-partition storm — rung 3 of the ladder
    (ISSUE 18). A 2-slice x 2-rank fleet trains in bounded-staleness
    mode while a ``dcn_partition`` sized far PAST the staleness budget
    starves the victim slice. No SIGKILL anywhere: the victim's own
    staleness clock must trip ``DcnSelfEvict``, the process exits 70,
    and the existing slice-granular machinery takes over — survivors
    escalate the silent peer (``dcn.escalations``), commit the shrink as
    ONE slice-shaped epoch, and the supervisor's relaunch readmits the
    slice (its new life strips the armed partition fault) as one epoch.
    The gate asserts the full ladder walked: skip -> escalate ->
    self-evict -> evict -> rejoin, with survivor rollbacks ONLY at the
    two membership transitions."""
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_part_")
    elastic_dir = os.path.join(workdir, "elastic")
    remote_root = os.path.join(workdir, "remote")
    os.makedirs(remote_root, exist_ok=True)
    sup_mod = CC.load_supervisor()

    nslices, rps = 2, 2
    nprocs = nslices * rps
    part_slice, target_epoch, post = 1, 2, 3
    victims = list(range(part_slice * rps, (part_slice + 1) * rps))
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_CHAOS_MULTI_MODE"] = "partition"
    env["DEAR_CHAOS_MULTI_EPOCHS"] = str(target_epoch)
    env["DEAR_CHAOS_MULTI_POST"] = str(post)
    env["DEAR_CHAOS_REMOTE"] = remote_root
    # a partition sized FAR past the staleness budget: outbound-dead
    # from exchange 3 until the process dies (the relaunched life strips
    # the fault, so the wall-clock arm never outlives the victim)
    env["DEAR_FAULTS"] = f"dcn_partition@3:600:s{part_slice}"
    env["DEAR_DCN_STALENESS"] = "1"
    env["DEAR_DCN_RETRIES"] = "1"
    env["DEAR_DCN_TIMEOUT_SECS"] = "2"
    # dead-member detection is the CLUSTER timeout here (the degraded
    # step never fails): keep it short so the shrink commits promptly
    # after the victims exit
    env["DEAR_CLUSTER_TIMEOUT_SECS"] = "10"
    sup = sup_mod.ElasticSupervisor(
        nprocs,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--multislice", "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=1, relaunch_window_s=300.0,
        ranks_per_slice=rps,
    ).start()

    decided = CC.decided_reader(elastic_dir)
    rc, elapsed_s = CC.run_fleet(sup, deadline_s=540.0)

    failures: list[str] = []
    _check(rc == 0, f"supervisor fleet exits clean (got rc={rc})",
           failures)
    _check(all(sup.relaunches.get(r, 0) == 1 for r in victims)
           and all(sup.relaunches.get(r, 0) == 0 for r in range(rps)),
           f"exactly the partitioned slice's ranks were relaunched "
           f"({sup.relaunches})", failures)

    # the victim slice must have evicted ITSELF — a durable self-evict
    # marker per rank, written before the exit-70
    evicts = []
    for name in sorted(os.listdir(workdir)):
        if name.startswith("selfevict_rank") and name.endswith(".json"):
            with open(os.path.join(workdir, name)) as f:
                evicts.append(json.load(f))
    _check(sorted(e["rank"] for e in evicts) == victims
           and all(e["slice"] == part_slice for e in evicts),
           f"every rank of the partitioned slice exited through "
           f"DcnSelfEvict, nobody else did ({evicts})", failures)

    rec1, rec2, rec3 = decided(1), decided(2), decided(3)
    _check(isinstance(rec1, dict)
           and rec1.get("delta", {}).get("removed") == victims
           and rec1.get("delta", {}).get("slices")
           == {"added": [], "removed": [part_slice]},
           f"e1 commits the self-evicted slice as one membership event "
           f"(got {rec1})", failures)
    _check(isinstance(rec2, dict)
           and rec2.get("delta", {}).get("added") == victims
           and rec2.get("delta", {}).get("slices")
           == {"added": [part_slice], "removed": []}
           and rec2.get("members") == list(range(nprocs)),
           f"e2 readmits the relaunched slice as one epoch at full "
           f"membership (got {rec2})", failures)
    _check(rec3 is None,
           f"no spurious membership epochs past e{target_epoch} "
           f"(e3 = {rec3})", failures)

    _lives, finals = CC.collect_verdicts(workdir)
    summary = {"passed": False, "workdir": workdir, "rc": rc,
               "elapsed_s": round(elapsed_s, 1), "finals": finals,
               "failures": failures}
    if sorted(finals) != list(range(nprocs)):
        failures.append(f"expected final verdicts from ranks 0-"
                        f"{nprocs - 1}, got {sorted(finals)}")
        return summary

    for r, v in sorted(finals.items()):
        _check(v["epoch"] == target_epoch
               and v["members"] == list(range(nprocs))
               and v["slices"] == [0, 1],
               f"rank {r} ends at epoch {target_epoch}, both slices "
               f"live (epoch {v['epoch']}, slices {v['slices']})",
               failures)
        _check(v.get("lockstep"), f"rank {r} finished in lockstep",
               failures)
        _check(v["dcn_slices"] == [0, 1],
               f"rank {r}'s DCN leg ends renormalized to both slices "
               f"({v['dcn_slices']})", failures)
    survivors = [v for r, v in finals.items() if r not in victims]
    for v in survivors:
        c = v["counters"]
        _check(c.get("dcn.skips", 0) >= 1
               and c.get("dcn.degraded_rounds", 0) >= 1,
               f"rank {v['rank']} first SKIPPED the starved slice "
               f"(skips={c.get('dcn.skips', 0)})", failures)
        _check(c.get("dcn.escalations", 0) >= 1,
               f"rank {v['rank']} escalated the past-budget peer "
               f"(dcn.escalations={c.get('dcn.escalations', 0)})",
               failures)
        _check(c.get("cluster.slice_losses", 0) == 1
               and c.get("cluster.slice_rejoins", 0) == 1,
               f"rank {v['rank']} saw exactly one slice loss and one "
               f"slice rejoin ({c})", failures)
        _check(len(v["rollback_steps"]) <= 2,
               f"rank {v['rank']}: rollbacks ONLY at the membership "
               f"transitions, never for the transient itself "
               f"({v['rollback_steps']})", failures)
        shrink = [t for t in v["transitions"]
                  if t["slices"] == [1 - part_slice]]
        rejoin = [t for t in v["transitions"] if t["slices"] == [0, 1]]
        _check(bool(shrink) and bool(rejoin)
               and rejoin[0]["steps_seen"] > shrink[0]["steps_seen"],
               f"rank {v['rank']} trained DEGRADED between shrink and "
               f"rejoin ({v['transitions']})", failures)
    rejoined = [v for r, v in finals.items() if r in victims]
    _check(all(v["rejoined"] for v in rejoined),
           "every relaunched rank of the partitioned slice came back "
           "through rejoin", failures)
    _check(all(v["counters"].get("faults.injected", 0) == 0
               for v in rejoined),
           "the relaunched lives stripped the armed partition fault",
           failures)

    summary.update({"passed": not failures, "failures": failures})
    return summary


# -- the serving storm ---------------------------------------------------------


def _serve_model():
    """The storm's tiny causal LM (identical on publisher and every
    replica — the params travel through the object store, the
    architecture through this function)."""
    from dear_pytorch_tpu.models.gpt import GptConfig, GptLmHeadModel

    cfg = GptConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=32, kv_cache_len=16,
        embd_dropout_prob=0.0, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
    )
    return GptLmHeadModel(cfg), cfg


def run_serve_publish(version: int, workdir: str) -> dict:
    """Publish weight version ``version`` to the serving object store —
    the 'trainer published a checkpoint' leg of the rolling weight swap.
    Different versions use different init seeds, so a swapped fleet is
    observably serving different logits."""
    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    import jax

    jax.config.update("jax_num_cpu_devices", 1)

    import jax.numpy as jnp

    from dear_pytorch_tpu.serving import weights as W
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    model, _cfg = _serve_model()
    params = model.init(
        {"params": jax.random.PRNGKey(1000 + version)},
        jnp.zeros((1, 4), jnp.int32), train=False)["params"]
    store = LocalObjectStore(os.environ["DEAR_SERVE_STORE"])
    key = W.publish_params(store, params, version)
    print(f"SERVE_PUBLISH v{version} -> {key}", flush=True)
    return {"passed": True, "version": version}


def run_worker_serve_replica(workdir: str) -> dict:
    """One serving replica (spawned — and respawned — by
    `launch/supervisor.py` under the elastic env contract). Loads the
    NEWEST committed weights from the object store (which is what makes
    drain+backfill a weight swap), serves the router's file protocol
    through a continuous-batching `serving.engine`, and exits 0 only via
    the SIGTERM drain path (`resilience.preempt`)."""
    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    import jax

    jax.config.update("jax_num_cpu_devices", 1)

    from dear_pytorch_tpu.resilience import PreemptionHandler
    from dear_pytorch_tpu.resilience import inject as INJ
    from dear_pytorch_tpu.serving import weights as W
    from dear_pytorch_tpu.serving.engine import DecodeEngine
    from dear_pytorch_tpu.serving.replica import ReplicaServer
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    rank = int(os.environ["DEAR_ELASTIC_RANK"])
    serve_dir = os.environ["DEAR_SERVE_DIR"]
    store = LocalObjectStore(os.environ["DEAR_SERVE_STORE"])
    # rank-targeted serving faults (slow replica, corrupted response):
    # own_rank comes from the supervisor contract, not jax.process_index
    raw = os.environ.get(INJ.FAULT_ENV, "").strip()
    injector = (INJ.FaultInjector(INJ.parse_faults(raw), own_rank=rank)
                if raw else None)
    # default load walks past corrupt AND rolled-back versions: a
    # backfill after a canary rollback lands on the last good version
    params, version = W.load_params(store)
    # load-time quality probe: the canary's per-version gauge is a real
    # held-out-perplexity eval (a NaN-poisoned bad_version publish reads
    # 0.0 here and fails the verdict; finite-but-damaged weights move
    # the gauge too — strictly more sensitive than the old
    # finite-fraction placeholder)
    quality = W.held_out_headroom(params)
    model, _cfg = _serve_model()
    engine = DecodeEngine(
        model, params,
        slots=int(os.environ.get("DEAR_SERVE_SLOTS", "4")),
        # the chunked-prefill fast path (ceil(P/C) prefill ticks),
        # interleaved with decode ticks under the engine's burst budget;
        # "1" restores the token-at-a-time path bit-identically
        prefill_chunk=int(os.environ.get("DEAR_SERVE_PREFILL_CHUNK", "1")))
    pre = PreemptionHandler().install()
    feedback = None
    if os.environ.get("DEAR_ONLINE_FEEDBACK") == "1":
        # the online loop's data plane: every response also becomes a
        # (prompt, response, feedback) record — bounded-buffer append
        # off the decode hot path, background segment flusher; the
        # writer id is the STABLE rank, so a relaunched incarnation
        # resumes the same single-writer stream at its committed tail
        from dear_pytorch_tpu.online.feedback import FeedbackWriter

        feedback = FeedbackWriter(
            store, writer_id=f"r{rank}", stream="main",
            flush_records=int(
                os.environ.get("DEAR_ONLINE_FLUSH_RECORDS", "8")),
            flush_interval_s=float(
                os.environ.get("DEAR_ONLINE_FLUSH_INTERVAL_S", "0.3")),
            injector=injector)
    srv = ReplicaServer(serve_dir, rank, engine, version=version,
                        quality=quality, injector=injector,
                        preemption=pre, feedback=feedback)
    summary = srv.run(
        deadline_s=float(os.environ.get("DEAR_SERVE_DEADLINE", "600")))
    if feedback is not None:
        # drain path: the final responses' records must be committed
        # before the process exits (the drain grace window covers this)
        feedback.close()
        summary["feedback_appended"] = feedback.appended
        summary["feedback_committed"] = feedback.committed
    print("CHAOS_SERVE_REPLICA " + json.dumps(summary), flush=True)
    return summary


def run_serve(workdir: str | None) -> dict:  # noqa: C901 — one storm, on
    #                                          purpose in one narrative
    """Parent of the SERVING storm — the fault-tolerant-fleet acceptance
    gate. A 2-replica supervised fleet serves closed-loop traffic while:

      1. an overload burst exceeds the admission depth — requests are
         shed with explicit 429-style backpressure and the clients'
         decorrelated-jitter retries (`resilience.retry`) land them;
      2. one replica is SIGKILLed MID-TRAFFIC — its in-flight requests
         are re-dispatched to the survivor (zero accepted-then-lost
         requests), and the supervisor relaunches it within the
         sliding-window budget;
      3. a scheduled ``corrupt_resp`` fault ships a checksum-broken
         response — the router discards and re-dispatches it;
      4. a new weight version is published to the object store and a
         ROLLING drain/backfill restart swaps every replica onto it with
         the fleet serving continuously (responses complete during every
         drain window);
      5. the capacity file scales the fleet 2 -> 3 under load;
      6. `bench_gate.py --slo` machine-checks the service contract: a
         throughput floor AND a p99-latency ceiling across the storm.

    The parent is jax-free: it runs the admission-controlled router
    (`serving.router`), drives `launch/supervisor.py` +
    `resilience.scale.ScalePolicy` through the capacity file, and
    SIGKILLs via the supervisor's pid files — exactly an operator's
    surface."""
    import signal
    import subprocess
    import tempfile
    import threading
    import time

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.resilience.retry import RetryError, retry_call
    from dear_pytorch_tpu.resilience.scale import ScalePolicy
    from dear_pytorch_tpu.serving.admission import (
        AdmissionController, SheddingError,
    )
    from dear_pytorch_tpu.serving.router import ReplicaRouter

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_serve_")
    os.makedirs(workdir, exist_ok=True)
    serve_dir = os.path.join(workdir, "serve")
    store_dir = os.path.join(workdir, "store")
    elastic_dir = os.path.join(workdir, "elastic")
    capacity = os.path.join(workdir, "capacity.json")
    failures: list[str] = []

    write_capacity = CC.capacity_writer(capacity)
    write_capacity({"target_world": 2})

    kill_rank = 1
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_SERVE_DIR"] = serve_dir
    env["DEAR_SERVE_STORE"] = store_dir
    env["DEAR_SERVE_SLOTS"] = "4"
    env["DEAR_SERVE_DEADLINE"] = "600"
    # the storm runs the chunked-prefill fast path: the zero-drop /
    # re-dispatch / drain guarantees must hold on the path production
    # would actually serve (deterministic greedy decode is unchanged, so
    # re-dispatched requests still reproduce identical tokens)
    env["DEAR_SERVE_PREFILL_CHUNK"] = os.environ.get(
        "DEAR_SERVE_PREFILL_CHUNK", "4")
    # the serving fault schedule: replica 1 straggles from its 8th
    # request on (admission backpressure fodder), replica 0's 3rd
    # response is corrupted after signing (checksum re-dispatch)
    env["DEAR_FAULTS"] = "slow@8:0.05:r1,corrupt_resp@3:r0"

    # v1 weights land in the store before any replica boots
    pub = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--serve-publish", "--version", "1", "--workdir", workdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=120)
    _check(pub.returncode == 0,
           f"weight v1 published: {pub.stdout[-800:]}", failures)

    sup_mod = CC.load_supervisor()
    policy = ScalePolicy(capacity_file=capacity, hysteresis_s=0.5,
                         max_world=3)
    sup = sup_mod.ElasticSupervisor(
        2,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--serve-replica", "--workdir", workdir],
        elastic_dir=elastic_dir, env=env,
        max_relaunches=2, relaunch_window_s=120.0, policy=policy,
    ).start()

    prev_tracer = T._tracer
    T.set_tracer(T.Tracer([T.MemoryExporter()]))
    admission = AdmissionController(max_depth=8)
    router = ReplicaRouter(serve_dir, admission=admission,
                           slots_per_replica=4,
                           health_timeout_s=5.0).start()
    t0 = time.monotonic()
    fleet = CC.FleetPump([sup], failures, deadline_s=480.0)
    pump = fleet.pump

    stop_clients = threading.Event()
    client_failures: list[str] = []
    retry_exhausted = [0]

    def one_request(tag, i, deadline_s=60.0, timeout_s=240.0):
        prompt = [(tag * 31 + i * 7 + k) % 61 for k in range(4 + i % 3)]
        try:
            rid = retry_call(
                router.submit, prompt, max_new_tokens=3,
                deadline_s=deadline_s, attempts=8, base_delay_s=0.05,
                max_delay_s=0.8, retry_on=(SheddingError,),
                name=f"serve-client-{tag}")
        except RetryError:
            retry_exhausted[0] += 1  # shed to exhaustion: accounted, not
            return None              # dropped (it was never accepted)
        try:
            return router.result(rid, timeout=timeout_s)
        except TimeoutError:
            client_failures.append(f"client {tag} req {i}: no response")
            return None

    def steady_client(tag):
        i = 0
        while not stop_clients.is_set():
            one_request(tag, i)
            i += 1
            time.sleep(0.05)

    clients = [threading.Thread(target=steady_client, args=(t,),
                                daemon=True) for t in range(2)]

    try:
        # -- phase A: fleet up, traffic flowing ---------------------------
        _check(pump(lambda: len(router.healthy_replicas()) >= 2,
                    "2 replicas healthy", 180.0),
               "initial fleet of 2 replicas is serving", failures)
        for c in clients:
            c.start()
        _check(pump(lambda: len(router.completed) >= 5,
                    "first responses", 60.0),
               "closed-loop traffic completes", failures)

        # -- phase B: overload burst -> explicit shedding -----------------
        burst_results = []
        burst_threads = [
            threading.Thread(target=lambda i=i: burst_results.append(
                one_request(100 + i, i, deadline_s=120.0)), daemon=True)
            for i in range(14)]
        for th in burst_threads:
            th.start()
        pump(lambda: admission.shed >= 1, "burst sheds", 30.0)
        _check(admission.shed >= 1,
               f"admission shed under the burst (shed={admission.shed}, "
               f"depth bound {admission.max_depth})", failures)

        # -- phase C: SIGKILL a replica MID-traffic -----------------------
        pump(lambda: router.inflight_on(kill_rank) >= 1,
             "in-flight work on the victim", 30.0)
        pid_path = os.path.join(elastic_dir, "supervisor", "pids",
                                str(kill_rank))
        with open(pid_path) as f:
            victim_pid = int(f.read())
        os.kill(victim_pid, signal.SIGKILL)
        _check(pump(lambda: router.redispatched >= 1,
                    "redispatch after SIGKILL", 60.0),
               "the dead replica's in-flight requests were re-dispatched",
               failures)
        _check(pump(lambda: sup.relaunches.get(kill_rank, 0) >= 1
                    and kill_rank in router.healthy_replicas(),
                    "victim relaunched + healthy", 120.0),
               "the supervisor relaunched the SIGKILLed replica within "
               "its window budget", failures)
        for th in burst_threads:
            th.join(timeout=240)

        # -- phase D: rolling weight swap (drain -> backfill per rank) ----
        pub2 = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--serve-publish", "--version", "2", "--workdir", workdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=120)
        _check(pub2.returncode == 0,
               f"weight v2 published: {pub2.stdout[-800:]}", failures)
        min_healthy_during_swap = [99]

        def sampling(base_cond):
            # sample the healthy count on EVERY pump poll THROUGHOUT the
            # drain/backfill window — a single post-backfill sample would
            # always read a healthy-by-construction fleet and the
            # continuous-serving assertion below would be vacuous
            def cond():
                min_healthy_during_swap[0] = min(
                    min_healthy_during_swap[0],
                    len(router.healthy_replicas()))
                return base_cond()
            return cond

        for rank in (0, 1):
            before = len(router.completed)
            write_capacity({"target_world": 2, "drain": [rank]})
            ok = pump(sampling(lambda r=rank: ("drained", r) in sup.events),
                      f"rank {rank} drained cleanly", 90.0)
            _check(ok, f"rank {rank} drained via the SIGTERM grace path",
                   failures)
            _check(pump(sampling(lambda r=rank:
                                 router.fleet_versions().get(r) == 2),
                        f"rank {rank} back on v2", 120.0),
                   f"backfilled rank {rank} serves weight v2", failures)
            _check(pump(sampling(lambda b=before:
                                 len(router.completed) > b),
                        f"traffic during rank-{rank} swap", 60.0),
                   f"responses completed during the rank-{rank} drain "
                   "window (continuous serving)", failures)
        _check(router.weight_swaps >= 2,
               f"the router observed both weight swaps "
               f"(serve.weight_swaps={router.weight_swaps})", failures)

        # -- phase E: capacity-up under load ------------------------------
        write_capacity({"target_world": 3})
        _check(pump(lambda: len(router.healthy_replicas()) >= 3,
                    "scale-up to 3", 120.0),
               "the capacity hint scaled the serving fleet to 3 replicas",
               failures)
        _check(router.fleet_versions().get(2) == 2,
               "the scaled-up replica came up on the newest weights",
               failures)

        # -- wind down: every accepted request must be answered -----------
        stop_clients.set()
        for c in clients:
            c.join(timeout=240)
        _check(pump(lambda: not router.open_requests(),
                    "all accepted requests answered", 120.0),
               "zero dropped requests: every admitted request got a "
               f"verified response (open={sorted(router.open_requests())})",
               failures)
        _check(not client_failures,
               f"no client timed out ({client_failures[:4]})", failures)
        _check(router.corrupt_responses >= 1,
               "the corrupted response was caught by its checksum and "
               f"re-served (corrupt={router.corrupt_responses})", failures)
        _check(sup.relaunches.get(kill_rank, 0) == 1
               and all(n == 0 for r, n in sup.relaunches.items()
                       if r != kill_rank),
               f"exactly the SIGKILLed replica was relaunched "
               f"({sup.relaunches})", failures)
        kinds = [d.kind for d in policy.decisions]
        _check(kinds.count("drain") >= 2 and kinds.count("scale_up") >= 3,
               f"policy drove both drains, both backfills, and the "
               f"scale-up ({kinds})", failures)
        _check(min_healthy_during_swap[0] >= 1,
               "at least one replica stayed healthy through the rolling "
               "swap", failures)
    finally:
        stop_clients.set()
        elapsed_s = time.monotonic() - t0
        sup.policy = None  # shutdown must not be 'lost capacity'
        sup.kill_all(signal.SIGTERM)  # drain path: clean exits
        t_end = time.monotonic() + 60.0
        while sup.poll() and time.monotonic() < t_end:
            time.sleep(0.1)
        if sup._procs:
            sup.kill_all(signal.SIGKILL)
        stats = router.stats()
        router.close()
        counters = T.get_tracer().counters()
        T.set_tracer(prev_tracer)

    bad_exits = {r: rc for r, rc in sup._final_rc.items()
                 if rc not in (0, -signal.SIGKILL.value)
                 and r != kill_rank}
    _check(not bad_exits, f"replicas exited clean ({bad_exits})", failures)

    # the machine-checked service contract: a throughput FLOOR and a
    # p99-latency CEILING through bench_gate --slo, across the whole storm
    completed = stats["completed"]
    rps = completed / max(elapsed_s, 1e-9)
    rps_floor = float(os.environ.get("DEAR_CHAOS_SERVE_RPS", "0.2"))
    p99_ceil = float(os.environ.get("DEAR_CHAOS_SERVE_P99_MS", "60000"))
    CC.slo_gate(
        os.path.join(workdir, "serve_contract.json"),
        "requests_per_s", round(rps, 3),
        [{"metric": "p99_latency_ms", "value": stats["latency_p99_ms"]},
         {"metric": "served", "value": completed},
         {"metric": "shed", "value": stats["shed"]}],
        [f"requests_per_s={rps_floor}", f"p99_latency_ms<={p99_ceil}"],
        failures,
        f"bench_gate --slo holds the serving contract "
        f"({rps:.2f} req/s >= {rps_floor}; p99 "
        f"{stats['latency_p99_ms']}ms <= {p99_ceil}ms)")

    return {
        "passed": not failures,
        "workdir": workdir,
        "elapsed_s": round(elapsed_s, 1),
        "requests_per_s": round(rps, 3),
        "stats": stats,
        "retry_exhausted": retry_exhausted[0],
        "policy_decisions": [d.kind for d in policy.decisions],
        "serve_counters": {k: v for k, v in sorted(counters.items())
                           if k.startswith("serve.")},
        "failures": failures,
    }


# -- the online continual-learning storm ---------------------------------------


def run_worker_online_trainer(checkpoint_every: int, workdir: str) -> dict:
    """One rank of the ONLINE trainer fleet (spawned — and relaunched —
    by `launch/supervisor.py` under the rejoin env contract). Mirrors the
    autoscale worker (guard + elastic cluster + checkpoint streamer +
    preemption) with the data path swapped for the online loop:

      - the pipeline is a PARTITIONED `online.ingest.FeedbackIngest`
        over the shared object store — each rank scatter-reads only its
        owned writers' segments (ownership hashed over the data world),
        takes its quota into a cursor copy, and ONE
        `ElasticCluster.exchange` per step all-gathers every shard's
        records + positions together with the exit votes (stop-file
        observation, drained flag, newest store version); every rank
        assembles the identical merged batch and union cursor, so
        replicas still train byte-identical batches (the desync
        sentinel watches) while ingest I/O scales with world size,
      - a `online.quality.QualityGate` sits above the reader: the
        scheduled `poison_feedback` burst advances the cursor and the
        reject counters but never reaches the model,
      - the leader compacts feedback segments below the cursor of the
        version two publishes back after each publish (retention riding
        the publish cadence),
      - the member-0 leader publishes weights through
        `online.publish.VersionPublisher` every N steps with cursor
        provenance,
      - the scheduled victim SIGKILLs itself a fixed number of steps
        after the fleet's consumed-record count crosses a threshold
        (consumed_total is lockstep-identical, so the schedule is
        deterministic without wall clocks),
      - exit is itself a consensus: all members observed the parent's
        stop file AND the cursor drained AND the version target AND the
        post-rejoin epoch — so the fleet finishes in lockstep with
        identical final cursors.
    """
    import signal
    import time

    os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
    os.environ["DEAR_CKPT_SHARED"] = "0"
    import jax

    jax.config.update("jax_num_cpu_devices", 2)

    import numpy as np

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.online.feedback import (
        Cursor, FeedbackReader, compact_segments,
    )
    from dear_pytorch_tpu.online.ingest import FeedbackIngest
    from dear_pytorch_tpu.online.publish import (
        VersionPublisher, read_online_sidecar,
    )
    from dear_pytorch_tpu.online.quality import QualityGate
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.resilience import PreemptionHandler
    from dear_pytorch_tpu.resilience import inject as INJ
    from dear_pytorch_tpu.resilience import membership as M
    from dear_pytorch_tpu.resilience.cluster import PeerTimeout
    from dear_pytorch_tpu.runtime import build as RB
    from dear_pytorch_tpu.runtime import pipeline as P
    from dear_pytorch_tpu.serving import weights as W
    from dear_pytorch_tpu.tuning.autotune import AutoTuner
    from dear_pytorch_tpu.utils import checkpoint as ckpt
    from dear_pytorch_tpu.utils.guard import GuardedTrainer
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    EH = _load_harness()
    cluster = M.ElasticCluster.from_env(max_candidates=256)
    rejoining = M.ElasticCluster.rejoining_by_env()
    rank = cluster.rank
    kr, kc, kx = os.environ["DEAR_CHAOS_ONLINE_KILL"].split(":")
    kill_rank, kill_consumed, kill_extra = int(kr), int(kc), int(kx)
    publish_every = int(
        os.environ.get("DEAR_CHAOS_ONLINE_PUBLISH_EVERY", "25"))
    target_versions = int(os.environ.get("DEAR_CHAOS_ONLINE_VERSIONS", "3"))
    target_epoch = int(os.environ.get("DEAR_CHAOS_ONLINE_EPOCHS", "2"))
    stop_file = os.environ["DEAR_CHAOS_ONLINE_STOP"]
    # deploy freeze: the parent caps the store's version ladder while
    # the canary judges the newest publish — the production push-freeze
    # during canary evaluation. The force path (drain) is uncapped.
    cap_path = os.environ.get("DEAR_CHAOS_ONLINE_PUBLISH_CAP")

    def publish_cap() -> int:
        if not cap_path:
            return 1 << 30
        try:
            with open(cap_path) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return 1 << 30
    remote_root = os.environ["DEAR_CHAOS_REMOTE"]
    store = LocalObjectStore(os.environ["DEAR_CHAOS_ONLINE_STORE"])
    ckpt_dir = os.path.join(workdir, f"trainer_rank{rank}", "ckpts")
    tracer = T.get_tracer()
    # rank-targeted trainer faults (bad_version): own_rank from the
    # supervisor contract, same as the serving side
    raw_faults = os.environ.get(INJ.FAULT_ENV, "").strip()
    injector = (INJ.FaultInjector(INJ.parse_faults(raw_faults),
                                  own_rank=rank) if raw_faults else None)

    # the trainer trains THE MODEL THE FLEET SERVES — the same tiny
    # causal LM `run_worker_serve_replica` decodes with — so a published
    # version really is a new set of serving weights, and the feedback
    # records (served prompt+response token sequences) really are its
    # training data
    import jax.numpy as jnp

    model, _cfg = _serve_model()

    def gpt_loss(p, batch):
        logits = model.apply({"params": p}, batch, train=False)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                                  axis=-1)
        tgt = batch[:, 1:]
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return -jnp.mean(ll)

    B, S = 8, 16
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((B, S), jnp.int32),
                        train=False)["params"]
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:min(cluster.world, 2)]), ("dp",))
    tuner = AutoTuner(
        gpt_loss, params, strategy="bo", threshold_mb=0.0008,
        interval=10**9, mesh=mesh, donate=False,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
    )

    # the online data path: base synthetic token stream + the feedback
    # log. batch_fn is a deterministic pure function (same base batch +
    # same records => same training batch on every rank and every
    # replay): each record's served prompt+response tokens overwrite the
    # head of one base row.
    spec = P.SyntheticSpec((
        P.Field("input_ids", (B, S), RB.KIND_UNIFORM_I32, 0, 61),
    ))
    base = P.NumpyPipeline(spec, seed=123, shard=0, num_shards=1)

    def batch_fn(base_batch, records):
        ids = np.array(base_batch["input_ids"], dtype=np.int32)
        for j, rec in enumerate(records[:B]):
            toks = (list(rec.get("prompt") or [])
                    + list(rec.get("response") or []))[:S]
            ids[j, :len(toks)] = np.asarray(toks, np.int32) % 61
        return ids

    # ONE exchange per step, now carrying the PARTITIONED ingest gather
    # (each rank's owned-writer take + post-take positions — the
    # scatter-read/all-gather protocol in online/ingest.py) PLUS the
    # exit votes. A dead peer costs one short timeout and a blend step
    # (nothing consumed); the guard's own health sync then commits the
    # shrink.
    shared = {"stop": False, "drained": False, "version": 0}

    def exchange_ingest(payload):
        stop_seen = os.path.exists(stop_file)
        if stop_seen:
            # drain intent: the drained verdict must rest on the
            # DEFINITIVE frontier (the probe fast path cannot jump a
            # torn segment's numbering gap until a discovery listing)
            ing.full_frontier = True
        wrapped = json.dumps({
            "ing": payload,
            "stop": stop_seen,
            "v": int(W.latest_version(store) or 0),
        })
        try:
            views = cluster.exchange("online.avail", wrapped,
                                     timeout_s=4.0)
        except PeerTimeout:
            shared["stop"] = shared["drained"] = False
            return None  # blend step: the cursor copy is discarded
        docs = [json.loads(v) for v in views]
        shared["stop"] = all(d["stop"] for d in docs)
        shared["drained"] = all(d["ing"]["d"] for d in docs)
        shared["version"] = min(d["v"] for d in docs)
        return [d["ing"] for d in docs]

    # the quality gate: poison bursts (the `poison_feedback` fault)
    # advance the cursor and the reject counters, never the model. Pure
    # => the post-filter batch stays identical across ranks.
    qgate = QualityGate(max_prompt_tokens=64, max_response_tokens=64)
    ing = FeedbackIngest(
        base, FeedbackReader(store, stream="main"), batch_records=B,
        batch_fn=batch_fn, exchange_fn=exchange_ingest, quality=qgate)
    if cluster.members and rank in cluster.members:
        # seat writer ownership for the boot membership; every later
        # transition re-seats it through the guard's reshard call
        ing.reshard(list(cluster.members).index(rank),
                    len(cluster.members), epoch=cluster.epoch)

    streamer = ckpt.CheckpointStreamer(
        ckpt_dir, LocalObjectStore(os.path.join(remote_root, f"rank{rank}")),
        upload_every=2, pin_last=4)
    pre = PreemptionHandler().install()
    guard = GuardedTrainer(
        tuner.ts, ckpt_dir, params,
        check_every=1, checkpoint_every=checkpoint_every, max_keep=1000,
        max_recoveries=8, coordinator=cluster, pipeline=ing,
        preemption=pre, streamer=streamer,
    )
    EH.attach_elastic(guard, tuner)
    rollback_steps = []
    guard.on_rollback = lambda c, at: rollback_steps.append(at)

    holder = {"state": None}
    publisher = VersionPublisher(
        store, publish_every=publish_every,
        params_fn=lambda: jax.device_get(
            guard.ts.gather_params(holder["state"])),
        cursor_fn=lambda: ing.cursor.to_dict(), injector=injector)

    resumed_at = None
    if rejoining:
        # hydrate from a fleet peer's remote tier so the consensus
        # restore loses at most the upload lag, not this rank's downtime
        hydrate, _ = _newest_remote_store(remote_root, skip_rank=rank)
        state, resumed_at, _last_epoch = EH.reenter(
            cluster, tuner, guard, ckpt_dir, hydrate_store=hydrate)
    else:
        state = tuner.init(params)
    holder["state"] = state

    deadline = time.monotonic() + 520.0
    kill_at = None
    preempted = False
    last_pub_consumed = [-1]

    def leader() -> bool:
        return bool(cluster.members) and cluster.members[0] == rank

    while True:
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"trainer rank {rank} never reached the consensus exit "
                f"(epoch {cluster.epoch}, consumed "
                f"{ing.cursor.consumed_total})")
        if not rejoining and kill_rank == rank:
            # deterministic mid-step loss: a fixed number of steps after
            # the (lockstep-identical) consumed-record threshold
            if kill_at is None \
                    and ing.cursor.consumed_total >= kill_consumed:
                kill_at = guard.steps_seen + 1 + kill_extra
            if kill_at is not None and guard.steps_seen + 1 == kill_at:
                os.kill(os.getpid(), signal.SIGKILL)  # abrupt host loss
        batch = ing.next()
        state, m = guard.step(state, batch)
        holder["state"] = state
        if m.get("preempted"):
            preempted = True
            break  # parent shutdown: drain cleanly with the grace window
        # publish on the cadence, but (past v1) only versions that
        # actually contain NEW feedback — a version bump should mean new
        # data reached the fleet, and the freshness audit relies on it —
        # and never past the parent's deploy-freeze cap
        if (ing.cursor.consumed_total > last_pub_consumed[0]
                or not publisher.published) \
                and int(W.latest_version(store) or 0) < publish_cap():
            v = publisher.maybe_publish(guard.steps_seen, leader=leader())
            if v is not None:
                last_pub_consumed[0] = ing.cursor.consumed_total
                # retention rides the publish cadence: the leader
                # compacts segments below the cursor of the version TWO
                # publishes back — a floor every restore horizon has
                # cleared (a guard rollback or a rejoiner's consensus
                # restore never needs a deleted segment) and that keeps
                # the previous version's provenance window replayable
                # for the parent's freshness audit
                if len(publisher.published) >= 3:
                    side = read_online_sidecar(
                        store, publisher.published[-3])
                    if side and side.get("cursor"):
                        compact_segments(
                            store, "main",
                            Cursor.from_dict(side["cursor"]),
                            reader=ing.reader)
        if shared["stop"] and shared["drained"] \
                and cluster.epoch >= target_epoch:
            if shared["version"] >= target_versions:
                break
            # the log is frozen but the version target is short: the
            # leader force-publishes the remaining versions (the final
            # ones cover the fully-drained cursor); followers keep
            # exchanging until the store shows the target
            publisher.maybe_publish(guard.steps_seen, leader=leader(),
                                    force=True)
        time.sleep(0.04)

    streamer.flush(20.0)
    streamer.close()
    counters = tracer.counters()
    verdict = {
        "rank": rank,
        "pid": os.getpid(),
        "rejoined": bool(rejoining),
        "preempted": preempted,
        "epoch": cluster.epoch,
        "members": list(cluster.members),
        "resumed_at": resumed_at,
        "rollback_steps": rollback_steps,
        "final_step": int(jax.device_get(state.step)),
        "steps_seen": guard.steps_seen,
        "plan_world": guard.ts.plan.world,
        "plan_epoch": guard.ts.plan.epoch,
        "ingest": ing.cursor.to_dict(),
        "shard_cursors": ing.shard_cursors(),
        "quality_rejected": dict(qgate.rejected),
        "quality_admitted": qgate.admitted,
        "published": publisher.published,
        "publish_failures": publisher.publish_failures,
        "uploaded": sorted(streamer.uploaded),
        "upload_failed": sorted(streamer.failed),
        "counters": {k: v for k, v in counters.items()
                     if k.startswith(("cluster.", "guard.", "pipeline.",
                                      "online.", "ckpt."))},
    }
    if not preempted:
        # the lockstep verdict is itself a member-scoped collective
        views = cluster.exchange("chaos.verdict", json.dumps(
            [verdict["final_step"], verdict["ingest"]["consumed_total"],
             verdict["ingest"]["checksum"], verdict["epoch"]]))
        verdict["lockstep"] = all(
            json.loads(v) == json.loads(views[0]) for v in views)
    path = os.path.join(workdir,
                        f"trainer_verdict_rank{rank}.{os.getpid()}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(verdict, f)
    os.replace(path + ".tmp", path)
    print(f"CHAOS_ONLINE_TRAINER rank={rank} " + json.dumps(verdict),
          flush=True)
    return verdict


def run_online(checkpoint_every: int, workdir: str | None) -> dict:  # noqa: C901
    """Parent of the ONLINE storm — the training↔serving closed-loop
    acceptance gate (ROADMAP item 4). Two supervised fleets share one
    object store:

      - a 2-replica SERVING fleet under closed-loop client traffic,
        every response appended to the durable feedback log
        (``torn_seg`` and ``dup_feedback`` faults scheduled on the
        writers),
      - a 2-rank TRAINER fleet ingesting the log exactly-once at a
        checkpointed consensus cursor, publishing weight versions with
        cursor provenance.

    The storm: SIGKILL a serving replica mid-traffic (zero
    accepted-then-lost), SIGKILL a trainer rank mid-step (elastic shrink
    + rejoin = the forced reshard; the PARTITIONED shard cursors
    redistribute across the world change with the union restored from
    the consensus checkpoint), walk a torn feedback segment, absorb a
    duplicate record, swallow a 12-record poisoned feedback burst
    through the quality gate, and execute the PR-11 drain+backfill
    rolling swap every time the trainer's published version bumps —
    twice. Then the DATA-plane and CONTROL-plane faults interact: the
    trainer's 4th publish is NaN-poisoned (``bad_version``), a canary
    deployment rolls one replica onto it, the router's A/B verdict fails
    it on the load-time quality gauge, the rollback marker lands in the
    store, and the loser's backfill returns the fleet to the last good
    version — the next publish minting a FRESH number, never reusing
    the rolled-back one. The gate then freezes the log (clients
    stopped, serving fleet drained), lets the trainer drain the cursor,
    and asserts the exactly-once ledger: the fleet's final cursor
    equals a jax-free replay of the whole log (consumed count AND
    order-independent checksum — no gaps, no dups; per-shard slices
    tile the union exactly), with the torn segment walked past, the
    duplicate deduplicated, the poison rejected-but-accounted, and the
    compaction markers (retention ran mid-storm) preserving the ledger
    across deleted segments. Freshness (feedback-commit → first version
    serving it) and throughput are machine-checked through
    `bench_gate.py --slo`."""
    import signal
    import tempfile
    import threading
    import time

    from dear_pytorch_tpu.observability import tracer as T
    from dear_pytorch_tpu.online.feedback import Cursor, FeedbackReader
    from dear_pytorch_tpu.online.publish import read_online_sidecar
    from dear_pytorch_tpu.resilience.retry import RetryError, retry_call
    from dear_pytorch_tpu.resilience.scale import ScalePolicy
    from dear_pytorch_tpu.serving import weights as W
    from dear_pytorch_tpu.serving.admission import (
        AdmissionController, SheddingError,
    )
    from dear_pytorch_tpu.serving.router import (
        CanaryController, ReplicaRouter,
    )
    from dear_pytorch_tpu.utils.objectstore import LocalObjectStore

    workdir = workdir or tempfile.mkdtemp(prefix="dear_chaos_online_")
    os.makedirs(workdir, exist_ok=True)
    serve_dir = os.path.join(workdir, "serve")
    store_dir = os.path.join(workdir, "store")        # weights + feedback
    remote_root = os.path.join(workdir, "remote")     # trainer ckpt tier
    trainer_elastic = os.path.join(workdir, "trainer_elastic")
    serve_elastic = os.path.join(workdir, "serve_elastic")
    capacity = os.path.join(workdir, "capacity.json")
    stop_file = os.path.join(workdir, "STOP_TRAINER")
    os.makedirs(remote_root, exist_ok=True)
    failures: list[str] = []
    write_capacity = CC.capacity_writer(capacity)
    write_capacity({"target_world": 2})

    trainer_kill_rank, serve_kill_rank = 1, 1
    target_versions = 5
    env = dict(os.environ)
    env.pop("DEAR_NUM_CPU_DEVICES", None)
    # the parent's trace identity must not leak into the fleet: each
    # worker's span stream keys off its own DEAR_ELASTIC_RANK
    env.pop("DEAR_TRACE_RANK", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["DEAR_DISABLE_DISTRIBUTED"] = "1"
    env["DEAR_TELEMETRY"] = "1"
    env["DEAR_CHAOS_ONLINE_STORE"] = store_dir
    env["DEAR_CHAOS_REMOTE"] = remote_root
    env["DEAR_CHAOS_ONLINE_STOP"] = stop_file
    env["DEAR_CHAOS_ONLINE_KILL"] = f"{trainer_kill_rank}:8:1"
    env["DEAR_CHAOS_ONLINE_PUBLISH_EVERY"] = "20"
    env["DEAR_CHAOS_ONLINE_VERSIONS"] = str(target_versions)

    # the deploy freeze: phases A-E run to v3; phase G lifts the cap to
    # v4 (the poisoned canary candidate), judges it, and only then
    # uncaps — so v5 can never race the canary verdict
    publish_cap = os.path.join(workdir, "publish_cap.txt")

    def write_publish_cap(n: int) -> None:
        tmp = publish_cap + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(n))
        os.replace(tmp, publish_cap)

    write_publish_cap(3)
    env["DEAR_CHAOS_ONLINE_PUBLISH_CAP"] = publish_cap
    env["DEAR_PREEMPT_GRACE_S"] = "30"
    # a peer's post-transition XLA recompile must not read as a death
    env.setdefault("DEAR_CLUSTER_TIMEOUT_SECS", "30")

    sup_mod = CC.load_supervisor()
    trainer_env = dict(env)
    # the control-plane fault: the leader's 4th publish ships NaN
    # weights — v4 is the storm's poisoned canary candidate
    trainer_env["DEAR_FAULTS"] = "bad_version@4:r0"
    sup_t = sup_mod.ElasticSupervisor(
        2,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--online-trainer", "--checkpoint-every", str(checkpoint_every),
         "--workdir", workdir],
        elastic_dir=trainer_elastic, env=trainer_env,
        max_relaunches=2, relaunch_window_s=180.0,
    ).start()

    store = LocalObjectStore(store_dir)
    reader = FeedbackReader(store, stream="main")
    t0 = time.monotonic()
    fleet = CC.FleetPump([sup_t], failures, deadline_s=560.0)
    pump = fleet.pump

    # -- phase A: the trainer publishes v1 before any replica boots -------
    _check(pump(lambda: (W.latest_version(store) or 0) >= 1,
                "trainer publishes v1", 150.0),
           "the trainer fleet published weight v1 to the store", failures)

    # -- phase B: serving fleet + closed-loop traffic + feedback ----------
    serve_env = dict(env)
    serve_env["DEAR_SERVE_DIR"] = serve_dir
    serve_env["DEAR_SERVE_STORE"] = store_dir
    serve_env["DEAR_SERVE_SLOTS"] = "4"
    serve_env["DEAR_ONLINE_FEEDBACK"] = "1"
    serve_env["DEAR_ONLINE_FLUSH_RECORDS"] = "8"
    serve_env["DEAR_ONLINE_FLUSH_INTERVAL_S"] = "0.3"
    # the data-path faults, writer-targeted: replica 0 tears its 2nd
    # segment flush (manifest-less partial write), replica 1 re-appends
    # an already-committed record on its 6th append. The slow fault
    # makes replica 1 a straggler from its 4th request on — which is
    # what guarantees the SIGKILL below lands while it HOLDS in-flight
    # work (without it the tiny model answers in milliseconds and the
    # mid-traffic kill is a coin flip). poison_feedback injects a
    # 12-record poisoned burst through writer r0's 10th append — the
    # trainer-side quality gate must reject every one while the cursor
    # ledger still accounts for them
    serve_env["DEAR_FAULTS"] = \
        "torn_seg@2:r0,dup_feedback@6:r1,slow@4:0.1:r1," \
        "poison_feedback@10:12:r0"
    policy = ScalePolicy(capacity_file=capacity, hysteresis_s=0.5,
                         max_world=3)
    sup_s = sup_mod.ElasticSupervisor(
        2,
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--serve-replica", "--workdir", workdir],
        elastic_dir=serve_elastic, env=serve_env,
        max_relaunches=2, relaunch_window_s=180.0, policy=policy,
    ).start()
    fleet.add_supervisor(sup_s)

    prev_tracer = T._tracer
    T.set_tracer(T.Tracer([T.MemoryExporter()]))
    admission = AdmissionController(max_depth=8)

    # the canary's store-side commit: a FAIL verdict drops the
    # first-writer-wins rollback marker; the parent then drives the
    # loser's drain+backfill (the PR-11 swap in reverse) below
    canary_rolled: list[int] = []

    def on_canary(version, verdict):
        if verdict == "FAIL":
            W.mark_rolled_back(store, version,
                               reason="canary quality gauge")
            canary_rolled.append(int(version))

    # latency_factor is deliberately loose: the slow@ fault makes one
    # replica a legitimate straggler, so only the quality gauge (NaN
    # params -> 0.0) may sink a candidate here
    router = ReplicaRouter(
        serve_dir, admission=admission, slots_per_replica=4,
        health_timeout_s=5.0,
        canary=CanaryController(min_requests=4, quality_floor=0.9,
                                latency_factor=50.0, share=3),
        on_canary=on_canary).start()

    # continuous observation: first wall-clock time each weight version
    # was seen SERVING (freshness), min healthy during the swaps
    first_served: dict[int, float] = {}
    min_healthy = [99]

    def sample():
        versions = router.fleet_versions()
        now = time.time()
        for _r, v in versions.items():
            if v is not None:
                first_served.setdefault(int(v), now)
        healthy = len(router.healthy_replicas())
        if healthy == 0 and min_healthy[0] > 0:
            # first zero-healthy observation: dump per-replica state so
            # a min-healthy failure is diagnosable from the log
            with router._lock:
                states = {r.rank: {"healthy": r.healthy,
                                   "draining": r.draining,
                                   "hb_age_s": round(
                                       now - r.last_wall_ts, 2)}
                          for r in router._replicas.values()}
            print(f"chaos_check: healthy=0 observed "
                  f"(replica states {states})", flush=True)
        min_healthy[0] = min(min_healthy[0], healthy)

    stop_clients = threading.Event()
    client_failures: list[str] = []

    def one_request(tag, i):
        prompt = [(tag * 31 + i * 7 + k) % 61 for k in range(4 + i % 3)]
        try:
            rid = retry_call(
                router.submit, prompt, max_new_tokens=3, deadline_s=60.0,
                attempts=8, base_delay_s=0.05, max_delay_s=0.8,
                retry_on=(SheddingError,), name=f"online-client-{tag}")
        except RetryError:
            return None  # shed to exhaustion: accounted, never accepted
        try:
            return router.result(rid, timeout=240.0)
        except TimeoutError:
            client_failures.append(f"client {tag} req {i}: no response")
            return None

    def steady_client(tag):
        i = 0
        while not stop_clients.is_set():
            one_request(tag, i)
            i += 1
            time.sleep(0.08)

    clients = [threading.Thread(target=steady_client, args=(t,),
                                daemon=True) for t in range(2)]
    try:
        _check(pump(lambda: len(router.healthy_replicas()) >= 2,
                    "2 replicas healthy", 180.0),
               "the serving fleet of 2 replicas came up on v1", failures)
        fleet.add_sampler(sample)
        for c in clients:
            c.start()
        _check(pump(lambda: len(router.completed) >= 5,
                    "first responses", 90.0),
               "closed-loop traffic completes", failures)
        _check(pump(lambda: reader.committed_records() >= 30,
                    "feedback committed", 90.0),
               "serving responses are landing in the durable feedback "
               "log", failures)

        # -- phase C: SIGKILL a serving replica MID-traffic ---------------
        # a burst outnumbering the fast replica's slot cap spills work
        # onto the slow victim (least-loaded dispatch otherwise starves
        # a straggler at low load — observed: 1683 vs 52 served), and
        # the straggler latency keeps it in-flight long enough for the
        # kill to land mid-request
        burst_threads = [
            threading.Thread(target=lambda i=i: one_request(100 + i, i),
                             daemon=True) for i in range(10)]
        for th in burst_threads:
            th.start()
        pump(lambda: router.inflight_on(serve_kill_rank) >= 1,
             "in-flight work on the serving victim", 30.0)
        with open(os.path.join(serve_elastic, "supervisor", "pids",
                               str(serve_kill_rank))) as f:
            victim_pid = int(f.read())
        os.kill(victim_pid, signal.SIGKILL)
        _check(pump(lambda: router.redispatched >= 1,
                    "redispatch after serving SIGKILL", 60.0),
               "the dead replica's in-flight requests were re-dispatched",
               failures)
        _check(pump(lambda: sup_s.relaunches.get(serve_kill_rank, 0) >= 1
                    and serve_kill_rank in router.healthy_replicas(),
                    "serving victim relaunched", 120.0),
               "the supervisor relaunched the SIGKILLed serving replica",
               failures)
        for th in burst_threads:
            th.join(timeout=240)

        # -- phase D: the trainer SIGKILL committed a shrink + rejoin -----
        decided_dir = os.path.join(trainer_elastic, "dearel", "elastic",
                                   "decided")

        def decided(n):
            try:
                with open(os.path.join(decided_dir, f"e{n}")) as f:
                    return json.load(f)
            except (OSError, ValueError):
                return None

        _check(pump(lambda: decided(2) is not None,
                    "trainer shrink+rejoin epochs", 150.0),
               "the trainer SIGKILL forced an elastic shrink and the "
               "relaunch rejoined (epoch 2 committed)", failures)
        rec1, rec2 = decided(1), decided(2)
        _check(isinstance(rec1, dict)
               and rec1.get("delta", {}).get("removed")
               == [trainer_kill_rank],
               f"epoch-1 record signs the trainer shrink ({rec1})",
               failures)
        _check(isinstance(rec2, dict)
               and rec2.get("delta", {}).get("added")
               == [trainer_kill_rank],
               f"epoch-2 record signs the rejoin ({rec2})", failures)

        # -- phase E: the version-advancement loop, twice -----------------
        # every time the trainer's published version bumps, execute the
        # PR-11 drain+backfill rolling swap so the fleet serves it
        def drains_of(r):
            return sum(1 for e in sup_s.events if e == ("drained", r))

        for round_no in (1, 2):
            want = round_no + 1  # v2, then v3
            _check(pump(lambda w=want: (W.latest_version(store) or 0) >= w,
                        f"v{want} published", 150.0),
                   f"the live trainer published v{want} from ingested "
                   "feedback", failures)
            for r in (0, 1):
                before = drains_of(r)
                write_capacity({"target_world": 2, "drain": [r]})
                ok = pump(lambda r=r, b=before: drains_of(r) > b,
                          f"serving rank {r} drained (round {round_no})",
                          90.0)
                _check(ok, f"serving rank {r} drained via the SIGTERM "
                       f"grace path (round {round_no})", failures)
                _check(pump(lambda r=r, w=want:
                            (router.fleet_versions().get(r) or 0) >= w,
                            f"rank {r} serving >= v{want}", 120.0),
                       f"backfilled serving rank {r} came up on "
                       f">= v{want}", failures)
        write_capacity({"target_world": 2})  # clear the stale drain hint
        _check(router.weight_swaps >= 2,
               f"the router observed the served version advance >= 2 "
               f"times (serve.weight_swaps={router.weight_swaps})",
               failures)
        _check(min_healthy[0] >= 1,
               "at least one replica stayed healthy through every "
               "rolling swap", failures)

        # -- phase G: poisoned publish -> canary verdict -> rollback ------
        # lift the deploy freeze one rung: the trainer's next cadenced
        # publish is v4, and the scheduled bad_version fault NaNs it on
        # the way out of the leader
        write_publish_cap(4)
        _check(pump(lambda: (W.latest_version(store) or 0) >= 4,
                    "v4 published", 150.0),
               "the trainer published v4 (the NaN-poisoned canary "
               "candidate)", failures)
        # canary deployment: roll ONLY rank 0 forward; rank 1 keeps
        # serving v3 as the baseline while the router splits traffic
        before = drains_of(0)
        write_capacity({"target_world": 2, "drain": [0]})
        _check(pump(lambda b=before: drains_of(0) > b,
                    "canary rank drained", 90.0),
               "the canary rank drained for the v4 rollout", failures)
        _check(pump(lambda: (router.fleet_versions().get(0) or 0) >= 4,
                    "canary rank on v4", 120.0),
               "the canary rank came back serving v4", failures)
        # clear the drain hint NOW: the policy dedups acted-on drain
        # victims until the hint stops listing them, and the rollback
        # below must drain rank 0 a second time — the verdict wait gives
        # the policy plenty of ticks to observe the cleared hint
        write_capacity({"target_world": 2})
        _check(pump(lambda: any(v == 4 and verdict == "FAIL"
                                for v, verdict in router.canary_verdicts),
                    "canary verdict on v4", 120.0),
               "the router's A/B verdict FAILed v4 on the load-time "
               "quality gauge", failures)
        _check(pump(lambda: W.rolled_back(store, 4),
                    "rollback marker", 30.0),
               "the FAIL verdict committed the first-writer-wins "
               "ROLLBACK.json marker for v4", failures)
        # the loser's drain — the PR-11 swap in reverse: the backfill
        # must land on the newest LIVE version (v3), never the dead v4
        before = drains_of(0)
        write_capacity({"target_world": 2, "drain": [0]})
        _check(pump(lambda b=before: drains_of(0) > b,
                    "rolled-back rank drained", 90.0),
               "the failed canary rank drained for the rollback",
               failures)
        _check(pump(lambda: router.fleet_versions().get(0) == 3,
                    "rollback backfill on v3", 120.0),
               "the rolled-back rank backfilled onto the last good "
               "version v3 (never the failed v4)", failures)
        write_capacity({"target_world": 2})  # clear the stale drain hint
        # lift the freeze exactly one rung: the next publish must mint
        # v5 — a FRESH number; the store-authoritative ladder never
        # reuses 4. The cap stays at 5 (not unlimited) so a fast box
        # can't keep minting versions between here and shutdown —
        # runaway publishes advance the trainer's compaction cut
        # (published[-3]) past the served versions' cursor windows and
        # destroy the freshness measurement below (observed: published
        # reached v10 and every freshness sample fell below the cut)
        write_publish_cap(5)
        _check(pump(lambda: (W.latest_version(store) or 0) >= 5,
                    "v5 minted past the rollback", 150.0),
               "the republish after the rollback minted v5 "
               "(numbering skips the dead version, never reuses it)",
               failures)

        # -- phase F: freeze the log, drain the cursor --------------------
        stop_clients.set()
        for c in clients:
            c.join(timeout=240)
        _check(pump(lambda: not router.open_requests(),
                    "all accepted requests answered", 120.0),
               "zero accepted-then-lost requests "
               f"(open={sorted(router.open_requests())})", failures)
        _check(not client_failures,
               f"no client timed out ({client_failures[:4]})", failures)
        sup_s.policy = None  # shutdown must not read as lost capacity
        sup_s.kill_all(signal.SIGTERM)  # drain: final feedback flush
        _check(pump(lambda: not sup_s.poll(), "serving fleet drained",
                    90.0),
               "the serving fleet drained cleanly (writers flushed)",
               failures)
        with open(stop_file, "w") as f:
            f.write("done")
        _check(pump(lambda: not sup_t.poll(), "trainer consensus exit",
                    150.0),
               "the trainer fleet drained the log and exited in lockstep",
               failures)
    finally:
        stop_clients.set()
        elapsed_s = time.monotonic() - t0
        sup_s.policy = None
        sup_s.kill_all(signal.SIGTERM)
        sup_t.kill_all(signal.SIGTERM)
        t_end = time.monotonic() + 60.0
        while (sup_s.poll() or sup_t.poll()) \
                and time.monotonic() < t_end:
            time.sleep(0.1)
        for sup in (sup_s, sup_t):
            if sup._procs:
                sup.kill_all(signal.SIGKILL)
        stats = router.stats()
        router.close()
        counters = T.get_tracer().counters()
        T.set_tracer(prev_tracer)

    bad_t = {r: rc for r, rc in sup_t._final_rc.items() if rc != 0}
    _check(not bad_t, f"trainer ranks exited clean ({bad_t})", failures)
    _check(sup_t.relaunches.get(trainer_kill_rank) == 1
           and all(n == 0 for r, n in sup_t.relaunches.items()
                   if r != trainer_kill_rank),
           f"exactly the SIGKILLed trainer rank was relaunched "
           f"({sup_t.relaunches})", failures)
    _check(sup_s.relaunches.get(serve_kill_rank, 0) == 1,
           f"exactly the SIGKILLed serving replica was relaunched "
           f"({sup_s.relaunches})", failures)

    # -- the exactly-once ledger: jax-free replay of the whole log --------
    # full=True: the one-shot audit needs the definitive frontier, not
    # the probe fast path (which stalls below torn-segment gaps between
    # discovery listings — observed: a stale pump-era reader audited 789
    # of 894 records)
    frontier = reader.frontier(full=True)
    audit = Cursor()
    records = []
    while True:
        got = reader.take(audit, frontier, 512)
        if not got:
            break
        records.append(got)
    flat = [r for chunk in records for r in chunk]
    ts_by_writer: dict[str, list[float]] = {}
    for r in flat:
        ts_by_writer.setdefault(r["writer"], []).append(float(r["ts"]))
    _check(audit.torn_segments >= 1,
           f"the injected torn segment was walked past "
           f"(torn_segments={audit.torn_segments})", failures)
    _check(audit.dedup_hits >= 1,
           f"the injected duplicate record was deduplicated "
           f"(dedup_hits={audit.dedup_hits})", failures)

    # newest verdict per trainer rank (churned ranks write one per life)
    finals: dict[int, dict] = {}
    for name in sorted(os.listdir(workdir)):
        if not (name.startswith("trainer_verdict_rank")
                and name.endswith(".json")):
            continue
        with open(os.path.join(workdir, name)) as f:
            v = json.load(f)
        prev = finals.get(int(v["rank"]))
        if prev is None or v["steps_seen"] >= prev["steps_seen"]:
            finals[int(v["rank"])] = v
    summary = {"passed": False, "workdir": workdir,
               "elapsed_s": round(elapsed_s, 1),
               "stats": stats, "finals": finals, "failures": failures}
    if sorted(finals) != [0, 1]:
        failures.append(f"expected final verdicts from trainer ranks 0-1, "
                        f"got {sorted(finals)}")
        return summary

    for r, v in sorted(finals.items()):
        ig = v["ingest"]
        _check(v["epoch"] >= 2 and v["members"] == [0, 1],
               f"trainer rank {r} ends at epoch >= 2, full membership "
               f"(epoch {v['epoch']}, members {v['members']})", failures)
        _check(v.get("lockstep"), f"trainer rank {r} finished in lockstep",
               failures)
        _check(ig["consumed_total"] == audit.consumed_total,
               f"rank {r} exactly-once count: records_trained "
               f"{ig['consumed_total']} == records_committed "
               f"{audit.consumed_total}", failures)
        _check(int(ig["checksum"]) == audit.checksum,
               f"rank {r} exactly-once checksum matches the log replay "
               "(no gaps, no dups, no reorders of the unique-record set)",
               failures)
        _check(ig["dedup_hits"] >= 1 and ig["torn_segments"] >= 1,
               f"rank {r} ingest absorbed the data faults (dedup "
               f"{ig['dedup_hits']}, torn {ig['torn_segments']})",
               failures)
        # zero training progress lost past the newest upload
        rstore = LocalObjectStore(os.path.join(remote_root, f"rank{r}"))
        from dear_pytorch_tpu.utils import checkpoint as _ck
        remote = _ck.remote_steps(rstore)
        _check(bool(remote) and v["final_step"] >= remote[0],
               f"rank {r} final step {v['final_step']} >= newest uploaded "
               f"checkpoint {remote[0] if remote else None}", failures)
    merged: dict = {}
    for v in finals.values():
        for k, n in v.get("counters", {}).items():
            merged[k] = merged.get(k, 0) + n
    _check(merged.get("cluster.reconfigs", 0) >= 1
           and merged.get("cluster.rejoins", 0) >= 1,
           "the trainer kill committed a shrink and a rejoin", failures)
    _check(merged.get("pipeline.reshards", 0) >= 2,
           "the ingest pipeline resharded through both transitions",
           failures)
    published = sorted(set().union(*(set(v["published"])
                                     for v in finals.values())))
    _check(len(published) >= target_versions,
           f"the trainer published >= {target_versions} versions "
           f"({published})", failures)

    # -- the canary/rollback ledger ---------------------------------------
    _check(canary_rolled == [4] and W.rolled_back(store, 4),
           f"exactly the poisoned v4 was canary-rolled-back "
           f"({canary_rolled})", failures)
    _check(4 in published and 5 in published
           and not W.rolled_back(store, 5)
           and W.latest_live_version(store) == max(published),
           "the post-rollback republish is live and the dead number "
           f"stays dead (published {published}, live "
           f"{W.latest_live_version(store)})", failures)
    prov = []
    for v in published:
        side = read_online_sidecar(store, v)
        prov.append(int((((side or {}).get("cursor")) or {})
                        .get("consumed_total", 0)))
    _check(all(a <= b for a, b in zip(prov, prov[1:])),
           f"sidecar cursor provenance is monotonic across the rollback "
           f"({dict(zip(published, prov))})", failures)

    # -- the quality-gate + retention ledger -------------------------------
    rej0 = finals[0].get("quality_rejected") or {}
    _check(sum(rej0.values()) >= 12,
           f"the never-restarted rank's quality gate rejected the full "
           f"12-record poison burst ({rej0})", failures)
    for kind in ("schema", "outlier", "oversize"):
        _check(merged.get(f"online.records_rejected_{kind}", 0) >= 1,
               f"poison shape '{kind}' hit its reject counter", failures)
    _check(merged.get("online.segments_compacted", 0) >= 1,
           "feedback retention compacted >= 1 segment mid-storm "
           f"(online.segments_compacted="
           f"{merged.get('online.segments_compacted', 0)})", failures)

    # -- the partition ledger: shard slices tile the union -----------------
    for r, v in sorted(finals.items()):
        CC.shard_union_balanced(v.get("shard_cursors") or {}, audit,
                                failures, f"trainer rank {r}")

    # -- feedback freshness: commit -> first version serving it -----------
    # for each version the fleet actually served, the oldest NEWLY
    # included record (per the cursor-provenance sidecar) waited
    # first_served - its append ts; the ceiling bounds the worst wait
    freshness = []
    served_versions = sorted(v for v in first_served if v >= 2)
    # compaction-aware index: the replay only holds records from each
    # writer's compaction cut up — the marker's consumed count is how
    # many older records were folded into the ledger, so absolute
    # per-writer positions shift down by it. A sample whose record fell
    # below the cut is unmeasurable (freshness lost to retention, by
    # design); the two-publish compaction lag keeps the NEWEST served
    # version's window above every cut.
    mk_off = {w: int((reader._compaction_marker(w) or {})
                     .get("consumed", 0)) for w in ts_by_writer}
    for v in served_versions:
        side = read_online_sidecar(store, v)
        prev_side = read_online_sidecar(store, v - 1)
        if side is None or side.get("cursor") is None:
            continue
        prev_writers = ((prev_side or {}).get("cursor") or {}) \
            .get("writers", {})
        for w, pos in (side["cursor"].get("writers") or {}).items():
            prev_c = int(prev_writers.get(w, {}).get("consumed", 0))
            if int(pos["consumed"]) <= prev_c:
                continue  # no new records from this writer in v
            ts_list = ts_by_writer.get(w, [])
            idx = prev_c - mk_off.get(w, 0)
            if 0 <= idx < len(ts_list):
                freshness.append(first_served[v] - ts_list[idx])
    fresh_s = max(freshness) if freshness else None
    _check(fresh_s is not None,
           f"freshness measurable for the served versions "
           f"({served_versions})", failures)
    fresh_ceil = float(os.environ.get("DEAR_CHAOS_ONLINE_FRESH_S", "300"))
    rps = len(router.completed) / max(elapsed_s, 1e-9)
    rps_floor = float(os.environ.get("DEAR_CHAOS_ONLINE_RPS", "0.2"))
    CC.slo_gate(
        os.path.join(workdir, "online_contract.json"),
        "requests_per_s", round(rps, 3),
        [{"metric": "feedback_freshness_s",
          "value": (round(fresh_s, 2) if fresh_s is not None
                    else float("nan"))},
         {"metric": "records_committed", "value": audit.consumed_total},
         {"metric": "records_trained",
          "value": finals[0]["ingest"]["consumed_total"]},
         {"metric": "versions_served", "value": len(served_versions)}],
        [f"requests_per_s={rps_floor}",
         f"feedback_freshness_s<={fresh_ceil}"],
        failures,
        f"bench_gate --slo holds the online contract ({rps:.2f} req/s "
        f">= {rps_floor}; freshness {fresh_s if fresh_s is None else round(fresh_s, 1)}s "
        f"<= {fresh_ceil:.0f}s)")

    summary.update({
        "passed": not failures,
        "requests_per_s": round(rps, 3),
        "feedback_freshness_s": (round(fresh_s, 2)
                                 if fresh_s is not None else None),
        "records_committed_unique": audit.consumed_total,
        "dedup_hits": audit.dedup_hits,
        "torn_segments": audit.torn_segments,
        "published": published,
        "served_versions": served_versions,
        "canary_verdicts": list(router.canary_verdicts),
        "rolled_back": canary_rolled,
        "weight_swaps": router.weight_swaps,
        "serve_counters": {k: v for k, v in sorted(counters.items())
                           if k.startswith(("serve.", "online."))},
        "failures": failures,
    })
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-fault recovery check (see module docstring)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--checkpoint-every", type=int, default=4)
    ap.add_argument("--workdir", type=str, default=None)
    ap.add_argument("--procs", type=int, default=1,
                    help="run the storm over N coordinated processes "
                         "(launcher env contract; rank-targeted faults)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic storm: SIGKILL one rank of a 3-rank "
                         "host-level cluster mid-run; survivors must "
                         "commit a smaller epoch and keep training, the "
                         "supervisor's relaunch must rejoin")
    ap.add_argument("--autoscale", action="store_true",
                    help="autoscaling service storm: capacity-up scale to "
                         "3 ranks, SIGKILL shrink + relaunch, spot-drain "
                         "planned shrink + backfill, steps/hour SLO gate, "
                         "and a cold start from the remote checkpoint tier")
    ap.add_argument("--multislice", action="store_true",
                    help="multi-slice hierarchical-training storm: a "
                         "2-slice x 4-rank fleet trains RS+AG over ICI "
                         "with a host-level DCN cross-slice exchange; "
                         "one WHOLE slice is SIGKILLed (must commit as "
                         "exactly one membership epoch), survivors "
                         "train degraded with the DCN leg renormalized "
                         "under a slice-targeted slow-link fault, and "
                         "the relaunched slice readmits as one epoch — "
                         "zero loss of progress past the newest upload")
    ap.add_argument("--multislice-flap", action="store_true",
                    help="degraded-mode DCN flap storm: a 2-slice fleet "
                         "in bounded-staleness mode absorbs a "
                         "sub-budget dcn_flap transient plus a dcn_slow "
                         "straggler with ZERO guard rollbacks, zero "
                         "membership churn, error-feedback residual "
                         "carry on the flapped slice, and a steps/hour "
                         "SLO gate")
    ap.add_argument("--multislice-degraded", action="store_true",
                    help="sustained-partition storm: a past-budget "
                         "dcn_partition walks the full ladder — skip, "
                         "escalate, DcnSelfEvict (exit 70, no SIGKILL), "
                         "slice-shaped shrink epoch, supervisor "
                         "relaunch, slice-gated rejoin")
    ap.add_argument("--serve", action="store_true",
                    help="serving storm: a supervised replica fleet "
                         "absorbs an overload burst (shed+retry), a "
                         "SIGKILL mid-traffic (zero dropped requests), "
                         "a checksum-corrupted response, a rolling "
                         "weight swap, and a capacity scale-up — gated "
                         "by a throughput floor + p99 ceiling")
    ap.add_argument("--online", action="store_true",
                    help="online continual-learning storm: a serving "
                         "fleet feeds a live trainer through the durable "
                         "feedback log while replicas AND a trainer rank "
                         "are SIGKILLed, a torn segment and a duplicate "
                         "record are injected, and the published version "
                         "advances through rolling swaps — gated on "
                         "exactly-once ingest accounting, zero "
                         "accepted-then-lost requests, zero training "
                         "progress lost, and a feedback-freshness "
                         "ceiling")
    ap.add_argument("--sdc", action="store_true",
                    help="SDC storm: a 3-rank fleet trains with the "
                         "fingerprint sentinel while one rank carries a "
                         "persistent padded-tail bit flip (checksums "
                         "blind); the vote must localize (rank, bucket), "
                         "the rollback replay must convict, the host "
                         "must quarantine-drain + probation-readmit, "
                         "and a serving fleet must catch a post-signing "
                         "token corruption via shadow replay into the "
                         "same ledger")
    ap.add_argument("--online-trainer", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one trainer rank
    ap.add_argument("--cold-start", action="store_true",
                    help=argparse.SUPPRESS)  # internal: scale-from-zero leg
    ap.add_argument("--serve-replica", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one serving replica
    ap.add_argument("--serve-publish", action="store_true",
                    help=argparse.SUPPRESS)  # internal: weight publisher
    ap.add_argument("--version", type=int, default=1,
                    help=argparse.SUPPRESS)  # --serve-publish version
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one storm rank
    args = ap.parse_args(argv)

    if args.worker and args.sdc:
        # one SDC-storm rank: the verdict / forensics file is the
        # output; a quarantine exits QUARANTINE_RC for the supervisor
        run_worker_sdc(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        return 0
    if args.sdc:
        summary = run_sdc(checkpoint_every=args.checkpoint_every,
                          workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "verdicts"}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.worker and args.serve_publish:
        summary = run_serve_publish(args.version, workdir=args.workdir)
        return 0 if summary["passed"] else 1
    if args.worker and args.online_trainer:
        # one online trainer rank: the verdict file is the output
        run_worker_online_trainer(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        return 0
    if args.online:
        summary = run_online(checkpoint_every=args.checkpoint_every,
                             workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k not in ("stats", "finals")}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.worker and args.serve_replica:
        # one serving replica: health/responses are the output; the
        # parent's router + gate do the asserting
        run_worker_serve_replica(workdir=args.workdir)
        return 0
    if args.serve:
        summary = run_serve(workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "stats"}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.worker and args.cold_start:
        summary = run_cold_start(workdir=args.workdir)
        return 0 if summary["passed"] else 1
    if args.worker and args.multislice:
        # one multislice rank: the verdict file is the output
        run_worker_multislice(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        return 0
    if args.multislice:
        summary = run_multislice(checkpoint_every=args.checkpoint_every,
                                 workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "finals"}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.multislice_flap:
        summary = run_multislice_flap(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "finals"}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.multislice_degraded:
        summary = run_multislice_degraded(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "finals"}))
        print("CHAOS CHECK " + ("PASSED" if summary["passed"]
                                else "FAILED"))
        return 0 if summary["passed"] else 1
    if args.worker and args.autoscale:
        # one autoscale rank: the verdict file is the output
        run_worker_autoscale(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        return 0
    if args.worker and args.elastic:
        # one elastic rank: the verdict file is the output, the parent
        # gate does the asserting — a clean exit just means "ran"
        run_worker_elastic(
            checkpoint_every=args.checkpoint_every, workdir=args.workdir)
        return 0
    if args.autoscale:
        summary = run_autoscale(checkpoint_every=args.checkpoint_every,
                                workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "finals"}))
    elif args.elastic:
        summary = run_elastic(3, checkpoint_every=args.checkpoint_every,
                              workdir=args.workdir)
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "verdicts"}))
    elif args.worker:
        summary = run_worker(steps=args.steps,
                             checkpoint_every=args.checkpoint_every,
                             workdir=args.workdir)
    elif args.procs > 1:
        summary = run_procs(args.procs, steps=args.steps,
                            checkpoint_every=args.checkpoint_every,
                            workdir=args.workdir)
        print(json.dumps(summary))
    else:
        summary = run(steps=args.steps,
                      checkpoint_every=args.checkpoint_every,
                      workdir=args.workdir)
        print(json.dumps(summary))
    print("CHAOS CHECK " + ("PASSED" if summary["passed"] else "FAILED"))
    return 0 if summary["passed"] else 1


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
    if "--worker" in sys.argv:
        # storm rank: the launcher env contract (coordinator address,
        # process id) drives backend.init(); each rank keeps its single
        # local CPU device — the 8-device emulation below is the
        # single-process world's shape, not the cluster's
        sys.exit(main())
    if any(a == "--procs" or a.startswith("--procs=") for a in sys.argv):
        # parent of the multi-process storm: pure process supervisor, no
        # jax in this process (the workers own the devices)
        sys.exit(main())
    if "--elastic" in sys.argv or "--autoscale" in sys.argv \
            or "--serve" in sys.argv or "--online" in sys.argv \
            or "--sdc" in sys.argv \
            or "--multislice-flap" in sys.argv \
            or "--multislice-degraded" in sys.argv:
        # parent of the elastic/autoscale/serving/online storms: likewise
        # jax-free — it drives launch/supervisor.py (+ the ScalePolicy /
        # capacity file, + the serving router) and reads the ranks'
        # verdict/health files and decision records
        sys.exit(main())
    # standalone single-process: emulate the 8-device CPU world the test
    # suite uses
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices",
                      int(os.environ.get("DEAR_NUM_CPU_DEVICES", "8")))
    sys.exit(main())
