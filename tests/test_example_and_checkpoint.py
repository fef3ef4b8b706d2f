"""End-to-end user-journey tests: the MNIST example converges (the
reference's convergence smoke test, SURVEY.md §4.3) and checkpoint/resume
round-trips exactly."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
from dear_pytorch_tpu.parallel import build_train_step
from dear_pytorch_tpu.utils import checkpoint as ckpt

from tests.test_dear_numerics import _data, _loss_fn, _mlp_params


def _load_example(filename: str = "mnist.py"):
    root = os.path.join(os.path.dirname(__file__), "..", "examples",
                        filename)
    name = filename.removesuffix(".py") + "_example"
    spec = importlib.util.spec_from_file_location(name, root)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_mnist_example_converges(mesh):
    m = _load_example()
    acc = m.main([
        "--data", "synthetic",
        "--epochs", "3", "--batch-size", "64", "--train-size", "2048",
        "--test-size", "512", "--lr", "0.05",
    ])
    assert acc > 0.9, acc


def test_mnist_example_learns_real_data(mesh):
    """REAL-data convergence through the full dear schedule (delayed
    update + sharded buffers + ShardedSampler input path): >= 90% held-out
    accuracy on scikit-learn's real handwritten digits. This is the test
    that fails if the delayed-update semantics break real learning —
    synthetic class-template data is too separable to falsify that
    (reference examples/mnist/pytorch_mnist.py:189-203 is the analogous
    real-MNIST demo)."""
    m = _load_example()
    acc = m.main([
        "--data", "real", "--epochs", "10", "--batch-size", "64",
        "--lr", "0.05", "--momentum", "0.9",
    ])
    assert acc >= 0.9, acc


def test_char_gpt_example_learns_real_text():
    """Causal-LM real-data convergence: the byte-level GPT must cut
    held-out bits/byte on the checked-in REAL English corpus from ~8.0
    (untrained) to < 5.5 in 100 quick steps through the dear schedule —
    below the ~5.6 of an English byte histogram, so it fails if the
    delayed-update semantics stop real sequence learning.

    Runs as a subprocess: the example asserts its own bar via exit code
    (main() < 5.5), and process isolation keeps a rare XLA:CPU allocator
    abort (SIGABRT mid-suite, not reproducible in isolation) from
    sinking the whole session."""
    import subprocess
    import sys

    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath(repo) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "char_gpt.py"),
         "--steps", "100", "--sample-chars", "0"],
        capture_output=True, text=True, timeout=800, env=env,
    )
    assert proc.returncode == 0, (proc.stdout[-500:], proc.stderr[-500:])
    assert "bits/byte" in proc.stdout


def test_checkpoint_roundtrip_and_plan_guard(mesh, tmp_path):
    params = _mlp_params(jax.random.PRNGKey(0))
    batches = [_data(jax.random.PRNGKey(100 + i)) for i in range(4)]
    opt = fused_sgd(lr=0.1, momentum=0.9)
    ts = build_train_step(_loss_fn, params, mesh=mesh, optimizer=opt,
                          threshold_mb=0.0008, donate=False)
    state = ts.init(params)
    for b in batches[:2]:
        state, _ = ts.step(state, b)

    d = str(tmp_path / "ckpts")
    ckpt.save_checkpoint(d, state, ts.plan)
    assert ckpt.latest_step(d) == 2

    template = ts.init(params)
    restored = ckpt.restore_checkpoint(d, ts, template=template)
    # restore lands ON the template's shardings (multi-host safe: no
    # host-replicated detour through device_get)
    def _check_sharding(r, t):
        assert r.sharding.is_equivalent_to(t.sharding, r.ndim), (
            r.sharding, t.sharding,
        )

    jax.tree.map(_check_sharding, restored, template)
    # exact roundtrip of every leaf (incl. sharded buffers and momentum)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        ),
        restored, state,
    )
    # ... and training continues identically from the restored state
    s1, m1 = ts.step(state, batches[2])
    s2, m2 = ts.step(restored, batches[2])
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-6)

    # a different plan must be refused (single fused bucket vs 3 buckets)
    ts2 = build_train_step(_loss_fn, params, mesh=mesh, optimizer=opt,
                           threshold_mb=None, donate=False)
    with pytest.raises(ValueError, match="plan"):
        ckpt.restore_checkpoint(d, ts2, template=ts2.init(params))


def test_production_example_runs_and_resumes(mesh, tmp_path):
    """examples/production.py: fsdp + guarded async checkpoints + metrics +
    pipeline end-to-end, then resume-from-latest continues the step count."""
    m = _load_example("production.py")

    wd = str(tmp_path / "run")
    m.main(["--steps", "12", "--checkpoint-every", "5", "--log-every", "3",
            "--workdir", wd])
    from dear_pytorch_tpu.utils import checkpoint as ckpt_mod
    from dear_pytorch_tpu.utils import read_metrics

    assert ckpt_mod.latest_step(os.path.join(wd, "ckpts")) == 10
    n_recs = len(read_metrics(os.path.join(wd, "metrics.jsonl")))
    assert n_recs >= 3

    m.main(["--steps", "18", "--checkpoint-every", "5", "--log-every", "3",
            "--workdir", wd])  # resumes from step 10
    assert ckpt_mod.latest_step(os.path.join(wd, "ckpts")) == 15
    recs = read_metrics(os.path.join(wd, "metrics.jsonl"))
    assert len(recs) > n_recs
    # replayed steps (11-12) must not leave duplicate step records behind
    steps = [r["step"] for r in recs if "step" in r]
    assert len(steps) == len(set(steps)), steps


def test_async_checkpoint_roundtrip(mesh, tmp_path):
    """save_checkpoint(asynchronous=True) returns before the write commits;
    after wait_for_checkpoints the checkpoint restores exactly, and the
    state mutating AFTER the async save must not corrupt what was saved
    (Orbax snapshots the arrays up front; donate=False here, but the
    snapshot guarantee is what this pins)."""
    params = _mlp_params(jax.random.PRNGKey(0))
    batches = [_data(jax.random.PRNGKey(200 + i)) for i in range(3)]
    opt = fused_sgd(lr=0.1, momentum=0.9)
    ts = build_train_step(_loss_fn, params, mesh=mesh, optimizer=opt,
                          threshold_mb=0.0008, donate=False)
    state = ts.init(params)
    state, _ = ts.step(state, batches[0])
    saved_buf0 = np.asarray(jax.device_get(state.buffers[0]))

    d = str(tmp_path / "async_ckpts")
    ckpt.save_checkpoint(d, state, ts.plan, asynchronous=True)
    # keep training while the write is in flight
    for b in batches[1:]:
        state, _ = ts.step(state, b)
    ckpt.wait_for_checkpoints()

    assert ckpt.latest_step(d) == 1
    restored = ckpt.restore_checkpoint(d, ts, template=ts.init(params))
    assert int(jax.device_get(restored.step)) == 1
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.buffers[0])), saved_buf0
    )


def test_wait_for_checkpoints_noop():
    ckpt.wait_for_checkpoints()  # nothing in flight: must not raise


def test_broadcast_helpers_single_process():
    import dear_pytorch_tpu as dear

    params = {"w": np.ones((3,))}
    out = dear.broadcast_parameters(params)
    assert out is params  # identity in single-process runs
    with pytest.raises(NotImplementedError):
        dear.broadcast_parameters(params, root_rank=1)


def test_checkpoint_roundtrip_with_model_state(mesh, tmp_path):
    """Non-empty model_state (BN stats) must survive restore with fields in
    the right slots (guards the orbax dict-ordering scramble)."""
    import flax.linen as nn
    import jax.numpy as jnp

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return nn.Dense(4)(x)

    model = TinyBN()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 12)) + 2.0
    y = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 4)
    variables = model.init({"params": jax.random.PRNGKey(2)}, x, train=False)
    params = variables["params"]
    mstate = {"batch_stats": variables["batch_stats"]}

    def loss_fn(p, ms, b):
        bx, by = b
        logits, new_state = model.apply(
            {"params": p, **ms}, bx, train=True, mutable=["batch_stats"]
        )
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.sum(logp * jax.nn.one_hot(by, 4), axis=-1)
        ), new_state

    ts = build_train_step(loss_fn, params, mesh=mesh, threshold_mb=None,
                          optimizer=fused_sgd(lr=0.05),
                          model_state_template=mstate, donate=False)
    state = ts.init(params, mstate)
    for _ in range(3):
        state, _ = ts.step(state, (x, y))

    d = str(tmp_path / "bn_ckpts")
    ckpt.save_checkpoint(d, state, ts.plan)
    restored = ckpt.restore_checkpoint(
        d, ts, template=ts.init(params, mstate)
    )
    assert int(jax.device_get(restored.step)) == 3  # step in the right slot
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        ),
        restored, state,
    )


def test_compressed_multi_axis_rejected():
    import jax.numpy as jnp

    devices = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh2d = jax.sharding.Mesh(devices, ("dp", "sp"))
    params = {"w": {"kernel": jnp.ones((4, 4))}}

    def loss_fn(p, b):
        return jnp.sum((b @ p["w"]["kernel"]) ** 2)

    with pytest.raises(ValueError, match="mean_axes"):
        build_train_step(
            loss_fn, params, mesh=mesh2d, mode="allreduce",
            axis_name=("dp", "sp"), mean_axes=("dp",),
            compressor="eftopk", density=0.5,
        )


@pytest.mark.parametrize("axis", ["tp", "pp", "pp-1f1b", "ep"])
def test_parallelism_example_smoke(axis):
    """examples/parallelism.py runs and improves for the model-sharding
    axes (dp/sp are covered end-to-end elsewhere)."""
    m = _load_example("parallelism.py")
    losses = m.main(["--axis", axis, "--steps", "4"])
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]  # actually trains, not just runs


def test_elastic_restore_world_resize(mesh, tmp_path):
    """Elastic recovery: a world=8 run's checkpoint resumes on a 4-device
    mesh (different padding, different shard sizes, different bucketing)
    and the continued loss trajectory matches the run that never resized —
    the global batch math is world-independent, so an exact restore of
    params + momentum must reproduce it."""
    params = _mlp_params(jax.random.PRNGKey(11))
    batches = [_data(jax.random.PRNGKey(700 + i)) for i in range(6)]
    opt = lambda: fused_sgd(lr=0.05, momentum=0.9)  # noqa: E731

    ts8 = build_train_step(_loss_fn, params, mesh=mesh, optimizer=opt(),
                          threshold_mb=0.0008, donate=False)
    state = ts8.init(params)
    for b in batches[:3]:
        state, _ = ts8.step(state, b)
    ckpt.save_checkpoint(str(tmp_path), state, ts8.plan)

    # the unresized continuation (ground truth)
    ref_losses = []
    for b in batches[3:]:
        state, m = ts8.step(state, b)
        ref_losses.append(float(m["loss"]))

    # resume on HALF the devices with a different fusion threshold
    mesh4 = jax.sharding.Mesh(
        np.asarray(jax.devices()[:4]).reshape(4), ("dp",)
    )
    ts4 = build_train_step(_loss_fn, params, mesh=mesh4, optimizer=opt(),
                          threshold_mb=0.002, donate=False)
    assert ckpt.plan_fingerprint(ts4.plan) != ckpt.plan_fingerprint(ts8.plan)
    restored = ckpt.elastic_restore(str(tmp_path), ts4)
    assert int(restored.step) == 3
    losses = []
    for b in batches[3:]:
        restored, m = ts4.step(restored, b)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)

    # sanity: the strict path still refuses the mismatched plan
    with pytest.raises(ValueError, match="plan"):
        ckpt.restore_checkpoint(str(tmp_path), ts4,
                                template=ts4.init(params))


def test_elastic_restore_keeps_span_padding(mesh, tmp_path, monkeypatch):
    """A checkpoint whose buckets are padded to XLA:TPU's spans (a TPU
    four's lane-dense 'dear' step, PR 41; forced here at world 4 alone)
    restores elastically onto world 8's flat plan and back: `plan_desc`
    records the padded lengths, so the rebuilt old plan matches the saved
    buffers, and parameters, momentum and step come back bit for bit."""
    import jax.numpy as jnp

    from dear_pytorch_tpu.ops import fusion as F

    monkeypatch.setattr(F, "spans_apply",
                        lambda platform, world: platform == "cpu"
                        and world == 4)
    params = _mlp_params(jax.random.PRNGKey(12))
    batches = [_data(jax.random.PRNGKey(800 + i)) for i in range(2)]
    mesh4 = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("dp",))

    def build(m):
        return build_train_step(
            _loss_fn, params, mesh=m, optimizer=fused_sgd(lr=0.05,
                                                          momentum=0.9),
            threshold_mb=0.0008, comm_dtype=jnp.bfloat16, donate=False)

    ts4, ts8 = build(mesh4), build(mesh)
    assert [b.padded_size for b in ts4.plan.buckets] == [
        F.bucket_length(b.size, 4, "cpu") for b in ts4.plan.buckets]
    assert [b.padded_size for b in ts8.plan.buckets] != [
        b.padded_size for b in ts4.plan.buckets]
    state = ts4.init(params)
    for b in batches:
        state, _ = ts4.step(state, b)
    ckpt.save_checkpoint(str(tmp_path / "w4"), state, ts4.plan)
    on8 = ckpt.elastic_restore(str(tmp_path / "w4"), ts8)
    jax.tree.map(np.testing.assert_array_equal, ts8.gather_params(on8),
                 ts4.gather_params(state))
    ckpt.save_checkpoint(str(tmp_path / "w8"), on8, ts8.plan)
    back = ckpt.elastic_restore(str(tmp_path / "w8"), ts4)
    jax.tree.map(np.testing.assert_array_equal,
                 (back.buffers, back.opt_state, back.step),
                 (state.buffers, state.opt_state, state.step))
    ts4.step(back, batches[0])


def test_generate_example_smoke(mesh, capsys):
    m = _load_example("generate.py")
    m.main(["--steps", "4", "--new-tokens", "3"])
    out = capsys.readouterr().out
    assert "greedy :" in out and "sampled:" in out
    assert "step 0: loss" in out
