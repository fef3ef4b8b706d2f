"""Numerical equivalence of every schedule vs a single-device baseline.

The reference could only eyeball norms on a live cluster (test_comm.py) and
rely on MNIST convergence. Here we assert: DeAR (decoupled RS+AG, sharded
state), 'rsag', 'rb', and 'allreduce' schedules all reproduce plain
full-batch SGD to floating-point tolerance, step for step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.ops.fused_sgd import fused_sgd, from_optax
from dear_pytorch_tpu.parallel import build_train_step


def _mlp_params(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "dense1": {
            "kernel": jax.random.normal(k1, (12, 32)) * 0.1,
            "bias": jnp.zeros((32,)),
        },
        "dense2": {
            "kernel": jax.random.normal(k2, (32, 16)) * 0.1,
            "bias": jnp.zeros((16,)),
        },
        "out": {
            "kernel": jax.random.normal(k3, (16, 4)) * 0.1,
            "bias": jnp.zeros((4,)),
        },
    }


def _forward(params, x):
    h = jnp.tanh(x @ params["dense1"]["kernel"] + params["dense1"]["bias"])
    h = jnp.tanh(h @ params["dense2"]["kernel"] + params["dense2"]["bias"])
    return h @ params["out"]["kernel"] + params["out"]["bias"]


def _loss_fn(params, batch):
    x, y = batch
    logits = _forward(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(logp * jax.nn.one_hot(y, 4), axis=-1))


def _data(key, n=64):
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, 12))
    y = jax.random.randint(ky, (n,), 0, 4)
    return x, y


def _baseline(params, batches, lr=0.1, momentum=0.9, steps=5):
    """Plain full-batch SGD+momentum (torch semantics) on one device."""
    opt = fused_sgd(lr=lr, momentum=momentum)
    flat, treedef = jax.tree_util.tree_flatten(params)
    states = [opt.init(p.reshape(-1)) for p in flat]
    losses = []
    for b in batches[:steps]:
        loss, grads = jax.value_and_grad(_loss_fn)(params, b)
        losses.append(float(loss))
        gflat = jax.tree_util.tree_leaves(grads)
        new_flat = []
        for i, (p, g) in enumerate(zip(flat, gflat)):
            newp, states[i] = opt.update(
                g.reshape(-1), states[i], p.reshape(-1)
            )
            new_flat.append(newp.reshape(p.shape))
        flat = new_flat
        params = jax.tree_util.tree_unflatten(treedef, flat)
    return params, losses


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    params = _mlp_params(key)
    batches = [_data(jax.random.PRNGKey(100 + i)) for i in range(5)]
    ref_params, ref_losses = _baseline(params, batches)
    return params, batches, ref_params, ref_losses


@pytest.mark.parametrize("mode", ["dear", "allreduce", "rsag", "rb", "fsdp"])
def test_schedule_matches_baseline(mesh, world, problem, mode):
    params, batches, ref_params, ref_losses = problem
    ts = build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1, momentum=0.9),
        mesh=mesh,
        mode=mode,
        threshold_mb=0.0008,  # tiny threshold -> several buckets
        donate=False,
    )
    assert ts.plan.num_buckets >= 2
    state = ts.init(params)
    losses = []
    for b in batches:
        state, metrics = ts.step(state, b)
        losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    got = ts.gather_params(state)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        got,
        ref_params,
    )
    assert int(state.step) == 5


def test_dear_state_is_sharded(mesh, world, problem):
    params, batches, _, _ = problem
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, mode="dear", threshold_mb=None, donate=False
    )
    state = ts.init(params)
    buf = state.buffers[0]
    # global padded buffer, sharded across dp: each device holds 1/world
    shard_bytes = buf.addressable_shards[0].data.size
    assert shard_bytes == buf.size // world
    # optimizer state: no momentum configured -> empty tuples
    ts2 = build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1, momentum=0.9),
        mesh=mesh,
        mode="dear",
        threshold_mb=None,
        donate=False,
    )
    st2 = ts2.init(params)
    mom = st2.opt_state[0][0]
    assert mom.addressable_shards[0].data.size == mom.size // world


@pytest.mark.parametrize("mode,build,spans", [
    ("dear", dict(comm_dtype=jnp.bfloat16), True),
    ("fsdp", dict(gather_dtype=jnp.bfloat16), True),
    ("allreduce", dict(comm_dtype=jnp.bfloat16), False),
    ("dear", dict(comm_dtype=None), False),
    ("dear", dict(comm_dtype=jnp.bfloat16, compressor="eftopk",
                  density=0.25), False),
    ("dear", dict(comm_dtype=jnp.bfloat16, compressor="topk", density=0.25,
                  gtopk=True), False),
])
def test_span_layout_gives_the_same_step(problem, mode, build, spans,
                                         monkeypatch):
    """The layout a TPU four gets (buckets padded to XLA:TPU's spans, both
    legs over ``[n / 128, 128]``; PR 41), with `F.spans_apply` forced on
    four CPU devices: the same losses and parameters, bit for bit, as the
    flat layout. It reaches only the dense 'dear' / 'fsdp' legs over a bf16
    wire; the other schedules, an f32 wire and the compressed legs (whose k
    would grow with the padding) keep the flat plan."""
    params, batches, _, _ = problem
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("dp",))

    def run():
        ts = build_train_step(
            _loss_fn, params, optimizer=fused_sgd(lr=0.1, momentum=0.9),
            mesh=mesh, mode=mode, threshold_mb=0.0008, donate=False,
            **build)
        state, losses = ts.init(params), []
        for b in batches[:3]:
            state, metrics = ts.step(state, b)
            losses.append(float(metrics["loss"]))
        return ts, losses, ts.gather_params(state)

    flat_ts, flat_losses, flat_params = run()
    # the CPU mesh stands in for a TPU four (platform None: no spans)
    monkeypatch.setattr(F, "spans_apply",
                        lambda platform, world: platform == "cpu")
    ts, losses, got = run()
    if spans:
        assert [b.padded_size for b in ts.plan.buckets] == [
            F.bucket_length(b.size, 4, "cpu") for b in ts.plan.buckets]
        assert all(b.padded_size > f.padded_size
                   for b, f in zip(ts.plan.buckets, flat_ts.plan.buckets))
        rows = ts.plan.buckets[0].padded_size // 4 // 128
        text = ts.lower(ts.init(params), batches[0]).as_text()
        assert f"-> tensor<{rows}x128x" in text
    else:
        assert ts.plan == flat_ts.plan
    assert losses == flat_losses
    jax.tree.map(np.testing.assert_array_equal, got, flat_params)


def test_no_fusion_mode(mesh, world, problem):
    # nearby_layers=1: one bucket per layer (reference no-TF ablation)
    params, batches, ref_params, ref_losses = problem
    ts = build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1, momentum=0.9),
        mesh=mesh,
        mode="dear",
        nearby_layers=1,
        donate=False,
    )
    assert ts.plan.num_buckets == 3
    state = ts.init(params)
    for b in batches[:2]:
        state, metrics = ts.step(state, b)
    np.testing.assert_allclose(
        float(metrics["loss"]), ref_losses[1], rtol=1e-5, atol=1e-6
    )


#: What each schedule's legs issue PER BUCKET in the lowered step, as
#: (reduce_scatter, all_gather, all_reduce) — `parallel/schedules.py`'s
#: table read as collectives. 'rb' is a reduce and a broadcast, each one
#: all-reduce (`comm.collectives`); 'bytescheduler' is one RS+AG pair a
#: PARTITION, counted from `chunk_bounds` below (None here); 'fsdp' gathers in the forward and again in the backward for
#: every bucket the backward still needs (None: 1 to 2 a bucket), its
#: reduce-scatter being the gather's transpose; 'dear-fused' issues no XLA
#: reduction at all (its rings are Pallas kernels; off the TPU their
#: interpreter stands in with all-gathers, so that column is not pinned).
_LEGS = {
    "dear": (1, 1, 0),
    "dear-fused": (0, None, 0),
    "allreduce": (0, 0, 1),
    "rsag": (1, 1, 0),
    "rb": (0, 0, 2),
    "bytescheduler": (None, None, 0),
    "fsdp": (1, None, 0),
}


@pytest.mark.parametrize("nworld", [1, 4])
@pytest.mark.parametrize("mode", sorted(_LEGS))
def test_schedule_issues_its_declared_collectives(problem, mode, nworld):
    """Each schedule's lowered step holds exactly the collectives its legs
    declare per bucket, plus the one all-reduce of the scalar loss. At
    world 1 the same legs are traced over groups of one device: nothing
    crosses a device boundary."""
    import re

    from dear_pytorch_tpu.parallel.dear import MODES

    assert sorted(_LEGS) == sorted(MODES)
    params, batches, _, _ = problem
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:nworld]), ("dp",))
    # bucket 1 holds 528 padded floats: two partitions of 0.0012 MB
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, mode=mode, threshold_mb=0.0008,
        partition_mb=0.0012, donate=False)
    nb = ts.plan.num_buckets
    assert nb == 3
    txt = ts.lower(ts.init(params), batches[0]).as_text()
    ops = re.findall(
        r'"stablehlo\.(reduce_scatter|all_gather|all_reduce|all_to_all|'
        r'collective_permute|collective_broadcast)"\(.*?replica_groups = '
        r'dense<[^>]*> : tensor<(\d+)x(\d+)xi64>', txt)
    count = {k: sum(1 for o in ops if o[0] == k) for k in
             ("reduce_scatter", "all_gather", "all_reduce")}
    assert len(ops) == sum(count.values()), ops  # no other collective
    assert all((g, n) == ("1", str(nworld)) for _, g, n in ops), ops
    rs, ag, ar = _LEGS[mode]
    if mode == "bytescheduler":
        # one RS+AG pair a partition, not a bucket
        parts = sum(len(F.chunk_bounds(b.padded_size, 4, 0.0012))
                    for b in ts.plan.buckets)
        assert parts > nb
        assert (count["reduce_scatter"], count["all_gather"]) == (parts,
                                                                  parts)
    else:
        assert count["reduce_scatter"] == rs * nb
        if mode == "fsdp":
            assert nb <= count["all_gather"] <= 2 * nb
        elif ag is not None:
            assert count["all_gather"] == ag * nb
    assert count["all_reduce"] == ar * nb + 1  # + the loss's pmean


def test_removed_options_are_type_errors(mesh, problem):
    """`exclude_parts` (the time-breakdown ablation; the device trace's
    exposed_reduce_ms / exposed_gather_ms give that breakdown) and
    `opt_spec_fn` (no caller) are gone from the builder's signature."""
    import inspect

    params, _, _, _ = problem
    with pytest.raises(TypeError, match="exclude_parts"):
        build_train_step(_loss_fn, params, mesh=mesh, exclude_parts=())
    with pytest.raises(TypeError, match="opt_spec_fn"):
        build_train_step(_loss_fn, params, mesh=mesh, opt_spec_fn=None)
    kwonly = [p for p in inspect.signature(build_train_step)
              .parameters.values() if p.kind is p.KEYWORD_ONLY]
    assert len(kwonly) == 26
    with pytest.raises(ValueError, match="mode must be one of"):
        build_train_step(_loss_fn, params, mesh=mesh, mode="bogus")


def test_optax_adamw_on_shards(mesh, world, problem):
    import optax

    params, batches, _, _ = problem
    tx = optax.adamw(1e-3)
    ts = build_train_step(
        _loss_fn,
        params,
        optimizer=from_optax(tx),
        mesh=mesh,
        mode="dear",
        threshold_mb=0.0008,
        donate=False,
    )
    state = ts.init(params)
    for b in batches:
        state, m = ts.step(state, b)

    # parity vs full-tree optax on one device
    opt_state = tx.init(params)
    p = params
    for b in batches:
        g = jax.grad(_loss_fn)(p, b)
        upd, opt_state = tx.update(g, opt_state, p)
        p = optax.apply_updates(p, upd)
    got = ts.gather_params(state)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        ),
        got,
        p,
    )


@pytest.mark.parametrize("mode", ["dear", "fsdp", "allreduce"])
def test_clip_norm_matches_optax_global_clip(mesh, problem, mode):
    """clip_norm on (sharded) buckets == optax clip_by_global_norm on the
    full tree: shard-local square-norms psum to the exact global norm."""
    import optax

    params, batches, _, _ = problem
    clip = 0.05  # small enough to be active every step
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, mode=mode, threshold_mb=0.0008,
        optimizer=fused_sgd(lr=0.1, momentum=0.9), clip_norm=clip,
        donate=False,
    )
    state = ts.init(params)
    norms = []
    for b in batches:
        state, m = ts.step(state, b)
        norms.append(float(m["grad_norm"]))
    assert all(n > clip for n in norms), norms  # the clip was active

    tx = optax.chain(
        optax.clip_by_global_norm(clip),
        optax.trace(decay=0.9),  # torch-style momentum (trace), lr applied
        optax.scale(-0.1),
    )
    opt_state = tx.init(params)
    p = params
    for b in batches:
        g = jax.grad(_loss_fn)(p, b)
        upd, opt_state = tx.update(g, opt_state, p)
        p = optax.apply_updates(p, upd)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        ts.gather_params(state), p,
    )


def test_accum_clip_gather_dtype_compose(mesh, problem):
    """The three newest builder options stack: microbatch accumulation,
    global-norm clipping of the accumulated gradient, bf16 gathers — and
    still match the same configuration without accumulation."""
    params, batches, _, _ = problem
    common = dict(
        mesh=mesh, mode="dear", threshold_mb=0.0008, clip_norm=0.05,
        gather_dtype=jnp.bfloat16,
        optimizer=fused_sgd(lr=0.1, momentum=0.9), donate=False,
    )
    ts1 = build_train_step(_loss_fn, params, **common)
    ts4 = build_train_step(_loss_fn, params, accum_steps=4, **common)
    s1, s4 = ts1.init(params), ts4.init(params)
    for b in batches[:3]:
        s1, m1 = ts1.step(s1, b)
        s4, m4 = ts4.step(s4, b)
        assert float(m4["grad_norm"]) == pytest.approx(
            float(m1["grad_norm"]), rel=1e-2
        )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=1e-4
        ),
        s1.buffers, s4.buffers,
    )


def test_clip_norm_validation(mesh, problem):
    params, _, _, _ = problem
    with pytest.raises(ValueError, match="positive"):
        build_train_step(_loss_fn, params, mesh=mesh, clip_norm=0.0)
    with pytest.raises(ValueError, match="compression"):
        build_train_step(
            _loss_fn, params, mesh=mesh, mode="allreduce",
            compressor="eftopk", density=0.1, clip_norm=1.0,
        )


def test_optax_lr_schedule_on_shards(mesh, problem):
    """optax schedules (stateful count) work on sharded buffers: the 0-d
    count leaf is replicated by _opt_bucket_specs, per-element state shards
    with its bucket — parity vs full-tree optax on one device."""
    import optax

    params, batches, _, _ = problem
    tx = optax.sgd(optax.exponential_decay(0.1, 2, 0.5))
    ts = build_train_step(
        _loss_fn, params, optimizer=from_optax(tx), mesh=mesh, mode="dear",
        threshold_mb=0.0008, donate=False,
    )
    state = ts.init(params)
    for b in batches:
        state, _ = ts.step(state, b)

    opt_state = tx.init(params)
    p = params
    for b in batches:
        g = jax.grad(_loss_fn)(p, b)
        upd, opt_state = tx.update(g, opt_state, p)
        p = optax.apply_updates(p, upd)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        ts.gather_params(state), p,
    )


def test_comm_dtype_bf16(mesh, world, problem):
    params, batches, _, _ = problem
    ts = build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1),
        mesh=mesh,
        mode="dear",
        threshold_mb=None,
        comm_dtype=jnp.bfloat16,
        donate=False,
    )
    state = ts.init(params)
    state, m = ts.step(state, batches[0])
    assert np.isfinite(float(m["loss"]))


def test_donation(mesh, world, problem):
    params, batches, _, _ = problem
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, mode="dear", threshold_mb=None, donate=True
    )
    state = ts.init(params)
    state2, _ = ts.step(state, batches[0])
    # donated: the old state's buffers are invalidated
    assert state.buffers[0].is_deleted()
    assert not state2.buffers[0].is_deleted()


def test_model_state_batchnorm(mesh, world):
    """Non-trained model collections (BN running stats) are carried through
    the step, updated, and cross-replica averaged (the reference/DDP leave
    them replica-local; see DearState docstring)."""
    import flax.linen as nn

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            return nn.Dense(4)(x)

    model = TinyBN()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 12)) * 3.0 + 1.0
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
        loss = -jnp.mean(jnp.sum(logp * jax.nn.one_hot(by, 4), axis=-1))
        return loss, new_state

    ts = build_train_step(
        loss_fn,
        params,
        optimizer=fused_sgd(lr=0.05),
        mesh=mesh,
        mode="dear",
        threshold_mb=None,
        model_state_template=mstate,
        donate=False,
    )
    state = ts.init(params, mstate)
    losses = []
    for i in range(4):
        state, m = ts.step(state, (x, y))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    stats = state.model_state["batch_stats"]["BatchNorm_0"]
    mean = np.asarray(stats["mean"])
    assert np.abs(mean).sum() > 0  # running stats actually moved
    # Replica consistency: every device's copy of the nominally replicated
    # stats must be identical (guards the pmean in _sync_leaf; with
    # check_vma=False, divergence would otherwise be silent).
    shards = [np.asarray(s.data) for s in stats["mean"].addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    # ... and equal to the pmean of per-device batch stats, not any single
    # device's local value: devices saw different batch shards, so a missing
    # pmean could not produce shard-identical values matched here.
    assert len(shards) == 8


def test_init_rejects_unexpected_model_state(mesh):
    params = _mlp_params(jax.random.PRNGKey(0))
    ts = build_train_step(_loss_fn, params, mesh=mesh, threshold_mb=None,
                          donate=False)
    with pytest.raises(ValueError, match="model_state"):
        ts.init(params, {"batch_stats": {}})


def test_rng_seed_varies_per_step(mesh):
    """With rng_seed, loss_fn receives a fresh per-step key (dropout masks
    change across steps)."""
    params = {"w": {"kernel": jnp.ones((4, 4))}}

    def loss2(p, b, rng):
        mask = jax.random.bernoulli(rng, 0.5, (4,))
        return jnp.sum((b * mask) @ p["w"]["kernel"])

    ts = build_train_step(loss2, params, mesh=mesh, threshold_mb=None,
                          rng_seed=7, donate=False)
    state = ts.init(params)
    b = jnp.ones((8, 4))
    losses = []
    for _ in range(3):
        state, m = ts.step(state, b)
        losses.append(float(m["loss"]))
    # distinct dropout masks -> losses differ across steps with prob ~1
    assert len(set(losses)) > 1, losses


def test_init_does_not_alias_caller_arrays(mesh):
    """ts.init must COPY what it stages: a same-device device_put aliases,
    and the donated step would delete the caller's arrays (e.g. the
    batch_stats pytree the user still holds) on the first step."""
    import flax.linen as nn

    class TinyBN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = True):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return nn.Dense(4)(x)

    model = TinyBN()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 12))
    y = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 4)
    variables = model.init({"params": jax.random.PRNGKey(2)}, x, train=False)
    params, mstate = variables["params"], {"batch_stats": variables["batch_stats"]}

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
                          model_state_template=mstate, donate=True)
    state = ts.init(params, mstate)
    state, _ = ts.step(state, (x, y))
    # the caller's originals survive the donated step
    np.asarray(jax.tree.leaves(mstate)[0])
    np.asarray(jax.tree.leaves(params)[0])
    # and a SECOND independent training run can start from them
    state2 = ts.init(params, mstate)
    state2, m2 = ts.step(state2, (x, y))
    assert np.isfinite(float(m2["loss"]))


def test_init_does_not_alias_single_leaf_1d_params(mesh):
    """pack_all's reshape(-1) + 1-element concat are identity for a
    single-leaf 1-D unpadded bucket, so the packed buffer can BE the
    caller's array — init must unlink it before the donated step."""
    w = jnp.ones((8,))
    params = {"scale": {"w": w}}

    def loss_fn(p, b):
        return jnp.sum(p["scale"]["w"] * b[0])

    ts = build_train_step(loss_fn, params, mesh=mesh, mode="allreduce",
                          threshold_mb=None, donate=True,
                          optimizer=fused_sgd(lr=0.1))
    state = ts.init(params)
    batch = jnp.ones((8, 8))
    state, _ = ts.step(state, batch)
    np.asarray(w)  # caller's array survives
    state2 = ts.init(params)
    state2, m = ts.step(state2, batch)
    assert np.isfinite(float(m["loss"]))


def test_grad_accumulation_matches_full_batch(mesh, problem):
    """accum_steps=k (k scanned microbatches, one collective+update) must
    reproduce the single-pass step: grads average over microbatches exactly
    as the full-batch mean does."""
    params, batches, ref_params, ref_losses = problem
    ts = build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1, momentum=0.9),
        mesh=mesh,
        mode="dear",
        threshold_mb=0.0008,
        accum_steps=4,
        donate=False,
    )
    state = ts.init(params)
    losses = []
    for b in batches:
        state, m = ts.step(state, b)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    got = ts.gather_params(state)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        got,
        ref_params,
    )


def test_grad_accumulation_validates(mesh, problem):
    params, batches, _, _ = problem
    with pytest.raises(ValueError, match="accum_steps"):
        build_train_step(_loss_fn, params, mesh=mesh, accum_steps=0)
    ts = build_train_step(
        _loss_fn, params, mesh=mesh, threshold_mb=None, accum_steps=3,
        donate=False,
    )
    state = ts.init(params)
    # 64-sample batch over 8 devices = 8/device, not divisible by 3
    with pytest.raises(Exception, match="divisible by accum_steps"):
        ts.step(state, batches[0])


def test_grad_accumulation_rng_distinct_keys(mesh):
    """Each microbatch sees a distinct dropout key (folded from the step
    key), so accumulated stochastic losses differ from accum=1 on the same
    seed but remain finite and step-varying."""
    params = {"w": {"kernel": jnp.ones((4, 4))}}

    def loss2(p, b, rng):
        mask = jax.random.bernoulli(rng, 0.5, (4,))
        return jnp.sum((b * mask) @ p["w"]["kernel"])

    ts = build_train_step(loss2, params, mesh=mesh, threshold_mb=None,
                          rng_seed=7, accum_steps=2, donate=False)
    state = ts.init(params)
    b = jnp.ones((16, 4))
    losses = []
    for _ in range(3):
        state, m = ts.step(state, b)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert len(set(losses)) > 1, losses


def test_multi_step_equals_sequential_steps(mesh):
    """ts.multi_step(n) (one scanned program) must equal n sequential
    ts.step calls exactly — state and final metrics."""
    params = _mlp_params(jax.random.PRNGKey(0))
    batch = _data(jax.random.PRNGKey(50))
    opt = fused_sgd(lr=0.05, momentum=0.9)

    ts = build_train_step(_loss_fn, params, mesh=mesh, optimizer=opt,
                          threshold_mb=0.0008, donate=False)
    s_seq = ts.init(params)
    for _ in range(4):
        s_seq, m_seq = ts.step(s_seq, batch)

    s_scan = ts.init(params)
    s_scan, m_scan = ts.multi_step(4)(s_scan, batch)

    assert float(m_scan["loss"]) == pytest.approx(float(m_seq["loss"]),
                                                  rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        s_scan.buffers, s_seq.buffers,
    )


def test_fused_adamw_matches_torch():
    """fused_adamw must reproduce torch.optim.AdamW exactly (the fused-path
    generalization the reference lacks — its fused path is SGD-only,
    dear/dear_dopt.py:310-336)."""
    import torch

    from dear_pytorch_tpu.ops.fused_sgd import fused_adamw

    rng = np.random.RandomState(0)
    p0 = rng.randn(257).astype(np.float32)  # odd length: no shape luck
    grads = [rng.randn(257).astype(np.float32) for _ in range(6)]
    lr, betas, eps, wd = 1e-2, (0.9, 0.999), 1e-8, 0.1

    tp = torch.nn.Parameter(torch.tensor(p0))
    topt = torch.optim.AdamW([tp], lr=lr, betas=betas, eps=eps,
                             weight_decay=wd)
    opt = fused_adamw(lr=lr, betas=betas, eps=eps, weight_decay=wd)
    jp = jnp.asarray(p0)
    st = opt.init(jp)
    for g in grads:
        tp.grad = torch.tensor(g)
        topt.step()
        jp, st = opt.update(jnp.asarray(g), st, jp)
        # torch's foreach kernels contract FMAs differently, so agreement
        # is to f32 rounding (observed <=1 ULP/step drift), not bit-exact
        np.testing.assert_allclose(
            np.asarray(jp), tp.detach().numpy(), rtol=1e-5, atol=1e-6
        )


def test_adamw_dear_schedule_matches_single_device(mesh, world):
    """The sharded dear schedule with fused_adamw (Adam state sharded with
    the params — ZeRO-1 where it matters most, state being 2x params) must
    equal a single-device AdamW loop step for step."""
    from dear_pytorch_tpu.ops.fused_sgd import fused_adamw

    params = _mlp_params(jax.random.PRNGKey(3))
    batches = [_data(jax.random.PRNGKey(200 + i)) for i in range(4)]
    mk = lambda: fused_adamw(lr=1e-2, weight_decay=0.05)  # noqa: E731

    # single-device reference: flat per-leaf updates
    opt = mk()
    flat, treedef = jax.tree_util.tree_flatten(params)
    states = [opt.init(p.reshape(-1)) for p in flat]
    ref_losses = []
    cur = params
    for b in batches:
        loss, grads = jax.value_and_grad(_loss_fn)(cur, b)
        ref_losses.append(float(loss))
        gflat = jax.tree_util.tree_leaves(grads)
        new_flat = []
        for i, (p, g) in enumerate(zip(flat, gflat)):
            newp, states[i] = opt.update(g.reshape(-1), states[i],
                                         p.reshape(-1))
            new_flat.append(newp.reshape(p.shape))
        flat = new_flat
        cur = jax.tree_util.tree_unflatten(treedef, flat)

    ts = build_train_step(
        _loss_fn, params, optimizer=mk(), mesh=mesh, mode="dear",
        threshold_mb=0.0008, donate=False,
    )
    assert ts.plan.num_buckets >= 2
    state = ts.init(params)
    losses = []
    for b in batches:
        state, m = ts.step(state, b)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        ts.gather_params(state), cur,
    )


def test_lamb_sharded_trust_ratios_exact(mesh, world):
    """fused_lamb on the dear schedule: per-parameter trust ratios must be
    EXACT even though every parameter spans shard boundaries (world devices
    each own 1/world of each bucket). Pinned against a per-leaf
    single-device LAMB written directly from the paper."""
    from dear_pytorch_tpu.ops.fused_sgd import fused_lamb

    lr, b1, b2, eps, wd = 1e-2, 0.9, 0.999, 1e-6, 0.05
    params = _mlp_params(jax.random.PRNGKey(5))
    batches = [_data(jax.random.PRNGKey(300 + i)) for i in range(4)]

    # single-device reference: leaf-shaped state, python floats for norms
    cur = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    m_tree = jax.tree.map(np.zeros_like, cur)
    v_tree = jax.tree.map(np.zeros_like, cur)
    ref_losses = []
    for t, b in enumerate(batches, start=1):
        loss, grads = jax.value_and_grad(_loss_fn)(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), cur), b
        )
        ref_losses.append(float(loss))
        grads = jax.tree.map(lambda g: np.asarray(g, np.float64), grads)

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / (1 - b1 ** t)
            vh = v / (1 - b2 ** t)
            u = mh / (np.sqrt(vh) + eps) + wd * p
            wn, un = np.linalg.norm(p), np.linalg.norm(u)
            trust = wn / max(un, 1e-12) if (wn > 0 and un > 0) else 1.0
            return p - lr * trust * u, m, v

        flat_p, treedef = jax.tree_util.tree_flatten(cur)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(m_tree)
        flat_v = jax.tree_util.tree_leaves(v_tree)
        out = [upd(p, g, m, v) for p, g, m, v
               in zip(flat_p, flat_g, flat_m, flat_v)]
        cur = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        m_tree = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        v_tree = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])

    ts = build_train_step(
        _loss_fn, params,
        optimizer=fused_lamb(lr=lr, betas=(b1, b2), eps=eps,
                             weight_decay=wd),
        mesh=mesh, mode="dear", threshold_mb=0.0008, donate=False,
    )
    assert ts.plan.num_buckets >= 2
    state = ts.init(params)
    losses = []
    for b in batches:
        state, mtr = ts.step(state, b)
        losses.append(float(mtr["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        ts.gather_params(state), cur,
    )


def test_lamb_works_under_fsdp(mesh, world):
    """The layerwise (segment-metadata) update path must compose with the
    fsdp schedule too — grads there are already shards from the AD
    transpose, and dear-vs-fsdp numerics must agree."""
    from dear_pytorch_tpu.ops.fused_sgd import fused_lamb

    params = _mlp_params(jax.random.PRNGKey(6))
    batches = [_data(jax.random.PRNGKey(400 + i)) for i in range(3)]
    mk = lambda: fused_lamb(lr=1e-2, weight_decay=0.05)  # noqa: E731

    runs = {}
    for mode in ("dear", "fsdp"):
        ts = build_train_step(
            _loss_fn, params, optimizer=mk(), mesh=mesh, mode=mode,
            threshold_mb=0.0008, donate=False,
        )
        state = ts.init(params)
        losses = []
        for b in batches:
            state, m = ts.step(state, b)
            losses.append(float(m["loss"]))
        runs[mode] = losses
    np.testing.assert_allclose(runs["dear"], runs["fsdp"],
                               rtol=1e-6, atol=1e-7)


def test_multi_step_does_not_stack_state(mesh):
    """The scanned n-step program must carry ONE state through the loop,
    not stack per-step buffers: its temp memory stays within a constant
    factor of the single-step program's (a scan that accumulated state
    would grow ~n-fold)."""
    params = _mlp_params(jax.random.PRNGKey(0))
    batch = _data(jax.random.PRNGKey(50))
    ts = build_train_step(
        _loss_fn, params, mesh=mesh,
        optimizer=fused_sgd(lr=0.05, momentum=0.9),
        threshold_mb=0.0008, donate=False,
    )
    state = ts.init(params)

    def temp_bytes(compiled):
        return compiled.memory_analysis().temp_size_in_bytes

    one = temp_bytes(ts.lower(state, batch).compile())
    eight = temp_bytes(ts.multi_step(8).lower(state, batch).compile())
    assert eight < 3 * max(one, 1), (one, eight)
