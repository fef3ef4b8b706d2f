"""The 'fsdp' (ZeRO-3) schedule: re-gather-in-backward via AD transpose.

Exact loss/param parity with the other schedules is covered by the
parametrized baseline test in test_dear_numerics.py; here we check the
structural claims: the backward pass contains a SECOND per-bucket gather
(rematerialized by the named checkpoint policy instead of keeping full
params live), the reduce-scatter appears as the gather's transpose, the
gather_dtype cast halves communicated bytes, and composition with
accumulation / validation of incompatible options.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
from dear_pytorch_tpu.parallel import build_train_step

from test_dear_numerics import _data, _loss_fn, _mlp_params


def _count(text: str, needle: str) -> int:
    return text.count(needle)


@pytest.fixture(scope="module")
def problem():
    params = _mlp_params(jax.random.PRNGKey(0))
    batch = _data(jax.random.PRNGKey(100))
    return params, batch


def _build(params, mesh, mode, **kw):
    return build_train_step(
        _loss_fn,
        params,
        optimizer=fused_sgd(lr=0.1, momentum=0.9),
        mesh=mesh,
        mode=mode,
        threshold_mb=0.0008,  # several buckets
        donate=False,
        **kw,
    )


def test_fsdp_regathers_in_backward(mesh, problem):
    """Emitted (StableHLO) program: 'dear' gathers each bucket once; 'fsdp'
    re-gathers in backward every bucket whose weights the backward consumes
    (all but the input layer's, whose dL/dx is never needed), same number of
    reduce-scatters (the AD transpose of the gather), plus the remat CSE
    barrier that keeps XLA from folding the re-gathers away. (CPU XLA
    expands the barrier early and CSEs anyway; TPU expands it after
    scheduling, so the memory benefit is a device-side property.)"""
    params, batch = problem
    ts_dear = _build(params, mesh, "dear")
    ts_fsdp = _build(params, mesh, "fsdp")
    assert ts_fsdp.plan.num_buckets == ts_dear.plan.num_buckets >= 2
    nb = ts_fsdp.plan.num_buckets

    hlo_dear = ts_dear.lower(ts_dear.init(params), batch).as_text()
    hlo_fsdp = ts_fsdp.lower(ts_fsdp.init(params), batch).as_text()
    assert _count(hlo_dear, "stablehlo.all_gather") == nb
    assert _count(hlo_dear, "stablehlo.reduce_scatter") == nb
    assert _count(hlo_fsdp, "stablehlo.reduce_scatter") == nb
    assert _count(hlo_fsdp, "stablehlo.all_gather") == 2 * nb - 1
    assert _count(hlo_fsdp, "stablehlo.optimization_barrier") >= 1


def test_fsdp_state_sharded_and_steps(mesh, world, problem):
    params, batch = problem
    ts = _build(params, mesh, "fsdp")
    state = ts.init(params)
    buf = state.buffers[0]
    assert buf.addressable_shards[0].data.size == buf.size // world
    state, m = ts.step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_fsdp_gather_dtype_bf16(mesh, problem):
    """gather_dtype=bf16: the gather AND its transposed reduce-scatter move
    bf16; masters stay f32 and training still converges on the quadratic."""
    params, batch = problem
    ts = _build(params, mesh, "fsdp", gather_dtype=jnp.bfloat16)
    hlo = ts.lower(ts.init(params), batch).as_text()
    assert "bf16" in hlo
    state = ts.init(params)
    losses = []
    for _ in range(5):
        state, m = ts.step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert state.buffers[0].dtype == jnp.float32


def test_dear_gather_dtype_bf16(mesh, problem):
    params, batch = problem
    ts = _build(params, mesh, "dear", gather_dtype=jnp.bfloat16)
    state = ts.init(params)
    state, m = ts.step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_fsdp_with_accumulation(mesh, problem):
    """fsdp x accum_steps: every microbatch re-gathers; grads accumulate in
    f32 SHARDS (cheaper than full trees); parity with accum=1."""
    params, batch = problem
    ts1 = _build(params, mesh, "fsdp")
    ts4 = _build(params, mesh, "fsdp", accum_steps=4)
    s1, s4 = ts1.init(params), ts4.init(params)
    for _ in range(3):
        s1, m1 = ts1.step(s1, batch)
        s4, m4 = ts4.step(s4, batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        s1.buffers, s4.buffers,
    )


def test_fsdp_matches_dear_on_two_axis_mesh(problem):
    """fsdp over a 2-D ('dp','sp')-style mesh: the gather/RS-transpose span
    BOTH axes (ZeRO degree = product) and match the dear schedule
    step-for-step."""
    devices = jax.devices()
    mesh2 = jax.sharding.Mesh(
        np.asarray(devices[:8]).reshape(2, 4), ("dp", "sp")
    )
    params, batch = problem
    common = dict(
        optimizer=fused_sgd(lr=0.1, momentum=0.9), mesh=mesh2,
        axis_name=("dp", "sp"), threshold_mb=0.0008, donate=False,
    )
    ts_d = build_train_step(_loss_fn, params, mode="dear", **common)
    ts_f = build_train_step(_loss_fn, params, mode="fsdp", **common)
    sd, sf = ts_d.init(params), ts_f.init(params)
    for _ in range(3):
        sd, md = ts_d.step(sd, batch)
        sf, mf = ts_f.step(sf, batch)
    assert float(md["loss"]) == pytest.approx(float(mf["loss"]), rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        sd.buffers, sf.buffers,
    )


def test_fsdp_option_validation(mesh, problem):
    params, _ = problem
    with pytest.raises(ValueError, match="comm_dtype"):
        _build(params, mesh, "fsdp", comm_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="gather_dtype"):
        _build(params, mesh, "allreduce", gather_dtype=jnp.bfloat16)
