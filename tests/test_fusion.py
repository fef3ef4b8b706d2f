"""Property tests for the fusion engine (reference had none — SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dear_pytorch_tpu.ops import fusion


def _params(rng, sizes):
    """A nested dict pytree with layer-grouped kernel/bias leaves."""
    tree = {}
    for i, n in enumerate(sizes):
        tree[f"layer{i:02d}"] = {
            "kernel": jnp.asarray(rng.standard_normal((n, 4)), jnp.float32),
            "bias": jnp.asarray(rng.standard_normal((4,)), jnp.float32),
        }
    return tree


def test_roundtrip_threshold(rng):
    params = _params(rng, [8, 16, 128, 3, 700, 9])
    plan = fusion.plan_by_threshold(params, world=8, threshold_mb=0.002)
    bufs = fusion.pack_all(params, plan)
    for b, buf in zip(plan.buckets, bufs):
        assert buf.shape == (b.padded_size,)
        assert b.padded_size % 8 == 0
        assert b.shard_size * 8 == b.padded_size
    out = fusion.unpack_all(bufs, plan)
    jax.tree.map(np.testing.assert_array_equal, out, params)


def test_threshold_none_single_bucket(rng):
    params = _params(rng, [8, 16, 32])
    plan = fusion.plan_by_threshold(params, world=4, threshold_mb=None)
    assert plan.num_buckets == 1
    assert plan.buckets[0].size == plan.total_size


def test_layer_atomicity(rng):
    # kernel+bias of one layer must never be split across buckets
    params = _params(rng, [100, 100, 100, 100])
    plan = fusion.plan_by_threshold(params, world=2, threshold_mb=0.0001)
    for b in plan.buckets:
        layers = {plan.leaves[i].layer for i in b.leaf_ids}
        for other in plan.buckets:
            if other.index != b.index:
                assert layers.isdisjoint(
                    {plan.leaves[i].layer for i in other.leaf_ids}
                )


def test_nearby_layers(rng):
    params = _params(rng, [4] * 10)
    plan = fusion.plan_by_nearby_layers(params, world=2, k=4)
    # 10 layers, k=4 -> buckets of 4,4,2 layers = 8,8,4 leaves
    assert [len(b.leaf_ids) for b in plan.buckets] == [8, 8, 4]
    plan1 = fusion.plan_by_nearby_layers(params, world=2, k=1)
    assert plan1.num_buckets == 10
    plan_all = fusion.plan_by_nearby_layers(params, world=2, k=-1)
    assert plan_all.num_buckets == 1


def test_flags(rng):
    params = _params(rng, [4] * 6)
    flags = [0, 0, 1, 0, 1, 0]  # split before layers 2 and 4
    plan = fusion.plan_by_flags(params, world=2, flags=flags)
    assert plan.num_buckets == 3
    assert [len(b.leaf_ids) // 2 for b in plan.buckets] == [2, 2, 2]
    with pytest.raises(ValueError):
        fusion.plan_by_flags(params, world=2, flags=[0, 1])


def test_offsets_contiguous(rng):
    params = _params(rng, [5, 7, 11])
    plan = fusion.plan_by_threshold(params, world=8, threshold_mb=None)
    b = plan.buckets[0]
    expect = 0
    for leaf_id, off in zip(b.leaf_ids, b.offsets):
        assert off == expect
        expect += plan.leaves[leaf_id].size
    assert b.size == expect


def test_make_plan_precedence(rng):
    params = _params(rng, [4] * 6)
    p = fusion.make_plan(params, 2, threshold_mb=1.0, nearby_layers=2)
    assert p.num_buckets == 3  # nearby wins over threshold
    p = fusion.make_plan(params, 2, nearby_layers=2, flags=[1] * 6)
    assert p.num_buckets == 6  # flags win over nearby


def test_pack_inside_jit(rng):
    params = _params(rng, [16, 8])
    plan = fusion.make_plan(params, world=4, threshold_mb=None)

    @jax.jit
    def f(p):
        bufs = fusion.pack_all(p, plan)
        return fusion.unpack_all(bufs, plan)

    out = f(params)
    jax.tree.map(np.testing.assert_array_equal, out, params)


def test_scalar_and_empty_edge_cases(rng):
    params = {"a": {"w": jnp.float32(3.0)}, "b": {"w": jnp.ones((3,))}}
    plan = fusion.make_plan(params, world=8, threshold_mb=None)
    assert plan.total_size == 4
    bufs = fusion.pack_all(params, plan)
    assert bufs[0].shape == (8,)  # padded 4 -> 8
    out = fusion.unpack_all(bufs, plan)
    assert np.asarray(out["a"]["w"]) == 3.0

    with pytest.raises(ValueError):
        fusion.make_plan(params, world=0)


def test_segment_ids_searchsorted_equivalence():
    """The train step derives per-element parameter ids via searchsorted
    over bucket offsets (no O(params) constant); it must agree with the
    explicit FusionPlan.segment_ids map everywhere, padding included."""
    import jax.numpy as jnp

    from dear_pytorch_tpu.ops import fusion as F

    params = {
        "a": {"kernel": jnp.zeros((5, 3)), "bias": jnp.zeros((3,))},
        "b": {"kernel": jnp.zeros((3, 7))},
    }
    plan = F.make_plan(params, world=8, nearby_layers=2)
    for b in plan.buckets:
        ref = plan.segment_ids(b.index)
        starts = jnp.asarray(b.offsets, jnp.int32)
        pos = jnp.arange(b.padded_size, dtype=jnp.int32)
        seg = jnp.searchsorted(starts, pos, side="right").astype(jnp.int32) - 1
        seg = jnp.where(pos < b.size, seg, len(b.leaf_ids))
        np.testing.assert_array_equal(np.asarray(seg), ref)
        assert b.pad > 0 or b.padded_size == b.size


# -- XLA:TPU's reduce-scatter spans (PR 41) ----------------------------------

#: GPT-2 124M's buckets at threshold 25 MB (the dp4 cell): unpadded ->
#: padded on a TPU four, each a whole number of equal spans
GPT2_DP4 = [(6_497_280, 6_553_600), (4_727_808, 4_767_744),
            (4_725_504, 4_767_744), (6_494_208, 6_553_600),
            (1_969_152, 1_998_848), (38_602_752, 38_879_232)]


@pytest.mark.parametrize("platform,world", [
    ("cpu", 4), ("cpu", 8), (None, 4), ("tpu", 1), ("tpu", 2), ("tpu", 8)])
def test_bucket_length_off_a_tpu_four_is_the_multiple_of_world(platform,
                                                               world):
    from dear_pytorch_tpu.comm.collectives import padded_length

    for n in (0, 1, 7, 1000, 16_385) + tuple(n for n, _ in GPT2_DP4):
        assert fusion.bucket_length(n, world, platform) == \
            padded_length(n, world)


@pytest.mark.parametrize("n,padded", GPT2_DP4)
def test_bucket_length_on_a_tpu_four_is_whole_spans(n, padded):
    assert fusion.bucket_length(n, 4, "tpu") == padded


def test_bucket_length_spans_are_the_fewest_that_hold_the_bucket():
    q, most = fusion._SPAN_QUANTUM, fusion._SPAN_QUANTA
    for n in np.unique(np.geomspace(1, 2e8, 400).astype(int)):
        padded = fusion.bucket_length(int(n), 4, "tpu")
        quanta = -(-int(n) // q)
        spans = -(-quanta // most)
        assert padded >= n and padded % q == 0
        assert padded // q % spans == 0 and padded // q // spans <= most
        assert padded - n < spans * q          # at most a quantum a span
        assert (padded // 4) % 128 == 0        # whole [., 128] rows a shard


def test_plans_off_a_tpu_four_keep_their_padding(rng):
    from dear_pytorch_tpu.comm.collectives import padded_length

    params = _params(rng, [8, 16, 128, 3, 700, 9])
    plan = fusion.plan_by_threshold(params, world=8, threshold_mb=0.002)
    assert [b.padded_size for b in plan.buckets] == [
        padded_length(b.size, 8) for b in plan.buckets]
    for platform in ("cpu", None, "tpu"):     # a TPU eight: no spans read
        assert fusion.rescale_plan(plan, 8, platform=platform) is plan


def test_plan_rebuilt_from_groups_keeps_the_spans(rng):
    params = _params(rng, [4000, 300, 70_000, 9])
    plan = fusion.plan_by_threshold(params, world=4, threshold_mb=0.5)
    tpu = fusion.rescale_plan(plan, 4, platform="tpu")
    spans = [fusion.bucket_length(b.size, 4, "tpu") for b in plan.buckets]
    assert [b.padded_size for b in tpu.buckets] == spans
    assert [b.leaf_ids for b in tpu.buckets] == [b.leaf_ids
                                                 for b in plan.buckets]
    assert fusion.rescale_plan(tpu, 4, platform="tpu") is tpu
    again = fusion.rescale_plan(tpu, 4, epoch=3, platform="tpu")
    assert again.epoch == 3
    assert [b.padded_size for b in again.buckets] == spans
    regrouped = fusion.plan_by_groups(params, 4, [[0, 1], [2, 3]])
    assert [b.padded_size for b in fusion.rescale_plan(
        regrouped, 4, platform="tpu").buckets] == [
        fusion.bucket_length(b.size, 4, "tpu") for b in regrouped.buckets]
    bufs = fusion.pack_all(params, tpu)
    assert [x.shape for x in bufs] == [(n,) for n in spans]
    jax.tree.map(np.testing.assert_array_equal,
                 fusion.unpack_all(bufs, tpu), params)


def test_lane_view_legs_round_trip_like_the_flat_ones(mesh, rng,
                                                      monkeypatch):
    """pack -> [n/128, 128] -> reduce-scatter -> all-gather -> unpack over
    the eight devices gives what the flat legs give: every element's sum
    over the devices, whatever the view."""
    from dear_pytorch_tpu.comm import collectives as C

    monkeypatch.setattr(fusion, "spans_apply", lambda platform, world: True)
    trees = [_params(np.random.default_rng(i), [300, 64, 1000])
             for i in range(8)]
    plan = fusion.plan_by_threshold(trees[0], world=8, threshold_mb=None)
    plan = fusion.rescale_plan(plan, 8, platform="cpu")
    (b,) = plan.buckets
    assert b.padded_size % (8 * C.LANES) == 0 and b.pad > 0
    stacked = jnp.stack([fusion.pack_all(t, plan)[0] for t in trees])
    flat = C.spmd_call(C.reduce_scatter, stacked, mesh=mesh)
    view = C.spmd_call(lambda x: C.reduce_scatter(C.lanes(x)).reshape(-1),
                       stacked, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(view), np.asarray(flat))
    full = C.spmd_call(lambda s: C.all_gather(C.lanes(s)).reshape(-1),
                       view, mesh=mesh)
    want = jax.tree.map(lambda *xs: sum(xs), *trees)
    for d in range(8):
        got = fusion.unpack_all([full[d]], plan)
        jax.tree.map(lambda a, w: np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-6, atol=1e-6), got, want)
