"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on the v5e trace the tree still holds (one chip, ResNet-50,
round 4: ten `jit_device_step` runs of about 27.6 ms)."""

import pathlib

import pytest

from perfbench import xplane

ROOT = pathlib.Path(__file__).resolve().parents[2]
RECORDED = (ROOT / "perf/onchip_r04/trace/plugins/profile/"
            "2026_07_31_04_34_37/vm.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    assert xplane.length([(0, 2), (1, 3), (10, 11)]) == 4


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(-5, 1), (9, 20)], [(1, 9)]),
    ([(0, 2), (4, 6)], [(1, 5)], [(0, 1), (5, 6)]),
    ([(0, 2)], [], [(0, 2)]),
])
def test_subtract(a, b, want):
    assert xplane.subtract(a, b) == want


def test_gaps_and_clip():
    assert xplane.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xplane.clip([(0, 5), (7, 9), (20, 30)], 3, 8) == [(3, 5), (7, 8)]


def _op(name, start, end, text=None):
    return xplane.Op(name, text or f"%{name} = f32[] op()", start, end)


def _device(ops, async_ops, window=(0, 100)):
    return xplane.Device(0, (_op("jit_step", *window),), tuple(ops),
                         tuple(async_ops))


@pytest.mark.parametrize("compute, want", [
    ((10, 40), 0.0),     # hidden: compute covers the whole collective
    ((10, 25), 10.0),    # half hidden
    ((50, 60), 20.0),    # exposed: nothing else runs meanwhile
])
def test_exposed_collective_hidden_half_hidden_exposed(compute, want):
    dev = _device(ops=[_op("fusion.1", *compute)],
                  async_ops=[_op("all-gather-start.3", 15, 35)])
    assert xplane.length(dev.exposed_collectives()) == want


def test_a_blocking_done_on_the_sync_line_is_exposed_not_compute():
    dev = _device(
        ops=[_op("fusion.1", 0, 20), _op("all-reduce-done.2", 20, 30),
             _op("fusion.4", 30, 50)],
        async_ops=[_op("all-reduce-start.2", 10, 30)])
    assert xplane.union(dev.exposed_collectives()) == [(20, 30)]
    # operand mentions do not make an op a collective
    assert not _op("fusion.9", 0, 1,
                   "%fusion.9 = f32[] fusion(%all-gather-done.3)"
                   ).is_collective


def test_busy_idle_and_the_breakdown_on_made_up_ops():
    dev = _device(
        ops=[_op("fusion.1", 0, 40, "%fusion.1 = f32[] fusion(), kind=kLoop"),
             _op("fusion.2", 50, 90,
                 "%fusion.2 = f32[] fusion(), kind=kOutput")],
        async_ops=[_op("copy-start.1", 35, 45)])
    assert xplane.length(dev.busy) == 85
    assert xplane.top_device_ops(dev) == [["fusion:kLoop", 40e-9],
                                          ["fusion:kOutput", 40e-9]]
    host = (_op("dispatch", 44, 49), _op("wait", 49, 200))
    assert xplane.idle_gaps_by_host(dev, host) == [
        ["in-program/wait", 10e-9], ["in-program/dispatch", 5e-9]]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.is_file():
        pytest.skip(f"{RECORDED} is not in the tree")
    return xplane.load(RECORDED)


def test_recorded_trace_has_ten_programs_of_27_6_ms(recorded):
    (dev,) = recorded.devices
    assert len(dev.modules) == 10
    assert all(m.name.startswith("jit_device_step") for m in dev.modules)
    for m in dev.modules:
        assert (m.end - m.start) * 1e-6 == pytest.approx(27.6, abs=0.1)
    assert len(dev.ops) == 32060 and len(dev.async_ops) == 12430


def test_recorded_trace_busy_plus_idle_is_the_window(recorded):
    dev = recorded.devices[0]
    lo, hi = dev.window
    busy = xplane.length(dev.busy)
    idle = xplane.length(xplane.gaps(dev.busy, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    busy_s, window_s = recorded.busy_and_window_s()
    assert window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0.99 < busy_s / window_s <= 1.0     # one chip, nothing to wait for
    # world 1: no collective in the program, so none exposed
    assert xplane.length(dev.exposed_collectives()) == 0.0


def test_recorded_trace_breakdown(recorded):
    dev = recorded.devices[0]
    top = xplane.top_device_ops(dev)
    assert len(top) == 10 and top[0][0] == "fusion:kOutput"
    assert sum(s for _, s in top) <= recorded.busy_and_window_s()[0]
    gaps = xplane.idle_gaps_by_host(dev, recorded.host_spans)
    assert all(name.endswith("/-") for name, _ in gaps)  # no harness spans
