"""The percentile, the one-in-flight loop on a fake step, and the window's
arithmetic."""

import numpy as np
import pytest

from perfbench import steploop


@pytest.mark.parametrize("q", [0, 50, 95, 100])
def test_percentile_matches_numpy(q):
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    assert steploop.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        steploop.percentile([], 95)


class FakeDevice:
    """A device that runs one step at a time, each ``step_s`` long, on a
    clock the test owns. Dispatch costs the host ``dispatch_s``."""

    def __init__(self, step_s, dispatch_s):
        self.now, self.free_at = 0.0, 0.0
        self.step_s, self.dispatch_s = step_s, dispatch_s
        self.dispatched, self.order = 0, []

    def clock(self):
        return self.now

    def step(self, state, batch):
        self.now += self.dispatch_s
        start = max(self.now, self.free_at)
        self.free_at = start + self.step_s
        self.dispatched += 1
        self.order.append(("dispatch", self.dispatched))
        return state + 1, (self.dispatched, self.free_at)

    def wait(self, handle):
        index, ready_at = handle
        self.now = max(self.now, ready_at)
        self.order.append(("wait", index))
        return float(index)


def test_one_step_stays_in_flight_and_the_device_never_waits():
    dev = FakeDevice(step_s=0.1, dispatch_s=0.01)
    state, rec = steploop.one_in_flight(
        dev.step, dev.wait, 0, None, clock=dev.clock,
        stop=lambda n, now, t0: n >= 5)
    # step i+1 is dispatched before step i is waited for
    assert dev.order[:4] == [("dispatch", 1), ("dispatch", 2), ("wait", 1),
                             ("dispatch", 3)]
    assert state == dev.dispatched == 6
    assert rec["attempted"] == 5 and len(rec["done"]) == 5
    # the losses are those of steps 1..6, the drained one last
    assert rec["losses"] == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    # back to back on the device: completions are one step apart although
    # every dispatch costs the host 10 ms
    assert np.diff(rec["done"]) == pytest.approx([0.1] * 4)
    assert rec["dispatch_s"] == pytest.approx([0.01] * 5)


def test_stop_by_seconds_counts_steps_dispatched_in_the_window():
    dev = FakeDevice(step_s=0.125, dispatch_s=0.0)
    _, rec = steploop.one_in_flight(
        dev.step, dev.wait, 0, None, clock=dev.clock,
        stop=lambda n, now, t0: now - t0 >= 1.0)
    assert len(rec["done"]) == 8 and rec["attempted"] == 8


def test_spans_wrap_dispatch_and_wait():
    import contextlib

    seen = []

    @contextlib.contextmanager
    def span(name):
        seen.append(name)
        yield

    dev = FakeDevice(step_s=0.1, dispatch_s=0.0)
    steploop.one_in_flight(dev.step, dev.wait, 0, None, clock=dev.clock,
                           stop=lambda n, now, t0: n >= 2, span=span)
    assert seen == ["dispatch", "wait", "dispatch", "wait"]


def test_window_metrics_rate_is_all_work_over_all_time():
    done = [10.0, 10.1, 10.2, 10.5, 10.6]      # one slow step of 300 ms
    m = steploop.window_metrics(done, tokens_per_step=1000, chips=4)
    assert m["tokens_per_s_per_chip"] == pytest.approx(4 * 1000 / 0.6 / 4)
    assert m["step_ms_median"] == pytest.approx(100.0)
    assert m["step_ms_max"] == pytest.approx(300.0)
    assert m["step_ms_p95"] == pytest.approx(
        np.percentile([100, 100, 300, 100], 95))
    assert m["intervals"] == 4


def test_window_too_short_raises():
    with pytest.raises(ValueError):
        steploop.window_metrics([1.0, 2.0], 1, 1)
