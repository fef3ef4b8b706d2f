"""The timed loop and its arithmetic. No JAX here: a step is any callable
``step(state, batch) -> (state, handle)`` and ``wait(handle) -> loss``, so
the loop is tested on a fake step (perfbench/tests/test_steploop.py)."""

from __future__ import annotations

import contextlib
import math
import statistics
import time


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default method), of a non-empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def one_in_flight(step, wait, state, batch, *, stop, clock=time.perf_counter,
                  span=None):
    """Run steps with one kept in flight: dispatch step i+1, then wait for
    step i and take the clock. The device never waits for the host, and
    every step yields a completion time.

    ``stop(n_completed, now, t0)`` is asked after each completion.
    ``span(name)`` (optional) is a context manager entered around each
    ``dispatch`` and ``wait``. Returns ``(state, record)`` with the
    completion times (seconds on ``clock``), the losses, the host seconds
    spent inside each dispatch call, and ``attempted`` = steps dispatched
    from ``t0`` on (the step already in flight at ``t0`` is the window's
    first completion; the last one dispatched is drained, not timed).
    """
    span = span or (lambda name: contextlib.nullcontext())
    state, pending = step(state, batch)      # primes the pipeline
    t0 = clock()
    done, losses, dispatch_s = [], [], []
    while True:
        t_a = clock()
        with span("dispatch"):
            state, nxt = step(state, batch)
        dispatch_s.append(clock() - t_a)
        with span("wait"):
            loss = wait(pending)
        now = clock()
        done.append(now)
        losses.append(loss)
        pending = nxt
        if stop(len(done), now, t0):
            break
    losses.append(wait(pending))             # drain; outside the window
    return state, {"t0": t0, "done": done, "losses": losses,
                   "dispatch_s": dispatch_s, "attempted": len(dispatch_s)}


def window_metrics(done, tokens_per_step: int, chips: int) -> dict:
    """Rate and step-interval statistics of one window of completions.

    The rate is over all the work and all the time between the first and
    the last completion: ``(n - 1)`` steps in ``done[-1] - done[0]``."""
    if len(done) < 3:
        raise ValueError(f"a window of {len(done)} completions is too short")
    intervals_ms = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
    span_s = done[-1] - done[0]
    return {
        "tokens_per_s_per_chip":
            (len(done) - 1) * tokens_per_step / span_s / chips,
        "step_ms_p95": percentile(intervals_ms, 95),
        "step_ms_median": statistics.median(intervals_ms),
        "step_ms_max": max(intervals_ms),
        "intervals": len(intervals_ms),
    }
