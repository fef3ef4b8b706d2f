"""Root conftest: a vendored per-test timeout plugin.

``pytest-timeout`` cannot be installed in this container (no package
installs), so the suite carries a minimal equivalent with the same CLI
surface the reference-scale suites rely on: ``--timeout=N`` /
``--timeout-method=signal|thread`` / ``@pytest.mark.timeout(N)``. The
cautionary tale is the reference's mpirun test harness, which simply
hangs forever when a rank wedges (reference common/comm_core/test.sh:29);
this suite's cluster tests (tests/test_multiprocess.py) spawn real
subprocess workers and must not be able to hang CI.

Methods (mirroring pytest-timeout's two strategies, own implementation):

- ``signal`` (default): SIGALRM in the main thread; dumps all thread
  stacks via faulthandler and fails JUST the hung test. Cannot interrupt
  a test stuck inside a C call (e.g. a long XLA compile) until it
  returns to Python.
- ``thread``: a daemon ``threading.Timer`` that dumps all stacks and
  ``os._exit(7)``s the whole process — fires even inside C calls. This is
  the backstop for a truly hung backend; the process dies, which is the
  honest outcome (state is unrecoverable).

A test stuck in a C call under the default method keeps the alarm
pending: SIGALRM delivery interrupts most blocking syscalls (EINTR), so
subprocess waits and socket reads do get failed.
"""

from __future__ import annotations

import faulthandler
import os
import signal
import sys
import threading

import pytest

# pytester drives the timeout plugin in tests/test_timeout_plugin.py
pytest_plugins = ["pytester"]


def pytest_addoption(parser):
    group = parser.getgroup("timeout", "per-test timeouts (vendored)")
    group.addoption(
        "--timeout", type=float, default=None,
        help="per-test timeout in seconds, armed separately for each "
             "phase (setup / call / teardown); 0 or unset disables",
    )
    group.addoption(
        "--timeout-method", choices=("signal", "thread"), default="signal",
        help="signal: SIGALRM fails the one hung test (cannot interrupt "
             "C calls); thread: stack-dump then os._exit(7), fires even "
             "inside C calls",
    )
    parser.addini("timeout", "default per-test timeout in seconds")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds, method='signal'|'thread'): override the "
        "per-test time limit for this test",
    )
    config.addinivalue_line(
        "markers",
        "flaky(reason=..., reruns=2): quarantined load-flaky test — a "
        "failure is rerun (fresh setup/teardown) up to `reruns` times "
        "and only reported if every attempt fails; set "
        "DEAR_FLAKY_RERUNS=0 to see first-attempt failures raw",
    )


def _settings(item):
    """(seconds, method) for ``item`` — marker overrides CLI overrides ini."""
    timeout = item.config.getoption("--timeout")
    method = item.config.getoption("--timeout-method")
    ini = item.config.getini("timeout")
    if timeout is None and ini:
        try:
            timeout = float(ini)
        except ValueError:
            timeout = None
    marker = item.get_closest_marker("timeout")
    if marker:
        if marker.args:
            timeout = float(marker.args[0])
        if "seconds" in marker.kwargs:
            timeout = float(marker.kwargs["seconds"])
        method = marker.kwargs.get("method", method)
    return timeout, method


def _guard(item):
    """Context manager arming the configured timeout for ONE test phase.

    Armed per phase (setup / call / teardown separately, like
    pytest-timeout) rather than across the whole runtest protocol: an
    alarm firing inside pytest's reporting machinery would escape as an
    INTERNALERROR and abort the session instead of failing one test."""
    import contextlib

    timeout, method = _settings(item)
    use_signal = (
        method == "signal"
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )

    @contextlib.contextmanager
    def armed():
        if not timeout or timeout <= 0:
            yield
            return
        if use_signal:
            def on_alarm(signum, frame):
                sys.stderr.write(
                    f"\n+++ timeout: {item.nodeid} exceeded {timeout:g}s "
                    "(signal method); thread stacks follow +++\n"
                )
                faulthandler.dump_traceback(file=sys.stderr)
                pytest.fail(f"timeout: exceeded {timeout:g}s", pytrace=False)

            old = signal.signal(signal.SIGALRM, on_alarm)
            signal.setitimer(signal.ITIMER_REAL, timeout)
            try:
                yield
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)
        else:
            def on_timer():
                sys.stderr.write(
                    f"\n+++ timeout: {item.nodeid} exceeded {timeout:g}s "
                    "(thread method); dumping stacks and exiting 7 +++\n"
                )
                faulthandler.dump_traceback(file=sys.stderr)
                sys.stderr.flush()
                os._exit(7)

            timer = threading.Timer(timeout, on_timer)
            timer.daemon = True
            timer.start()
            try:
                yield
            finally:
                timer.cancel()

    return armed()


def pytest_runtest_protocol(item, nextitem):
    """Rerun-on-failure for tests quarantined with ``@pytest.mark.flaky``
    (vendored, same idea as pytest-rerunfailures — which cannot be
    installed in this container). A marked test that fails any phase is
    torn down and rerun from a fresh setup, up to ``reruns`` times; only
    the FINAL attempt's reports are logged, so a load blip neither fails
    CI nor inflates the dot count. ``DEAR_FLAKY_RERUNS`` overrides the
    marker (0 disables rerunning — for hunting the flake itself)."""
    marker = item.get_closest_marker("flaky")
    if marker is None:
        return None
    env = os.environ.get("DEAR_FLAKY_RERUNS", "").strip()
    reruns = int(env) if env.isdigit() else int(marker.kwargs.get("reruns", 2))
    if reruns <= 0:
        return None
    from _pytest.runner import runtestprotocol

    hook = item.ihook
    hook.pytest_runtest_logstart(nodeid=item.nodeid, location=item.location)
    for attempt in range(reruns + 1):
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
        if not any(r.failed for r in reports) or attempt == reruns:
            for report in reports:
                hook.pytest_runtest_logreport(report=report)
            break
        # runtestprotocol ran teardown for the failed attempt; the next
        # loop iteration re-runs setup from scratch
        sys.stderr.write(
            f"\nflaky: {item.nodeid} failed attempt {attempt + 1}/"
            f"{reruns + 1} ({marker.kwargs.get('reason', 'quarantined')}); "
            "rerunning\n")
    hook.pytest_runtest_logfinish(nodeid=item.nodeid, location=item.location)
    return True


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _guard(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _guard(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _guard(item):
        yield
