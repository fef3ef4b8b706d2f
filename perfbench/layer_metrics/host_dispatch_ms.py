"""Entry points: median host milliseconds inside one `ts.step` call in the
traced stretch (the harness's own span around the call)."""

import statistics


def read(run):
    spent = run["record"]["dispatch_s"]
    return statistics.median(spent) * 1e3 if spent else None
