"""Device: 100 * (1 - the union of operation intervals on device 0 over the
traced stretch, first program start to last program end)."""

from perfbench import xplane


def read(run):
    device = run["trace"].devices[0]
    lo, hi = device.window
    return 100.0 * (1.0 - xplane.length(device.busy) / (hi - lo))
