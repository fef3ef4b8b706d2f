"""Collectives: milliseconds per step, on device 0, covered by a collective
operation (on either op line of the device trace) and by no other operation
of the synchronous line: the communication the schedule did not hide. A
trace without collectives reports nothing."""

from perfbench import xplane


def read(run):
    device = run["trace"].devices[0]
    if not any(o.is_collective for o in device.ops + device.async_ops):
        return None
    steps = len(device.modules)
    return xplane.length(device.exposed_collectives()) * 1e-6 / steps
