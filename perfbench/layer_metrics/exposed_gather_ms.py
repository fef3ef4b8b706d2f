"""Collectives: milliseconds per step, on device 0, of exposed collective
time (`exposed_collective_ms`'s arithmetic) under a
``dear/bucket<g>/gather`` scope: the parameter leg the next forward pass did
not hide. Nothing where no collective carries that scope."""

from perfbench import scopes


def read(run):
    legs = scopes.run_exposed_by_leg(run)
    return None if legs is None else legs["gather"]
