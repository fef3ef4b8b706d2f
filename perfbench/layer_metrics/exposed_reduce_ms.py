"""Collectives: milliseconds per step, on device 0, of exposed collective
time (`exposed_collective_ms`'s arithmetic) under a
``dear/bucket<g>/reduce`` scope: the gradient leg the backward pass did not
hide. Logs all three legs. Nothing where no collective carries that scope."""

from perfbench import harness, scopes


def read(run):
    legs = scopes.run_exposed_by_leg(run)
    if legs is None:
        return None
    harness.log("[scopes] exposed collective ms/step by leg: " + ", ".join(
        f"{leg} {ms:.3f}" for leg, ms in legs.items() if ms is not None))
    return legs["reduce"]
