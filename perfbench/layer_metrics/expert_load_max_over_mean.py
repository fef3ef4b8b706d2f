"""Models and kernels: the imbalance of the routing, from the program's
counter (`moe_scopes.routing_counts`: assignments per held expert per expert
layer on the run's batch and weights, one forward pass outside the traced
stretch): the largest count of a layer over the layer's mean, the worst
layer's. 1 is an even load; the grouped matmul's tiles and, in a deployment,
the slowest chip of the expert group follow the maximum."""


from perfbench import moe_scopes


def read(run):
    counts = moe_scopes.routing_counts(run)
    if counts is None or not counts.sum():
        return None
    return float((counts.max(axis=1) / counts.mean(axis=1).clip(1e-9)).max())
