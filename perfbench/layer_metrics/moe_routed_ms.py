"""Models and kernels: milliseconds per step, forward + backward, under the
routed experts' scopes ``moe/{route,dispatch,experts,combine}`` of
`parallel.ep.RoutedExperts` in every expert layer (the shared expert is not
in it). A program without those scopes reports nothing."""

from perfbench import moe_scopes


def read(run):
    return moe_scopes.ms_under(run, moe_scopes.ROUTED)
