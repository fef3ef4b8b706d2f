"""Models and kernels: milliseconds per step, forward + backward, that
sparsity costs beyond its matmuls: ``moe/{route,dispatch,combine}`` (router,
top-k, sort, gathers, the weighted sum), i.e. `moe_routed_ms` less what lies
under ``moe/experts``."""

from perfbench import moe_scopes


def read(run):
    routed = moe_scopes.ms_under(run, moe_scopes.ROUTED)
    if routed is None:
        return None
    return routed - (moe_scopes.ms_under(run, moe_scopes.EXPERTS) or 0.0)
