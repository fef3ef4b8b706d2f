"""Models and kernels: milliseconds per step, forward + backward, in the
latent attention's projections of all blocks: the low-rank down and up
matmuls of queries, keys and values, their norms, the rotary embedding and
the output projection (the ``query`` / ``key`` / ``value`` / ``output``
scopes of `models/glm_moe.py`; `perfbench.scopes`' ``projections`` part).
The attention core is not in it."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), parts=("projections",))
