"""Models and kernels: milliseconds per step, on device 0's synchronous line,
of the operations whose op_name says forward pass (``jvp(`` and not
``transpose(``), all parts."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), phase="forward")
