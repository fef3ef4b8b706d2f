"""Models and kernels: milliseconds per step making and applying dropout
masks, forward and backward: the ``attention/dropout`` scope and Flax's
``Dropout_<n>`` modules. A step with no such operation reports nothing."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), parts=("dropout",))
