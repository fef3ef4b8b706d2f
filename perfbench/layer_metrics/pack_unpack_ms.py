"""Schedule builder: milliseconds per step packing gradients into bucket
buffers and unpacking bucket buffers into parameters: the ``dear/pack`` and
``dear/unpack`` scopes. A program without those scopes reports nothing."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), parts=("pack", "unpack"))
