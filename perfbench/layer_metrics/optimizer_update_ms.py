"""Schedule builder: milliseconds per step in the update phase: the
``dear/bucket<g>/update`` scopes (whichever optimizer was passed), with
``dear/clip`` and ``dear/sdc_fp`` where the step has them. A program without
those scopes reports nothing."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), phase="update")
