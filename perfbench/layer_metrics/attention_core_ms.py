"""Models and kernels: milliseconds per step in the attention core, forward
and backward: the ``attention/{scores,softmax,context}`` scopes of
``models/gpt.py`` and ``models/bert.py`` (the query/key/value/output
projections are not in it, nor the probs dropout: see ``dropout_ms``). A
program without those scopes reports nothing."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), parts=("attention",))
