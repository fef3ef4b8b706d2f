"""Models and kernels: milliseconds per step, forward + backward, of
everything under the multi-token-prediction module's ``mtp`` scope: the
merge of state and next-token embedding, its expert block, its final norm,
its pass through the shared head and its cross-entropy. A program without
the scope reports nothing."""

from perfbench import moe_scopes


def read(run):
    return moe_scopes.ms_under(run, moe_scopes.MTP)
