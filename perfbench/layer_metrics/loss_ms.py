"""Models and kernels: milliseconds per step under the ``loss`` scope,
forward and backward: the language-model head's matmuls (tied or not, and
BERT's MLM transform and pooler) and the cross-entropy, of every head the
program has (GLM's prediction module's too). A program without the scope
reports nothing."""

from perfbench import scopes


def read(run):
    return scopes.total(scopes.run_table(run), parts=("loss",))
