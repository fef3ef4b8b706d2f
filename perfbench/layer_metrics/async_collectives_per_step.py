"""Schedule builder: the collectives of the compiled step's text that the
compiler kept asynchronous, i.e. ``-start`` forms of all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute (a pair counts once): only
those can run beside compute. ``0.0`` where the program holds collectives
and none is asynchronous; nothing where it holds none."""

from perfbench import bucket_timeline


def read(run):
    return bucket_timeline.read(run, "async_per_step")
