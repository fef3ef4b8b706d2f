"""Schedule builder: the in-program collectives the schedule issues a step,
as the program's own account lists them: the rows of ``TrainStep.comm`` (one
a bucket and leg, so 2 x buckets in ``mode="dear"``; the host-level ``dcn``
leg is not one). Beside `collectives_per_step`, which counts what the
compiler made of them. A program without the account reports nothing."""

from perfbench import bucket_timeline


def read(run):
    return bucket_timeline.read(run, "asked_per_step")
