"""Collectives: how long a finished gradient waited, per step on device 0:
the mean over the buckets of (start of the collective that carries the
bucket - end of the last operation that produced the bucket's packed
gradient, ``dear/pack/bucket<g>``). DeAR's claim is that this is about zero.
Also logs the whole timeline as ``[schedule]`` lines: the per-bucket table,
asked against compiled by opcode, both legs' wire bytes and rates."""

from perfbench import bucket_timeline, harness


def read(run):
    timeline = bucket_timeline.of_run(run)
    if timeline is None:
        return None
    bucket_timeline.log_schedule(timeline, harness.log)
    return bucket_timeline.read(run, "reduce_wait_ms")
