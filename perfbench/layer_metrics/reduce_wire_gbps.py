"""Collectives: what the schedule ASKED the wire to move on the gradient leg
a step and chip (``TrainStep.comm``'s ring-estimate ``wire_bytes`` of the
reduce-scatter / all-reduce rows) over the time device 0 spends in that
leg's collectives (union of their intervals, either op line), in GB/s at
1e9. A compiler that moves more than was asked (an all-reduce in place of a
reduce-scatter) reads low here; the ``[schedule]`` lines give the rate on
what was compiled beside it."""

from perfbench import bucket_timeline


def read(run):
    return bucket_timeline.read(run, "reduce_wire_gbps")
