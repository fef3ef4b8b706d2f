"""Collectives: `reduce_wire_gbps`' arithmetic on the parameter leg: the
all-gather rows' ring-estimate wire bytes a step and chip over the time
device 0 spends in the gather leg's collectives, GB/s at 1e9."""

from perfbench import bucket_timeline


def read(run):
    return bucket_timeline.read(run, "gather_wire_gbps")
