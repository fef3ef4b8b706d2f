"""Collectives: the share of device 0's communication time that nothing
hid: 100 x `exposed_collective_ms`' arithmetic over the union of the
collectives' intervals (either op line). 100 where every collective runs
with the core idle; it cannot pass 100, the exposed part being a part."""

from perfbench import bucket_timeline


def read(run):
    return bucket_timeline.read(run, "exposed_share_pct")
