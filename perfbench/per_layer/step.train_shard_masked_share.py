"""Step, on a ``--shard-devices`` mesh: share of the gather and scatter
descriptors the chips issue for a train flush that carry no feature. A
flush is routed by column range on the host, so a shard is handed only the
entries it owns, its rows padded to the width of the fullest row any shard
holds: counters ``step.train.shard_entries_issued`` (shards x padded rows
x routed width) less ``step.train.shard_entries`` (entries that carry a
feature), over the former. What is left is row padding: 60% on four
shards at a routed width of 24 where a shard's mean row holds 9.5
entries; handing every shard every entry cost 75% and the padding."""

from harness import reading

NAME = "step.train_shard_masked_share"


def read(run):
    issued = reading.counter(
        run, "trace.counter.step.train.shard_entries_issued")
    if issued <= 0:
        return None
    entries = reading.counter(run, "trace.counter.step.train.shard_entries")
    return 100.0 * (issued - entries) / issued
