"""Step, on a ``--shard-devices`` mesh: share of the gather and scatter
descriptors the chips issue for a train flush that address nothing their
chip owns. Every shard is handed every entry of the padded flush and masks
the ones outside its column range onto one cell of its slice, so counters
``step.train.shard_entries_issued`` (shards x padded rows x width) less
``step.train.shard_entries`` (entries that carry a feature), over the
former: 75% and the padding on four shards. A step that routed each entry
to its owner would leave the padding alone."""

from harness import reading

NAME = "step.train_shard_masked_share"


def read(run):
    issued = reading.counter(
        run, "trace.counter.step.train.shard_entries_issued")
    if issued <= 0:
        return None
    entries = reading.counter(run, "trace.counter.step.train.shard_entries")
    return 100.0 * (issued - entries) / issued
