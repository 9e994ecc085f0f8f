"""Step, on a ``--shard-devices`` mesh: the fullest shard's share of the
entries that carry a feature: counter ``step.train.shard_entries_owned_max``
(the most any shard owns of a flush, summed over flushes) over
``step.train.shard_entries``. 25% is even over four shards; a key that
every row carries is one fixed column on one shard, so it is not. It is
the least share of a flush that one chip of a routed step would handle."""

from harness import reading

NAME = "step.train_shard_owned_max_share"


def read(run):
    entries = reading.counter(run, "trace.counter.step.train.shard_entries")
    if entries <= 0:
        return None
    return 100.0 * reading.counter(
        run, "trace.counter.step.train.shard_entries_owned_max") / entries
