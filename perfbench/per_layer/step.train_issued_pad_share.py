"""Step: share of the entries the device is handed that carry no feature:
counters ``step.train.entries_issued`` (slabs in their bucket x the slab's
width where a flush was cut, the rows' bucket x the flush's width else)
less ``step.train.entries``, over the former. Gather and scatter cost per
entry issued; ``step.train_width_pad_share`` reads the flush as it
arrives instead."""

from harness import reading

NAME = "step.train_issued_pad_share"


def read(run):
    issued = reading.counter(run, "trace.counter.step.train.entries_issued")
    if issued <= 0:
        return None
    entries = reading.counter(run, "trace.counter.step.train.entries")
    return 100.0 * (issued - entries) / issued
