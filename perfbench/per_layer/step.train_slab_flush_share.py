"""Step: share of the window's train flushes that were handed to the chip
as slabs: counter ``step.train.slab_flushes`` over the flushes that were
staged (span ``step.train.stage``). A flush of uneven rows is cut, rows
that are alike run as they come: 100 or 0 says which side of the rule a
cell's traffic lies on. Nothing to read in a program without the rule
(it counts no ``step.train.entries_issued``)."""

from harness import reading

NAME = "step.train_slab_flush_share"


def read(run):
    flushes, _ms = reading.span(run, "step.train.stage")
    if flushes <= 0 or reading.counter(
            run, "trace.counter.step.train.entries_issued") <= 0:
        return None
    cut = reading.counter(run, "trace.counter.step.train.slab_flushes")
    return 100.0 * cut / flushes
