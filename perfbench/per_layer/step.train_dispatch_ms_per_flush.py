"""Step: host milliseconds in the jitted train call, entry to return (it
blocks when the runtime will queue no more): the mean of span
``step.train.dispatch`` over the window."""

from harness import reading

NAME = "step.train_dispatch_ms_per_flush"


def read(run):
    n, ms = reading.span(run, "step.train.dispatch")
    return ms / n if n > 0 else None
