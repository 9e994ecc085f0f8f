"""Step: milliseconds a train flush waits for the driver's lock before its
step: the mean of span ``step.train.lock_wait`` over the window."""

from harness import reading

NAME = "step.train_lock_wait_ms_per_flush"


def read(run):
    n, ms = reading.span(run, "step.train.lock_wait")
    return ms / n if n > 0 else None
