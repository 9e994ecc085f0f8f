"""Step: milliseconds a query's dispatch waits for the driver's lock, behind
a train step's: the mean of span ``step.classify.lock_wait``."""

from harness import reading

NAME = "step.classify_lock_wait_ms"


def read(run):
    n, ms = reading.span(run, "step.classify.lock_wait")
    return ms / n if n > 0 else None
