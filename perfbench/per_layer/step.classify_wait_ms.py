"""Step: milliseconds from a classify flush's dispatch returning to its
scores on the host: the device's queue ahead of it, its own program, the
readback. The mean of span ``step.classify.wait``."""

from harness import reading

NAME = "step.classify_wait_ms"


def read(run):
    n, ms = reading.span(run, "step.classify.wait")
    return ms / n if n > 0 else None
