"""Coalescer: how long a classify call's ticket waited in the queue,
enqueue to claim: the mean of span ``microbatch.classify_raw.queue_wait``
over the window (one record per ticket)."""

from harness import reading

NAME = "coalescer.classify_queue_wait_ms"


def read(run):
    n, ms = reading.span(run, "microbatch.classify_raw.queue_wait")
    return ms / n if n > 0 else None
