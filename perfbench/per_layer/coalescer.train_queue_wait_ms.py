"""Coalescer: how long a train call's ticket waited in the queue, enqueue
to claim: the mean of span ``microbatch.train_raw.queue_wait`` over the
window (one record per ticket, under the ticket's trace id)."""

from harness import reading

NAME = "coalescer.train_queue_wait_ms"


def read(run):
    n, ms = reading.span(run, "microbatch.train_raw.queue_wait")
    return ms / n if n > 0 else None
