"""Coalescer: share of the window in which the train coalescer's device
stage was busy (``device_seconds`` difference over the window's seconds,
mean over the servers)."""

from harness import reading

NAME = "coalescer.device_stage_share"


def read(run):
    busy = reading.counter(run, "microbatch.train_raw.device_seconds")
    return 100.0 * busy / (run.seconds * len(run.status0)) if busy > 0 else None
