"""Coalescer: rows per train flush over the window, from the train
coalescer's own ``item_count`` and ``flush_count``."""

from harness import reading

NAME = "coalescer.rows_per_flush"


def read(run):
    return reading.rows_per_flush(run, "train_raw")
