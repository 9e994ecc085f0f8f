"""Coalescer: rows per classify flush over the window, from the query
coalescer's ``item_count`` and ``flush_count``."""

from harness import reading

NAME = "coalescer.classify_rows_per_flush"


def read(run):
    return reading.rows_per_flush(run, "classify_raw")
