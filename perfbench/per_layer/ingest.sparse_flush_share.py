"""Ingest: share of the window's train flushes that took the sparse plan:
``ingest.sparse_flushes`` over all the plans' flush counters, which are
all stamped at the same stage (the coalescer's own flush count is stamped
a stage later, so it can differ by the one flush in the pipeline). Since
PR 28 a raw flush has one shape and counts as ``sparse_flushes``: this can
only read 100, and stays while a test outside the benchmark's directories
(``tests/test_sparse_width.py``) asserts it (PERF.md section 7)."""

from harness import reading

NAME = "ingest.sparse_flush_share"


def read(run):
    by_plan = [reading.counter(run, f"ingest.{p}_flushes")
               for p in ("sparse", "schema", "combo")]
    return 100.0 * by_plan[0] / sum(by_plan) if sum(by_plan) > 0 else None
