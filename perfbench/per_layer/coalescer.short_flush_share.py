"""Coalescer: share of the window's train claims that were under seven
eighths of the cap: ``microbatch.train_raw.flushes_fill_<k>`` with k < 7
(k = floor(8 x rows / max_batch); 8,000 rows of 8,192 is k = 7) over all
nine. A short claim costs a whole step for part of a flush."""

from harness import reading

NAME = "coalescer.short_flush_share"


def read(run):
    fill = [reading.counter(run, f"microbatch.train_raw.flushes_fill_{k}")
            for k in range(9)]
    return 100.0 * sum(fill[:7]) / sum(fill) if sum(fill) > 0 else None
