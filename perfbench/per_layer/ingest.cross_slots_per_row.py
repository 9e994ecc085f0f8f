"""Ingest: pair features the cross product emitted a row, before the merge
by column: counter ``fv.combine.slots`` over ``fv.combine.rows``. Reads the
configuration's ``features_per_row`` less its base features (741 for 39)
where no pair is sampled away or dropped."""

from harness import reading

NAME = "ingest.cross_slots_per_row"


def read(run):
    rows = reading.counter(run, "trace.counter.fv.combine.rows")
    if rows <= 0:
        return None
    return reading.counter(run, "trace.counter.fv.combine.slots") / rows
