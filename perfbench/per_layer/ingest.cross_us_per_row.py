"""Ingest: host microseconds the cross product of a combination
configuration takes a row: span ``fv.combine`` (the native parser's own
clock around the pairs of each row, inside ``fv.convert``) over counter
``fv.combine.rows``, over the window."""

from harness import reading

NAME = "ingest.cross_us_per_row"


def read(run):
    _n, ms = reading.span(run, "fv.combine")
    rows = reading.counter(run, "trace.counter.fv.combine.rows")
    return ms * 1e3 / rows if rows > 0 else None
