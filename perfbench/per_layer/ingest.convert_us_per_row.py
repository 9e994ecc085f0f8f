"""Ingest: the ``fv.convert`` span's total over the window, over the rows
of the calls answered in it (train and classify rows alike)."""

from harness import reading

NAME = "ingest.convert_us_per_row"


def read(run):
    _n, ms = reading.span(run, "fv.convert")
    rows = sum(run.groups[r[0]]["rows_per_call"] for r in run.window())
    return ms * 1e3 / rows if rows and ms > 0 else None
