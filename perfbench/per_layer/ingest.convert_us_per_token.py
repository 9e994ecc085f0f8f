"""Ingest: the ``fv.convert`` span's total over the window, over the
tokens its string rules cut in it (counter ``fv.tokens``): what the parse
costs by the text's length, where ``ingest.convert_us_per_row`` is by the
row."""

from harness import reading

NAME = "ingest.convert_us_per_token"


def read(run):
    _n, ms = reading.span(run, "fv.convert")
    tokens = reading.counter(run, "trace.counter.fv.tokens")
    return ms * 1e3 / tokens if tokens > 0 and ms > 0 else None
