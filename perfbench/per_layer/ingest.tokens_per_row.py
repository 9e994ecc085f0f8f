"""Ingest: tokens the string rules cut a row: counter ``fv.tokens`` (the
native parser counts them as it goes; a whole ``str`` value is one) over
the rows of the calls answered in the window. What the data is, not what
the program does: ``better`` says ``higher`` because the file needs a
direction, and a change of this number is a change of the traffic."""

from harness import reading

NAME = "ingest.tokens_per_row"


def read(run):
    tokens = reading.counter(run, "trace.counter.fv.tokens")
    rows = sum(run.groups[r[0]]["rows_per_call"] for r in run.window())
    return tokens / rows if rows and tokens > 0 else None
