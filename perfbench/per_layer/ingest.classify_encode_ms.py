"""Ingest: milliseconds a classify flush spends on turning scores into the
answer (the rows of ``[label, score]`` pairs the driver builds from the
scores on the host). The mean of span ``classify.encode``."""

from harness import reading

NAME = "ingest.classify_encode_ms"


def read(run):
    n, ms = reading.span(run, "classify.encode")
    return ms / n if n > 0 else None
