"""Ingest: share of the window's combination requests that the Python
converter expanded (counter ``fv.combine.generic``: the native parser
declined them) among all of them (with ``.native``: the parse threads'
cross product). The cell's calls are of one size, so this is the share of
rows too. Must read 0."""

from harness import reading

NAME = "ingest.cross_generic_share"


def read(run):
    by_path = [reading.counter(run, f"trace.counter.fv.combine.{p}")
               for p in ("generic", "native")]
    return 100.0 * by_path[0] / sum(by_path) if sum(by_path) > 0 else None
