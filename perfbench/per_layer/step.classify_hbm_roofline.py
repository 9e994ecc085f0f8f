"""Kernels: bytes the scores of a classify flush need (needed.py) over peak
HBM rate, over the ``scores`` program's device time."""

from harness import needed, reading

NAME = "step.classify_hbm_roofline"


def read(run):
    return reading.hbm_roofline_pct(run, "classify", "classify_raw",
                                    needed.classify_flush_bytes)
