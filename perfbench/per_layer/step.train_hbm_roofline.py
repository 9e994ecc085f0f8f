"""Kernels: bytes the AROW update needs for the rows of a flush (needed.py;
not the whole-table sweeps the program makes) over the chip's peak HBM
rate, over the train program's device time. Bound by bytes: the update is
gathers and scatters. Rows per flush are the window's."""

from harness import needed, reading

NAME = "step.train_hbm_roofline"


def read(run):
    return reading.hbm_roofline_pct(run, "train", "train_raw",
                                    needed.train_flush_bytes)
