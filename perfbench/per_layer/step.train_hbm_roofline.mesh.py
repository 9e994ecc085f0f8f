"""Kernels, one step over the cell's chips: bytes the AROW update needs
for the rows of a flush (needed.py: the same work whatever implements it,
and not what each shard reads of the entries it masks) over the chips'
peak HBM rate together, over the train program's device time per
execution (the mean over the chips: each runs the program once a flush).
``step.train_hbm_roofline``'s arithmetic with the peak of ``chips``
chips."""

from harness import needed, reading

NAME = "step.train_hbm_roofline.mesh"


def read(run):
    one_chip = reading.hbm_roofline_pct(run, "train", "train_raw",
                                        needed.train_flush_bytes)
    if one_chip is None:
        return None
    return one_chip / int(run.workload["chips"])
