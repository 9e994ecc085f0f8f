"""Every row of every ``train`` call acknowledged in the window, all servers
together, over the window's seconds."""

from harness import stats

NAME = "train_rows_per_s"


def read(run):
    rows = sum(r[7] for r in run.window("train") if r[6] and isinstance(r[7], int))
    return stats.rate(rows, run.t0, run.t1) if rows else None
