"""Step: how many train programs the window's flushes ran: the
``step.train.program_<form>_<shape>`` counters that moved in the window,
one key a compiled shape. A cell whose flushes are all the coalescer's cap
reads 1; a second one is a bucket the flushes straddle, and was compiled
in the warm-up or in the window (``compile.in_window``)."""

from harness import stats

NAME = "step.train_programs_in_window"
PREFIX = "trace.counter.step.train.program_"


def read(run):
    moved = {key for s0, s1 in zip(run.status0, run.status1) for key in s1
             if key.startswith(PREFIX)
             and stats.counter_delta(s0, s1, key) > 0}
    return len(moved) or None
