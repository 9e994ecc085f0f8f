"""Step: how many distinct widths the window's train flushes ran at: the
``step.train.width_<K>`` counters that moved in the window. Each is a
compiled program (times the row buckets); a cell whose rows are alike
reads 1, and so does one whose uneven rows the parser packs at powers of
two, once a flush's widest request decides."""

from harness import stats

NAME = "step.train_widths_in_window"
PREFIX = "trace.counter.step.train.width_"


def read(run):
    moved = {key for s0, s1 in zip(run.status0, run.status1) for key in s1
             if key.startswith(PREFIX)
             and stats.counter_delta(s0, s1, key) > 0}
    return len(moved) or None
