"""Step: share of the entries a train flush's rows have at the program's
width that carry no feature: counters ``step.train.entries_padded`` (rows
asked for x the width ladder's rung) less ``step.train.entries``, over
the former (780 features on the rung of 832: 6.25%). The rows' own
padding is ``step.train_pad_share``."""

from harness import reading

NAME = "step.train_width_pad_share"


def read(run):
    padded = reading.counter(run, "trace.counter.step.train.entries_padded")
    if padded <= 0:
        return None
    entries = reading.counter(run, "trace.counter.step.train.entries")
    return 100.0 * (padded - entries) / padded
