"""Step: host milliseconds a train flush spends on row-bucket padding and
the host-to-device copies of its arrays: the mean of span
``step.train.stage`` over the window."""

from harness import reading

NAME = "step.train_stage_ms_per_flush"


def read(run):
    n, ms = reading.span(run, "step.train.stage")
    return ms / n if n > 0 else None
