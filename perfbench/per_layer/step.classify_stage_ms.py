"""Step: host milliseconds a classify flush spends on padding and the
host-to-device copies: the mean of span ``step.classify.stage``."""

from harness import reading

NAME = "step.classify_stage_ms"


def read(run):
    n, ms = reading.span(run, "step.classify.stage")
    return ms / n if n > 0 else None
