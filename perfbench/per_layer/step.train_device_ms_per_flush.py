"""Device step: device time of the train program's executions in the traced
interval, over their number (one execution is one flush)."""

from harness import reading

NAME = "step.train_device_ms_per_flush"


def read(run):
    return reading.program_ms(run, "train")
