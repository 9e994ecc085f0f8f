"""Device step: device time of the ``scores`` program per execution (one
classify flush, or the quality plane's scoring of a train call) in the
traced interval."""

from harness import reading

NAME = "step.classify_device_ms"


def read(run):
    return reading.program_ms(run, "classify")
