"""Device step: device time of one train execution in the serving cell."""

from harness import reading

NAME = "step.train_device_ms.serve"


def read(run):
    return reading.program_ms(run, "train")
