"""Step: megabytes (10^6 B) a train flush's stage puts on the device:
counter ``step.train.upload_bytes`` (the padded index, value and label
arrays) over the flushes that counted it (span ``step.train.stage``)."""

from harness import reading

NAME = "step.train_upload_mb_per_flush"


def read(run):
    flushes, _ms = reading.span(run, "step.train.stage")
    up = reading.counter(run, "trace.counter.step.train.upload_bytes")
    return up / flushes / 1e6 if flushes > 0 and up > 0 else None
