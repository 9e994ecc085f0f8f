"""Device: 1 minus the union of device operations over the traced interval."""

from harness import reading

NAME = "device.idle_share.serve"


def read(run):
    return reading.idle_share_pct(run)
