"""Device: 1 minus the union of device operations over the traced interval,
mean over the chips (each chip's own is printed on an earlier line)."""

from harness import reading

NAME = "device.idle_share.train"


def read(run):
    return reading.idle_share_pct(run)
