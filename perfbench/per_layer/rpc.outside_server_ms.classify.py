"""RPC transport: mean ``classify`` latency at the client minus the server's
own ``trace.rpc.classify`` mean, over the window."""

from harness import reading

NAME = "rpc.outside_server_ms.classify"


def read(run):
    return reading.outside_server_ms(run, "classify")
