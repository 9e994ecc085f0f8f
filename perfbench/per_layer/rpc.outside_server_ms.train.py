"""RPC transport: mean ``train`` latency at the client minus the server's
own ``trace.rpc.train`` mean, over the window."""

from harness import reading

NAME = "rpc.outside_server_ms.train"


def read(run):
    return reading.outside_server_ms(run, "train")
