"""Entry points: process start to the first answered RPC of the slowest
server."""

NAME = "entry.first_answer_s"


def read(run):
    return run.first_answer_s
