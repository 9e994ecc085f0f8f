"""Process start to the window's start: interpreter and servers up, tables
allocated, programs compiled or loaded, the cell's shapes warmed, the
request pool encoded."""

NAME = "setup_s"


def read(run):
    return run.setup_s
