"""Load generator: mean time from an answer's arrival to the next send on
that connection, over the closed-loop train connections."""

NAME = "loadgen.turnaround_us"


def read(run):
    ta = [s for g, s in run.turnaround if run.groups[g]["method"] == "train"]
    return sum(ta) / len(ta) * 1e6 if ta else None
