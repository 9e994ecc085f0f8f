#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell once, from the root of a checkout, on the machine it is
started on. Lines about the run go to standard error; the last line of
standard output is the result. Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result."""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, metavar="PRECISION",
                    help="for the readings a limit of `correct` is set "
                    "from: also put the reference in this lower precision "
                    "(bfloat16) in the program's place and report its gaps "
                    "under `control`")
    ap.add_argument("--check-seeds", default="", metavar="N,N,...",
                    help="for the same: after the run, drive the check "
                    "plan again through the same servers for each of these "
                    "seeds and report every reading under `readings`")
    ns = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    try:
        result = cell.run_cell(root, ns.workload, ns.seed, ns.seconds,
                               bool(ns.trace), T_PROCESS_START,
                               control=ns.control, check_seeds=[
                                   int(n) for n in ns.check_seeds.split(",")
                                   if n])
    except cell.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
