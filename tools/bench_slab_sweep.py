"""The sweep behind models/classifier.py's _SLAB_WIDTH and _SLAB_GAIN and
the power-of-two bucket of the slab count (PERF.md section 6, PR 35;
docs/PERF_NOTES.md has the table).

One process on the chip, through the driver's own ``train_indexed``:
flushes of 8,000 ``news20_arow`` documents (perfbench's ``text_rows``
generator through the native parser, D = 2^23, 32 label rows) trained in
rows and in slabs of 32, 64, 128 and 256 entries, the slab count bucketed
to a power of two or to ``_width_bucket``'s rungs. For each form: the
programs its eight flushes made (a bucket that the flushes straddle makes
two), the first call's seconds (the compile), the host's stage and the
wall time of a flush waited for, in milliseconds. Then where rows and
slabs cross: rows of one length at K = 1,024 whose slabs issue 1, 1/2 and
1/4 of the rows' entries, both forms forced. The constants under sweep
are this module's to set, around the calls; the program has no option for
them.

    python tools/bench_slab_sweep.py [--docs 8000] [--flushes 8] [--out F]

Prints one JSON document. Runs on the CPU too (a count of slabs and
programs there, no time worth reading)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "perfbench"))

SEED = 2350000017


def _flushes(conf, docs, n, dim_bits):
    """``n`` parsed flushes of ``docs`` documents at 1,024 wide."""
    import msgpack

    from harness import cell, wire
    from jubatus_tpu.native.ingest import IngestParser

    gen = cell.load_module(os.path.join(REPO, "perfbench", "generators"),
                           conf["data"]["generator"])
    conv = dict(conf["model"]["converter"], hash_max_size=1 << dim_bits)
    parser = IngestParser.from_converter_config(conv, dim_bits)
    out = []
    for i in range(n):
        rows = gen.make_rows(conf["data"], SEED, 10 + i, docs)
        frame = bytes(wire.encode_request("train", ["x", [
            [label, wire.datum(s, nv)] for label, s, nv in rows]]))
        params = msgpack.packb(msgpack.unpackb(frame, raw=False)[3],
                               use_bin_type=True)
        (uniq, label_idx), idx, val = parser.parse_indexed(params)
        pad = ((0, 0), (0, max(0, 1024 - idx.shape[1])))
        out.append((uniq, label_idx, np.pad(idx, pad), np.pad(val, pad)))
    return out


def _even_rows(rng, labels, rows, k, n, dim):
    idx = np.zeros((rows, k), np.int32)
    idx[:, :n] = rng.integers(1, dim, size=(rows, n))
    return (labels, (np.arange(rows) % len(labels)).astype(np.int32), idx,
            (idx != 0).astype(np.float32))


def _time(M, conf, dim_bits, flushes, reps):
    """One form over the flushes: a fresh driver, the first call apart."""
    import jax

    from jubatus_tpu.utils import tracing

    model = dict(conf["model"], converter=dict(
        conf["model"]["converter"], hash_max_size=1 << dim_bits))
    d = M.ClassifierDriver(model, dim_bits=dim_bits)
    d.trace = reg = tracing.Registry()
    t0 = time.perf_counter()
    for f in flushes:       # every label live, every program compiled
        d.train_indexed(*f)
    jax.block_until_ready(d.state)
    first_s = time.perf_counter() - t0
    made = dict(reg.counters())
    walls = []
    for _ in range(reps):
        for f in flushes:
            t0 = time.perf_counter()
            d.train_indexed(*f)
            jax.block_until_ready(d.state)
            walls.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter()
    for f in flushes:       # as the server runs them: one behind another
        d.train_indexed(*f)
    jax.block_until_ready(d.state)
    piped = (time.perf_counter() - t0) * 1e3 / len(flushes)
    c, st = reg.counters(), reg.trace_status()
    n = sum(v for k, v in c.items() if k.startswith("step.train.program_"))
    d._let_go()
    return {
        "programs": sorted(k[len("step.train.program_"):] for k in made
                           if k.startswith("step.train.program_")),
        "first_pass_s": round(first_s, 2),
        "flush_ms_waited_for": [round(float(np.median(walls)), 2),
                                round(min(walls), 2), round(max(walls), 2)],
        "flush_ms_back_to_back": round(piped, 2),
        "stage_ms": round(st["trace.step.train.stage.mean_ms"], 2),
        "dispatch_ms": round(st["trace.step.train.dispatch.mean_ms"], 2),
        "entries_issued_per_flush": c["step.train.entries_issued"] // n,
        "slabs_per_flush": [c.get("step.train.slabs", 0) // n,
                            c.get("step.train.slabs_padded", 0) // n],
        "upload_mb_per_flush": round(
            c["step.train.upload_bytes"] / n / 1e6, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int, default=8000)
    ap.add_argument("--flushes", type=int, default=8)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--dim-bits", type=int, default=23)
    ap.add_argument("--cross-rows", type=int, default=2048)
    ap.add_argument("--out", default="")
    ns = ap.parse_args()

    import jax

    from jubatus_tpu.core import sparse
    from jubatus_tpu.models import classifier as M
    from jubatus_tpu.utils.compile_cache import configure

    configure()
    with open(os.path.join(REPO, "perfbench", "configs",
                           "news20_arow.json")) as f:
        conf = json.load(f)
    flushes = _flushes(conf, ns.docs, ns.flushes, ns.dim_bits)
    calls = _flushes(conf, 500, ns.flushes, ns.dim_bits)
    report = {"device": str(jax.devices()[0]), "docs": ns.docs,
              "entries_per_flush": [int(np.count_nonzero(f[2]))
                                    for f in flushes],
              "slab_counts": {}, "forms": {}, "crossing": {}}
    for w in (32, 64, 128, 256):
        def count(fs):
            n = [int(np.count_nonzero(f[2][:, ::w])) for f in fs]
            return [min(n), max(n)]
        report["slab_counts"][str(w)] = {
            "flush": count(flushes), "call_of_500": count(calls)}

    cut = M._cut_slabs
    rules = {"pow2": lambda n, m=16: sparse._bucket(n, m),
             "ladder": lambda n, m=16: sparse._width_bucket(n, m)}

    def under(rule):
        """_cut_slabs with the slab count bucketed by ``rule`` (the rows'
        own bucket, taken before the cut, stays a power of two)."""
        def run(*a):
            M._bucket = rules[rule]
            try:
                return cut(*a)
            finally:
                M._bucket = sparse._bucket
        return run

    gain, width = M._SLAB_GAIN, M._SLAB_WIDTH
    try:
        M._SLAB_GAIN = float("inf")
        report["forms"]["rows"] = _time(M, conf, ns.dim_bits, flushes,
                                        ns.reps)
        print("rows", report["forms"]["rows"], file=sys.stderr, flush=True)
        M._SLAB_GAIN = 0
        for w in (32, 64, 128, 256):
            for rule in rules:
                M._SLAB_WIDTH, M._cut_slabs = w, under(rule)
                key = f"slabs_{w}_{rule}"
                report["forms"][key] = _time(M, conf, ns.dim_bits, flushes,
                                             ns.reps)
                print(key, report["forms"][key], file=sys.stderr, flush=True)
        # where rows and slabs cross: rows of one length, both forms
        # forced, the slabs issuing 1, 1/2 and 1/4 of the rows' entries
        M._cut_slabs = cut
        rng = np.random.default_rng(SEED)
        for w in (32, 64):
            M._SLAB_WIDTH = w
            for n in (1024, 512, 256):
                even = [_even_rows(rng, conf["data"]["labels"],
                                   ns.cross_rows, 1024, n, 1 << ns.dim_bits)
                        for _ in range(3)]
                got = {}
                for form, g in (("rows", float("inf")), ("slabs", 0)):
                    M._SLAB_GAIN = g
                    got[form] = _time(M, conf, ns.dim_bits, even, ns.reps)
                report["crossing"][f"w{w}_ratio_{1024 // n}"] = got
                print(w, n, got, file=sys.stderr, flush=True)
    finally:
        M._SLAB_GAIN, M._SLAB_WIDTH, M._cut_slabs = gain, width, cut
    text = json.dumps(report, indent=1)
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
