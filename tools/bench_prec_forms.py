"""The measurement behind ops/classifier.py's _PREC_FULL_UPTO (PERF.md
section 6, PR 38): the train step at the benchmark's shapes with the
precision read in either form, timed back to back on the chip.

``full``: gathered beside the weights at every label row addressed, 16
lanes a descriptor (the form up to _PREC_FULL_UPTO label groups);
``two``: the scores out of an entry-major copy of the weights, an entry's
row in runs of 128 lanes (_gather_scores), and the precision read at the
correct label's and the rival's rows after the scores. Each form is
forced around its calls by moving the rule's bound; the program has no
option for it. The shapes, random entries on random columns:

- ``criteo``: L = 8, D = 2^25, 8,192 rows x 40 (the Criteo train cells);
- ``news20``: L = 32, 24 rows addressed, D = 2^23, 16,384 slabs of 64;
- ``wili``: L = 256, 240 rows addressed, D = 2^20, 32,768 slabs of 64.

For each shape and form: the compile's seconds (or the compiler's
refusal), the compiled temporaries, and the milliseconds a step over
``--steps`` steps dispatched back to back.

    python tools/bench_prec_forms.py [--steps 20] [--shapes criteo,news20,wili] [--out F]

Prints one JSON document. Runs on the CPU too at ``--small`` shapes (a
check of the control flow, no time worth reading)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: name -> (capacity, rows addressed, columns, B, K, slabs)
SHAPES = {
    "criteo": (8, 8, 1 << 25, 8192, 40, False),
    "news20": (32, 24, 1 << 23, 16384, 64, True),
    "wili": (256, 240, 1 << 20, 32768, 64, True),
}


def _one(C, shape, two, steps, small):
    import jax
    import jax.numpy as jnp

    cap, rows, dim, b, k, slabs = shape
    if small:
        dim, b = 1 << 12, 256
    rng = np.random.default_rng(38)
    jax.clear_caches()
    C._PREC_FULL_UPTO = -1 if two else 10 ** 6
    state = C.init_state(cap, dim, True)
    idx = jnp.asarray(rng.integers(1, dim, size=(b, k)), jnp.int32)
    val = jnp.asarray(rng.random((b, k)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, rows, size=b), jnp.int32)
    mask = jnp.asarray(np.ones(rows, bool))
    owner = jnp.asarray(np.arange(b) // 3, jnp.int32) if slabs else None
    out = {}
    t0 = time.perf_counter()
    try:
        prog = C.train_batch_parallel.lower(
            state, idx, val, labels, mask, 1.0, owner, method="AROW",
            token=True).compile()
    except Exception as e:  # the compiler's refusal is the reading
        out["refused"] = str(e).splitlines()[0][:300]
        return out
    out["compile_s"] = round(time.perf_counter() - t0, 2)
    out["temp_bytes"] = prog.memory_analysis().temp_size_in_bytes
    got = C.train_batch_parallel(state, idx, val, labels, mask, 1.0, owner,
                                 method="AROW", token=True)
    jax.block_until_ready(got)
    state = C.ClassifierState(*got[:4])
    t0 = time.perf_counter()
    for _ in range(steps):
        got = C.train_batch_parallel(state, idx, val, labels, mask, 1.0,
                                     owner, method="AROW", token=True)
        state = C.ClassifierState(*got[:4])
    jax.block_until_ready(got)
    out["ms_per_step"] = round((time.perf_counter() - t0) * 1e3 / steps, 3)
    del state, got
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--out", default="")
    ns = ap.parse_args()

    import jax

    from jubatus_tpu.ops import classifier as C

    bound = C._PREC_FULL_UPTO
    report = {"device": str(jax.devices()[0]), "steps": ns.steps}
    try:
        for name in ns.shapes.split(","):
            for form in ("full", "two"):
                report[f"{name}.{form}"] = _one(C, SHAPES[name],
                                                form == "two", ns.steps,
                                                ns.small)
                print(name, form, report[f"{name}.{form}"], file=sys.stderr,
                      flush=True)
    finally:
        C._PREC_FULL_UPTO = bound
    text = json.dumps(report, indent=1)
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
