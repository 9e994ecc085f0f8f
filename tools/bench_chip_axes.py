"""Measured numbers for the chip's claimed advantages:

  (1) multi-label L scaling — the CPU's per-example cost is linear in L
      (every label row is gathered for scores); the chip's packed [D, 2L]
      gather fetches all labels with one descriptor per feature, so the
      kernel is ~flat in L.
  (2) concurrent serving — the reference serializes every update under
      one write lock; added ingest threads buy lock contention. The chip
      answer is microbatching, whose e2e numbers bench_serving captures.
  (3) capacity — D=2^26 (1 GB f32 weights + 1 GB precision) via 2-way
      --shard-devices feature sharding.

CPU sides run anywhere; chip sides need the TPU and raise without it.
Results feed docs/PERF_NOTES.md's table.

Usage: PYTHONPATH=/root/repo python tools/bench_chip_axes.py [--cpu-only]
"""

from __future__ import annotations

import ctypes
import json
import sys
import time

import numpy as np

D_BITS = 20
D = 1 << D_BITS
K = 64
N_CPU = 100000
BATCH = 32768
L_SWEEP = (2, 8, 32)
THREAD_SWEEP = (1, 4, 16)


def _lib():
    from jubatus_tpu import native as nb

    out = nb.build("arow_baseline")
    if out is None:
        raise RuntimeError("baseline compile failed")
    lib = ctypes.CDLL(out)
    ptr_i = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    ptr_f = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.jt_arow_baseline_multi.restype = ctypes.c_double
    lib.jt_arow_baseline_multi.argtypes = [
        ptr_i, ptr_f, ptr_i, ctypes.c_int, ctypes.c_int, ctypes.c_int64,
        ctypes.c_int, ctypes.c_float]
    lib.jt_arow_baseline_locked.restype = ctypes.c_double
    lib.jt_arow_baseline_locked.argtypes = [
        ptr_i, ptr_f, ptr_i, ctypes.c_int, ctypes.c_int, ctypes.c_int64,
        ctypes.c_int, ctypes.c_float, ctypes.c_int]
    return lib


def cpu_axes() -> dict:
    lib = _lib()
    rng = np.random.default_rng(0)
    idx = rng.integers(1, D, size=(N_CPU, K), dtype=np.int32)
    val = rng.normal(size=(N_CPU, K)).astype(np.float32)
    out = {}
    for L in L_SWEEP:
        labels = rng.integers(0, L, size=N_CPU).astype(np.int32)
        sps = lib.jt_arow_baseline_multi(idx, val, labels, N_CPU, K, D, L,
                                         1.0)
        out[f"cpu_L{L}_samples_per_sec"] = round(sps, 1)
    labels2 = rng.integers(0, 2, size=N_CPU).astype(np.int32)
    for t in THREAD_SWEEP:
        sps = lib.jt_arow_baseline_locked(idx, val, labels2, N_CPU, K, D, 2,
                                          1.0, t)
        out[f"cpu_locked_{t}threads_samples_per_sec"] = round(sps, 1)
    return out


def chip_l_sweep() -> dict:
    """ops.train_batch at L in L_SWEEP on the bench device (flat-in-L is
    the claim: the packed [D, 2L] layout gathers every label's values
    with one descriptor per feature).

    chip_L* keys come from the TPU or not at all: any other platform
    raises."""
    import jax
    import jax.numpy as jnp

    from jubatus_tpu.ops import classifier as C

    plat = jax.devices()[0].platform
    if plat != "tpu":
        raise RuntimeError(f"chip_l_sweep needs the TPU; jax found {plat!r}")
    rng = np.random.default_rng(0)
    out = {}
    val = jnp.asarray(rng.normal(size=(BATCH, K)).astype(np.float32))
    idxs = [jnp.asarray(rng.integers(1, D, size=(BATCH, K), dtype=np.int32))
            for _ in range(5)]
    for L in L_SWEEP:
        labels = jnp.asarray(rng.integers(0, L, size=BATCH).astype(np.int32))
        mask = jnp.ones(L, dtype=bool)
        st = C.init_state(L, D, confidence=True)
        st = C.train_batch(st, idxs[0], val, labels, mask, 1.0,
                           method="AROW")
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        for i in range(1, 5):
            st = C.train_batch(st, idxs[i], val, labels, mask, 1.0,
                               method="AROW")
        jax.block_until_ready(st)
        sps = 4 * BATCH / (time.perf_counter() - t0)
        out[f"chip_L{L}_samples_per_sec"] = round(sps, 1)
        del st
    return out


def chip_shard_capacity() -> dict:
    """D=2^26 AROW (2 GB of state with covariance) via 2-way feature
    sharding — beyond one bench-host transfer budget; correctness +
    throughput on whatever devices exist (virtual CPU devices prove the
    sharding compiles; the real capacity point needs 2 chips)."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"chip_shard_note": f"one visible device; --shard-devices "
                                   f"capacity point needs >=2 (have {n_dev})"}
    if jax.devices()[0].platform == "cpu":
        return {"chip_shard_note": "backend is cpu (virtual devices); "
                                   "capacity point needs real chips"}
    from jax.sharding import Mesh

    from jubatus_tpu.models.classifier import ClassifierDriver

    mesh = Mesh(jax.local_devices()[:2], axis_names=("shard",))
    d = ClassifierDriver(
        {"method": "AROW", "parameter": {"regularization_weight": 1.0},
         "converter": {"num_rules": [{"key": "*", "type": "num"}]}},
        dim_bits=26, mesh=mesh)
    rng = np.random.default_rng(0)
    b = 8192
    idx = rng.integers(1, 1 << 26, size=(b, K)).astype(np.int32)
    val = rng.normal(size=(b, K)).astype(np.float32)
    lidx = rng.integers(0, 2, size=b).astype(np.int32)
    d.train_indexed(["a", "b"], lidx, idx, val)
    jax.block_until_ready(d.state.w)
    t0 = time.perf_counter()
    for _ in range(3):
        d.train_indexed(["a", "b"], lidx, idx, val)
    jax.block_until_ready(d.state.w)
    sps = 3 * b / (time.perf_counter() - t0)
    return {"chip_shard2_d26_samples_per_sec": round(sps, 1)}


def main() -> int:
    failed = False
    try:
        out = cpu_axes()
    except (RuntimeError, OSError) as e:  # no toolchain: still print JSON
        out = {"cpu_axes_error": repr(e)[:160]}
        failed = True
    if "--cpu-only" not in sys.argv:
        from jubatus_tpu.utils.compile_cache import configure

        configure()
        try:
            out.update(chip_l_sweep())
        except Exception as e:  # noqa: BLE001
            out["chip_l_error"] = repr(e)[:160]
            failed = True
        try:
            out.update(chip_shard_capacity())
        except Exception as e:  # noqa: BLE001
            out["chip_shard_error"] = repr(e)[:160]
            failed = True
    print(json.dumps(out, indent=1))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
