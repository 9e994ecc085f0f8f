"""A/B: pre-scatter dedup via host-computed permutation.

The d2^24 AROW step is scatter-bound (4 scatter-adds ~= 123 ms vs ~60 ms
everything else; docs/PERF_NOTES.md). Hypothesis under test: since the
[B, K] indices are known HOST-side at parse time (native/fast_ingest.cpp
owns the batch), the C++ side can compute — off the device's critical
path — a sort permutation + segment boundaries, letting the device
replace each scatter-add with
    reorder-gather (updates[perm]) -> segment_sum(sorted ids) ->
    scatter into the n_unique touched rows.

What host pre-compute CANNOT do: pre-sum duplicate indices across
examples — the update value is alpha_b * x[b, k] with alpha computed ON
DEVICE per example, so only the permutation (value-independent) ships.

Variants timed (same process, alternating trials, median):
  A  plain scatter-add of [B*K] updates (the shipping kernel's shape)
  B  updates[perm] -> segment_sum(indices_are_sorted=True) -> scatter
     of n_unique rows (permutation/segments precomputed host-side, cost
     EXCLUDED — models the C++ overlap)

Usage: PYTHONPATH=/root/repo python tools/bench_scatter_dedup.py
Prints one JSON dict; feed the verdict into docs/PERF_NOTES.md.
"""

from __future__ import annotations

import json
import time

import numpy as np

D_BITS = 24
B = 32768
K = 64
TRIALS = 5


def main() -> None:
    import jax
    import jax.numpy as jnp

    from jubatus_tpu.utils.compile_cache import configure

    configure()
    d = 1 << D_BITS
    rng = np.random.default_rng(0)
    idx_host = rng.integers(1, d, size=B * K, dtype=np.int32)
    # host-side precompute (the part C++ would overlap with device work)
    t0 = time.perf_counter()
    perm = np.argsort(idx_host, kind="stable")
    sorted_idx = idx_host[perm]
    uniq, seg_start = np.unique(sorted_idx, return_index=True)
    seg_ids = np.zeros(B * K, np.int32)
    seg_ids[seg_start[1:]] = 1
    seg_ids = np.cumsum(seg_ids, dtype=np.int32)
    host_ms = (time.perf_counter() - t0) * 1e3
    n_uniq = len(uniq)

    table = jnp.zeros((d,), jnp.float32)
    upd = jnp.asarray(rng.normal(size=B * K).astype(np.float32))
    idx = jnp.asarray(idx_host)
    j_perm = jnp.asarray(perm.astype(np.int32))
    j_seg = jnp.asarray(seg_ids)
    j_uniq = jnp.asarray(uniq.astype(np.int32))

    @jax.jit
    def plain(tab, u):
        return tab.at[idx].add(u)

    @jax.jit
    def dedup(tab, u):
        s = jax.ops.segment_sum(u[j_perm], j_seg, num_segments=n_uniq,
                                indices_are_sorted=True)
        return tab.at[j_uniq].add(s, unique_indices=True,
                                  indices_are_sorted=True)

    # parity first
    a = plain(table, upd)
    b = dedup(table, upd)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=1e-5)

    out = {"platform": jax.devices()[0].platform, "n_unique": int(n_uniq),
           "dup_fraction": round(1.0 - n_uniq / (B * K), 4),
           "host_precompute_ms": round(host_ms, 1)}
    for name, fn in (("plain_scatter", plain), ("dedup_scatter", dedup)):
        fn(table, upd).block_until_ready()  # warm
        times = []
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            fn(table, upd).block_until_ready()
            times.append(time.perf_counter() - t0)
        out[f"{name}_ms"] = round(float(np.median(times)) * 1e3, 2)
    out["speedup"] = round(out["plain_scatter_ms"] /
                           out["dedup_scatter_ms"], 3)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
