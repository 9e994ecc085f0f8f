"""Ring all-to-all scan tests (parallel/ring.py) on the 8-device CPU mesh.

The ring result must exactly match a dense single-device scan: same
distances, same winner set — the rotation is an execution strategy, not
an approximation. Also checks the generic ring_scan visits every block
exactly once with correct origin attribution.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from jubatus_tpu.ops import knn
from jubatus_tpu.parallel.mesh import grid_mesh
from jubatus_tpu.parallel.ring import (
    ring_euclid_topk,
    ring_hamming_topk,
    ring_scan,
    shard_rows,
)

S = 8  # conftest forces an 8-device CPU platform


@pytest.fixture(scope="module")
def mesh():
    return grid_mesh(replica=1, shard=S)


def test_ring_scan_visits_every_block_once(mesh):
    """Each device must accumulate sum over ALL blocks, with origin ids
    summing to 0+1+...+S-1 — catches rotation/origin bookkeeping bugs."""
    blocks = jnp.arange(S, dtype=jnp.float32).reshape(S, 1) * 10.0

    def shard_fn(blk):
        def step(carry, block, origin):
            total, origin_sum = carry
            return total + block.sum(), origin_sum + origin

        total, origin_sum = ring_scan(
            step, (jnp.float32(0), jnp.int32(0)), blk, "shard")
        return total[None], origin_sum[None]

    total, origin_sum = shard_map(
        shard_fn, mesh=mesh, in_specs=(P("shard", None),),
        out_specs=(P("shard"), P("shard")), check_vma=False,
    )(blocks)
    np.testing.assert_allclose(np.asarray(total), np.full(S, 10.0 * sum(range(S))))
    assert np.asarray(origin_sum).tolist() == [sum(range(S))] * S


def _sparse_rows(rng, n, nnz, dim):
    idx = rng.integers(1, dim, size=(n, nnz)).astype(np.int32)
    val = rng.normal(size=(n, nnz)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(val)


def test_ring_hamming_matches_dense(mesh, rng):
    hash_num, dim, nnz = 64, 1 << 12, 8
    B, C, k = 16, 64, 5
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_sigs = knn.lsh_signature(qi, qv, hash_num=hash_num)
    row_sigs = knn.lsh_signature(ri, rv, hash_num=hash_num)

    d, gidx = ring_hamming_topk(
        mesh,
        shard_rows(mesh, q_sigs),
        shard_rows(mesh, row_sigs),
        hash_num=hash_num, k=k,
    )
    d, gidx = np.asarray(d), np.asarray(gidx)

    dense = np.asarray(
        knn._hamming_distances_batch_xla(q_sigs, row_sigs, hash_num=hash_num))
    for b in range(B):
        want = np.sort(dense[b])[:k]
        np.testing.assert_allclose(np.sort(d[b]), want, rtol=1e-6)
        # returned ids really score those distances
        np.testing.assert_allclose(
            np.sort(dense[b][gidx[b]]), want, rtol=1e-6)


def test_ring_euclid_matches_dense(mesh, rng):
    dim, nnz = 1 << 10, 6
    B, C, k = 8, 32, 4
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_dense = jnp.stack([knn.densify(qi[b], qv[b], dim=dim) for b in range(B)])

    d, gidx = ring_euclid_topk(
        mesh,
        shard_rows(mesh, q_dense),
        shard_rows(mesh, ri),
        shard_rows(mesh, rv),
        k=k,
    )
    d, gidx = np.asarray(d), np.asarray(gidx)

    for b in range(B):
        dense = np.asarray(knn.euclid_distances(ri, rv, q_dense[b]))
        want = np.sort(dense)[:k]
        np.testing.assert_allclose(np.sort(d[b]), want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.sort(dense[gidx[b]]), want,
                                   rtol=1e-5, atol=1e-5)


def test_ring_k_exceeding_table_clamps(mesh, rng):
    """k > C must clamp to C (no +inf/fabricated-id padding columns)."""
    hash_num, dim, nnz = 32, 1 << 10, 4
    B, C = 8, 16
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_sigs = knn.lsh_signature(qi, qv, hash_num=hash_num)
    row_sigs = knn.lsh_signature(ri, rv, hash_num=hash_num)
    d, gidx = ring_hamming_topk(
        mesh, shard_rows(mesh, q_sigs), shard_rows(mesh, row_sigs),
        hash_num=hash_num, k=24,
    )
    assert d.shape == (B, C) and gidx.shape == (B, C)
    assert np.isfinite(np.asarray(d)).all()
    for b in range(B):
        assert sorted(np.asarray(gidx)[b].tolist()) == list(range(C))


def test_ring_k_larger_than_local_block(mesh, rng):
    """k spanning multiple blocks: the running merge must keep candidates
    from several origins (c_local = 2 here, k = 6)."""
    hash_num, dim, nnz = 32, 1 << 10, 4
    B, C, k = 8, 16, 6
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_sigs = knn.lsh_signature(qi, qv, hash_num=hash_num)
    row_sigs = knn.lsh_signature(ri, rv, hash_num=hash_num)

    d, gidx = ring_hamming_topk(
        mesh, shard_rows(mesh, q_sigs), shard_rows(mesh, row_sigs),
        hash_num=hash_num, k=k,
    )
    d, gidx = np.asarray(d), np.asarray(gidx)
    dense = np.asarray(
        knn._hamming_distances_batch_xla(q_sigs, row_sigs, hash_num=hash_num))
    for b in range(B):
        np.testing.assert_allclose(np.sort(d[b]), np.sort(dense[b])[:k],
                                   rtol=1e-6)
        assert len(set(gidx[b].tolist())) == k  # no duplicate winners


def test_ring_euclid_valid_mask_hides_dead_rows(mesh, rng):
    """Deleted/padding rows must never surface as finite euclid hits
    (ADVICE round 1: ring_euclid_topk had no valid mask)."""
    dim, nnz = 1 << 10, 6
    B, C, k = 8, 32, 4
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_dense = jnp.stack([knn.densify(qi[b], qv[b], dim=dim) for b in range(B)])
    valid = np.ones(C, bool)
    valid[::3] = False

    d, gidx = ring_euclid_topk(
        mesh,
        shard_rows(mesh, q_dense),
        shard_rows(mesh, ri),
        shard_rows(mesh, rv),
        k=k,
        valid=shard_rows(mesh, jnp.asarray(valid)),
    )
    d, gidx = np.asarray(d), np.asarray(gidx)
    finite = np.isfinite(d)
    assert valid[gidx[finite]].all(), "masked row surfaced as a finite hit"
    for b in range(B):
        dense = np.asarray(knn.euclid_distances(ri, rv, q_dense[b]))
        want = np.sort(np.where(valid, dense, np.inf))[:k]
        np.testing.assert_allclose(np.sort(d[b]), want, rtol=1e-5, atol=1e-5)


def test_ring_rejects_indivisible_row_count(mesh, rng):
    """C % shards != 0 must raise, not silently drop rows."""
    dim, nnz, hash_num = 1 << 10, 4, 32
    B, C = 8, 13  # 13 % 8 != 0
    qi, qv = _sparse_rows(rng, B, nnz, dim)
    ri, rv = _sparse_rows(rng, C, nnz, dim)
    q_sigs = knn.lsh_signature(qi, qv, hash_num=hash_num)
    row_sigs = knn.lsh_signature(ri, rv, hash_num=hash_num)
    with pytest.raises(ValueError, match="not divisible"):
        ring_hamming_topk(mesh, q_sigs, row_sigs, hash_num=hash_num, k=4)
