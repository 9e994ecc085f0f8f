"""Ring all-to-all scans — context parallelism for sharded row tables.

``sharded_knn`` keeps queries replicated and merges tiny per-shard top-k
candidates with one all_gather; that is the right shape when the query
batch is small. When BOTH the query batch and the row table are too large
to replicate, this module provides the ring-attention-structured
alternative (the reference has no analog — its closest mechanism is CHT
row sharding + RPC fan-out, cht.cpp:107-143, SURVEY.md §5 "long-context"):

- queries stay put, sharded over the mesh axis (each device owns B/S);
- table blocks ROTATE around the ring with ``jax.lax.ppermute`` — S-1
  hops, each hop moving C/S rows to the neighbor over ICI while every
  device scans the block it currently holds;
- each device keeps a running top-k merge, so after S steps every query
  shard has seen the whole table without any device ever materializing
  it, and without any all_gather of candidates.

Per-device HBM footprint is O(B/S + 2·C/S) and the ICI traffic per hop is
exactly one block — the same overlap-compute-with-neighbor-transfer
pipeline ring attention uses for KV blocks.

``ring_scan`` is the generic building block (any per-block kernel +
associative carry merge); ``ring_hamming_topk`` / ``ring_euclid_topk``
instantiate it for the LSH/minhash and euclid_lsh engine backends.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from jubatus_tpu.parallel.sharded_knn import shard_table as shard_rows  # noqa: F401


def ring_scan(step_fn: Callable, carry, block, axis: str):
    """Rotate ``block`` once around the ring axis (must run inside
    shard_map). ``step_fn(carry, block, origin) -> carry`` is applied S
    times; ``origin`` is the shard index the block started on, so kernels
    can reconstruct global row ids. Returns the final carry.

    The ppermute send executes concurrently with the next step's compute
    (XLA schedules the collective-permute async on TPU), which is the
    whole point of the ring shape: the wire hides behind the scan.
    """
    s = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % s) for i in range(s)]

    def body(state, t):
        blk, c = state
        origin = (me - t) % s
        c = step_fn(c, blk, origin)
        # unconditional hop (the S-th rotation returns blocks home; a
        # lax.cond around a collective is not SPMD-safe)
        blk = jax.lax.ppermute(blk, axis, perm)
        return (blk, c), None

    (_, carry), _ = jax.lax.scan(body, (block, carry), jnp.arange(s))
    return carry


def _topk_merge(best_neg, best_idx, neg, idx, k: int):
    """Merge running [B, k] candidates with new [B, kk] ones."""
    negs = jnp.concatenate([best_neg, neg], axis=1)
    idxs = jnp.concatenate([best_idx, idx], axis=1)
    top, pos = jax.lax.top_k(negs, k)
    return top, jnp.take_along_axis(idxs, pos, axis=1)


def _ring_topk(mesh, queries, blocks, local_scores, k: int, axis: str):
    """Shared driver: ``local_scores(q_block, row_block) -> [b, c] scores``
    (HIGHER = better; negate distances before passing). ``blocks`` is any
    pytree of [C, ...] arrays row-sharded over ``axis`` (ppermute rotates
    pytrees whole). Returns (scores [B, k], global row ids [B, k]) with B
    sharded over ``axis``."""
    n_shards = mesh.shape[axis]
    c_total = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    if c_total % n_shards:
        raise ValueError(
            f"row count {c_total} not divisible by {n_shards} ring shards "
            "(pad the table to a multiple and mask the padding rows)")
    c_local = c_total // n_shards
    # never return more candidates than the table holds — padding slots
    # would carry +inf distance but a fabricated row id 0
    # (sharded_knn.sharded_hamming_topk clamps the same way)
    k = min(k, c_local * n_shards)

    def shard_fn(q, blk):
        kk = min(k, c_local)
        init = (
            jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.zeros((q.shape[0], k), jnp.int32),
        )

        def step(carry, block, origin):
            sc = local_scores(q, block).astype(jnp.float32)  # [b, c_local]
            neg, idx = jax.lax.top_k(sc, kk)
            gidx = idx + origin * c_local
            return _topk_merge(carry[0], carry[1], neg, gidx, k)

        best_neg, best_idx = ring_scan(step, init, blk, axis)
        return best_neg, best_idx

    q_spec = P(axis, *([None] * (queries.ndim - 1)))
    blk_specs = jax.tree_util.tree_map(
        lambda x: P(axis, *([None] * (x.ndim - 1))), blocks)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(q_spec, blk_specs),
        out_specs=(P(axis, None), P(axis, None)),
        check_vma=False,
    )
    return fn(queries, blocks)


@functools.partial(jax.jit, static_argnames=("mesh", "hash_num", "k", "axis"))
def ring_hamming_topk(
    mesh: Mesh,
    q_sigs: jax.Array,    # [B, W] uint32, sharded over `axis`
    row_sigs: jax.Array,  # [C, W] uint32, sharded over `axis`
    *,
    hash_num: int,
    k: int,
    axis: str = "shard",
    valid: Optional[jax.Array] = None,  # [C] bool, sharded over `axis`
) -> Tuple[jax.Array, jax.Array]:
    """Global top-k smallest hamming distance, both operands sharded.
    Returns (distances [B, k], global row ids [B, k]), B-sharded. ``valid``
    masks dead/padding rows out (it rotates with the table blocks)."""
    from jubatus_tpu.ops import knn

    if valid is None:
        def scores(q, blk):
            return -knn._hamming_distances_batch_xla(
                q, blk, hash_num=hash_num)

        blocks = row_sigs
    else:
        def scores(q, blk):
            sigs, v = blk
            d = knn._hamming_distances_batch_xla(q, sigs, hash_num=hash_num)
            return jnp.where(v[None, :], -d, -jnp.inf)

        blocks = (row_sigs, valid)

    neg, gidx = _ring_topk(mesh, q_sigs, blocks, scores, k, axis)
    return -neg, gidx


@functools.partial(jax.jit, static_argnames=("mesh", "k", "axis"))
def ring_euclid_topk(
    mesh: Mesh,
    q_dense: jax.Array,   # [B, D] float32, sharded over `axis`
    row_idx: jax.Array,   # [C, nnz] int32, sharded over `axis`
    row_val: jax.Array,   # [C, nnz] float32, sharded over `axis`
    *,
    k: int,
    axis: str = "shard",
    valid: Optional[jax.Array] = None,  # [C] bool, sharded over `axis`
) -> Tuple[jax.Array, jax.Array]:
    """Global top-k smallest euclidean distance over a sparse row table,
    both operands sharded. Returns (distances [B, k], ids [B, k]).
    ``valid`` masks dead/padding rows out (it rotates with the blocks),
    mirroring ring_hamming_topk; masked-out slots surface as +inf."""
    from jubatus_tpu.ops import knn

    if valid is None:
        def scores(q, blk):
            idx, val = blk
            return -jax.vmap(lambda q1: knn.euclid_distances(idx, val, q1))(q)

        blocks = (row_idx, row_val)
    else:
        def scores(q, blk):
            idx, val, v = blk
            d = jax.vmap(lambda q1: knn.euclid_distances(idx, val, q1))(q)
            return jnp.where(v[None, :], -d, -jnp.inf)

        blocks = (row_idx, row_val, valid)

    neg, gidx = _ring_topk(mesh, q_dense, blocks, scores, k, axis)
    return -neg, gidx
