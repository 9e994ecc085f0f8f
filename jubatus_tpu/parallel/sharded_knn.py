"""Mesh-sharded similarity scans — the TPU replacement for CHT row
sharding (SURVEY.md §5 "long-context": the reference scales a dimension
across nodes with consistent-hash row placement, cht.cpp:107-143; on a
pod the same capacity scaling is a static shard of the signature table
over the mesh's ``shard`` axis).

One query batch fans out to every shard implicitly (the table is sharded,
the query replicated), each device scans its slice of the table with the
same kernels the single-chip path uses (ops/knn; pallas on TPU), takes a
LOCAL top-k, and one tiny all_gather of [k]-sized candidates feeds the
log-depth on-device tree merge (``merge_topk``) — O(shards·k) bytes over
ICI and log2(shards) selection passes instead of O(rows). All three
hash methods (lsh/minhash/euclid_lsh) ride the same driver; an optional
``valid`` row mask keeps dead/padding slots out of the results (the
single-chip path's live-mask, models/_nn_backend.py).

For batches where the QUERIES don't fit replicated either, use the ring
strategy (parallel/ring.py) instead.

Row placement: ``coord.cht.shard_for(row_id, n_shards)`` keeps placement
stable and hash-based like the ring; slot index within the shard is the
store's business. Global ids returned by queries are ``shard * capacity +
local_slot`` — decode with ``divmod(gid, capacity)``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_table(mesh: Mesh, table, axis: str = "shard"):
    """Place [C, ...] rows sharded over the mesh axis (C must be a multiple
    of the axis size; pad the store capacity to match). Shared by the
    all-gather (this module) and ring (parallel/ring.py) scan strategies."""
    spec = P(axis, *([None] * (table.ndim - 1)))
    return jax.device_put(table, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P()))


def merge_topk(scores, ids, k: int):
    """Log-depth on-device merge of per-shard top-k candidate sets.

    ``scores``/``ids``: [S, B, kk] partials (HIGHER score = better).
    Pairwise tree reduction: each level merges shard pairs with one
    top_k over the concatenated 2·kk candidates, halving S per level —
    log2(S) selection passes over O(k)-sized sets instead of one flat
    [B, S·kk] sort whose cost grows linearly with the shard count.
    Selection is associative (top-k of a union == top-k of per-part
    top-ks), so the result matches the flat merge exactly; equal-score
    ties are pinned to ascending id so the result is deterministic and
    independent of shard pairing order. Returns ([B, k'], [B, k'])
    with k' = min(k, S·kk)."""
    s = scores.shape[0]
    k = min(k, s * scores.shape[2])
    while s > 1:
        half = s // 2
        lo_s, hi_s = scores[:half], scores[half: 2 * half]
        lo_i, hi_i = ids[:half], ids[half: 2 * half]
        cat_s = jnp.concatenate([lo_s, hi_s], axis=-1)     # [half, B, 2kk]
        cat_i = jnp.concatenate([lo_i, hi_i], axis=-1)
        kk = min(k, cat_s.shape[-1])
        merged_s, pos = jax.lax.top_k(cat_s, kk)
        merged_i = jnp.take_along_axis(cat_i, pos, axis=-1)
        if s % 2:                                          # odd: carry last
            carry_s, carry_i = scores[-1:], ids[-1:]
            if carry_s.shape[-1] > kk:    # keep the carry's own top-kk
                carry_s, pos = jax.lax.top_k(carry_s, kk)
                carry_i = jnp.take_along_axis(carry_i, pos, axis=-1)
            pad = kk - carry_s.shape[-1]
            if pad > 0:    # widen with -inf sentinels (never selected)
                carry_s = jnp.pad(carry_s, ((0, 0), (0, 0), (0, pad)),
                                  constant_values=-jnp.inf)
                carry_i = jnp.pad(carry_i, ((0, 0), (0, 0), (0, pad)))
            merged_s = jnp.concatenate([merged_s, carry_s], axis=0)
            merged_i = jnp.concatenate([merged_i, carry_i], axis=0)
        scores, ids = merged_s, merged_i
        s = scores.shape[0]
    out_s, out_i = scores[0], ids[0]
    if out_s.shape[-1] > k:
        out_s, pos = jax.lax.top_k(out_s, k)
        out_i = jnp.take_along_axis(out_i, pos, axis=-1)
    # pin tie order: score desc, then id asc (−(−inf) = +inf keeps
    # dead/padding sentinels last) — deterministic across shard counts
    order = jnp.lexsort((out_i, -out_s), axis=-1)
    out_s = jnp.take_along_axis(out_s, order, axis=-1)
    out_i = jnp.take_along_axis(out_i, order, axis=-1)
    return out_s, out_i


def _sharded_topk(mesh, q, table, local_scores, k: int, axis: str,
                  valid=None):
    """Generic all-gather-merge driver. ``local_scores(q, rows) -> [B, c]``
    (HIGHER = better; negate distances). Returns (scores [B, k'],
    global ids [B, k']) replicated, k' = min(k, C)."""
    n_shards = mesh.shape[axis]
    c_local = table.shape[0] // n_shards
    k = min(k, c_local * n_shards)

    def scan(q, rows, *v):
        sc = local_scores(q, rows).astype(jnp.float32)     # [B, c_local]
        if v:
            sc = jnp.where(v[0][None, :], sc, -jnp.inf)
        kk = min(k, c_local)
        neg, idx = jax.lax.top_k(sc, kk)                   # [B, kk]
        shard_id = jax.lax.axis_index(axis)
        gidx = idx + shard_id * c_local                    # global ids
        # merge across shards: gather the tiny candidate sets, then the
        # log-depth tree merge (O(S·k) wire bytes, log2(S) selections)
        negs = jax.lax.all_gather(neg, axis, tiled=False)  # [S, B, kk]
        gidxs = jax.lax.all_gather(gidx, axis, tiled=False)
        return merge_topk(negs, gidxs, k)

    in_specs = [P(), P(axis, *([None] * (table.ndim - 1)))]
    args = [q, table]
    if valid is not None:
        in_specs.append(P(axis))
        args.append(valid)
    fn = shard_map(
        scan, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(*args)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "method", "hash_num", "axis"))
def sharded_distances(
    mesh: Mesh,
    q_sigs: jax.Array,    # [B, W/H] replicated
    row_sigs: jax.Array,  # [C, W/H] sharded over `axis`
    *,
    method: str,
    hash_num: int,
    axis: str = "shard",
) -> jax.Array:
    """FULL distance matrix [B, C] from a sharded table — each device
    scans its slice, one all_gather assembles the rows. For consumers
    that need every distance (LOF's lrd cache), not just top-k: HBM holds
    only C/S signature rows per device; the [B, C] float result is the
    caller's to size."""
    from jubatus_tpu.ops import knn

    scorer = {
        "lsh": lambda q, r: knn._hamming_distances_batch_xla(
            q, r, hash_num=hash_num),
        "minhash": lambda q, r: knn._minhash_distances_batch_xla(q, r),
        "euclid_lsh": lambda q, r: knn.euclid_lsh_distances_batch(
            q, r, hash_num=hash_num),
    }[method]

    def scan(q, rows):
        d = scorer(q, rows).astype(jnp.float32)            # [B, c_local]
        parts = jax.lax.all_gather(d, axis, tiled=False)   # [S, B, c_local]
        return jnp.transpose(parts, (1, 0, 2)).reshape(q.shape[0], -1)

    fn = shard_map(
        scan, mesh=mesh,
        in_specs=(P(), P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q_sigs, row_sigs)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "hash_num", "k", "axis"))
def sharded_hamming_topk(
    mesh: Mesh,
    q_sigs: jax.Array,    # [B, W] uint32, replicated
    row_sigs: jax.Array,  # [C, W] uint32, sharded over `axis`
    *,
    hash_num: int,
    k: int,
    axis: str = "shard",
    valid: Optional[jax.Array] = None,  # [C] bool, sharded over `axis`
) -> Tuple[jax.Array, jax.Array]:
    """Global top-k nearest (smallest hamming distance) over the sharded
    table. Returns (distances [B, k], global row indices [B, k])."""
    from jubatus_tpu.ops import knn

    def scores(q, rows):
        return -knn._hamming_distances_batch_xla(q, rows, hash_num=hash_num)

    neg, gidx = _sharded_topk(mesh, q_sigs, row_sigs, scores, k, axis, valid)
    return -neg, gidx


@functools.partial(jax.jit, static_argnames=("mesh", "k", "axis"))
def sharded_minhash_topk(
    mesh: Mesh,
    q_sigs: jax.Array,    # [B, H] uint32, replicated
    row_sigs: jax.Array,  # [C, H] uint32, sharded over `axis`
    *,
    k: int,
    axis: str = "shard",
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k smallest (1 - weighted-Jaccard estimate) distance."""
    from jubatus_tpu.ops import knn

    def scores(q, rows):
        return -knn._minhash_distances_batch_xla(q, rows)

    neg, gidx = _sharded_topk(mesh, q_sigs, row_sigs, scores, k, axis, valid)
    return -neg, gidx


@functools.partial(jax.jit,
                   static_argnames=("mesh", "hash_num", "k", "axis"))
def sharded_euclid_lsh_topk(
    mesh: Mesh,
    q_projs: jax.Array,   # [B, H] float32, replicated
    row_projs: jax.Array, # [C, H] float32, sharded over `axis`
    *,
    hash_num: int,
    k: int,
    axis: str = "shard",
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k smallest JL-estimated euclidean distance."""
    from jubatus_tpu.ops import knn

    def scores(q, rows):
        return -knn.euclid_lsh_distances_batch(q, rows, hash_num=hash_num)

    neg, gidx = _sharded_topk(mesh, q_projs, row_projs, scores, k, axis, valid)
    return -neg, gidx
