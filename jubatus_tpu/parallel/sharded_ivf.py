"""Mesh-sharded two-phase IVF queries — the ANN tier over the sharded
row store (ISSUE 16).

Same data placement as the exact scan (parallel/sharded_knn.py): the
signature table is sharded over the mesh's ``shard`` axis, queries are
replicated, results merge through the identical log-depth
``merge_topk`` tree. What changes is what each device SCANS:

  exact   every live row in the local arena        O(C/S) per query
  ivf     probe top-``nprobe`` cells against the   O(K + P·cap)
          replicated centroid table (one [B, K]×[K, E] matmul), gather
          ONLY those cells' member slots from the local cell table
          ([n_cells, cap] int32, −1-padded; parallel/row_store.py
          CellArenas), rescore the gathered rows with the method's
          EXACT distance math

Each shard probes its OWN top-P cells — cell population differs per
shard, so the probe set does too; no cross-shard coordination is
needed because the merge is over exact distances either way. The
cross-shard wire cost is unchanged: one all_gather of [S, B, kk]
candidates, log2(S) merge levels.

The cell-slot table is sharded P(axis) on its leading [S·n_cells] dim,
so device ``s`` sees exactly its own [n_cells, cap] block and gathered
LOCAL slots index the local arena block directly; global ids come out
as ``local_slot + s · capacity_per_shard`` exactly like the exact path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from jubatus_tpu.ops.ivf import candidate_sig_distances, pairwise_sq_dists
from jubatus_tpu.parallel.sharded_knn import merge_topk


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "method", "hash_num", "k", "nprobe", "axis"))
def sharded_ivf_topk(
    mesh: Mesh,
    q_sigs: jax.Array,      # [B, W/H] replicated (method signature space)
    q_emb: jax.Array,       # [B, E] float32 replicated (probe space)
    row_sigs: jax.Array,    # [C, W/H] sharded over `axis`
    centroids: jax.Array,   # [n_cells, E] float32 replicated
    cell_slots: jax.Array,  # [S*n_cells, cap] int32 sharded over `axis`
    *,
    method: str,
    hash_num: int,
    k: int,
    nprobe: int,
    axis: str = "shard",
) -> Tuple[jax.Array, jax.Array]:
    """Global approximate top-k over the sharded table: per-shard cell
    probe + gathered exact rescore, merged with the log-depth tree.

    Returns (distances [B, k'], global row ids [B, k']) replicated;
    k' = min(k, S · min(k, nprobe·cap)). Slots short of k rows carry
    non-finite distances (their ids are meaningless) — same contract as
    the exact path's dead-slot masking."""
    n_shards = mesh.shape[axis]
    c_local = row_sigs.shape[0] // n_shards
    n_cells = cell_slots.shape[0] // n_shards
    nprobe = min(nprobe, n_cells)

    def scan(qs, qe, rows, cents, cells):
        # phase 1 — probe: rank this shard's centroid table (replicated,
        # tiny) and take the nprobe nearest cells per query
        d2 = pairwise_sq_dists(qe, cents)                  # [B, n_cells]
        _, sel = jax.lax.top_k(-d2, nprobe)                # [B, P]
        # phase 2 — gather only the probed cells' member slots and
        # rescore them with the exact signature distance
        cand = cells[sel].reshape(qs.shape[0], -1)         # [B, P·cap]
        ok = cand >= 0
        safe = jnp.maximum(cand, 0)
        d = candidate_sig_distances(qs, rows[safe], method=method,
                                    hash_num=hash_num)
        sc = jnp.where(ok, -d.astype(jnp.float32), -jnp.inf)
        kk = min(k, sc.shape[-1])
        neg, pos = jax.lax.top_k(sc, kk)                   # [B, kk]
        lslot = jnp.take_along_axis(safe, pos, axis=-1)
        gidx = lslot + jax.lax.axis_index(axis) * c_local
        negs = jax.lax.all_gather(neg, axis, tiled=False)  # [S, B, kk]
        gidxs = jax.lax.all_gather(gidx, axis, tiled=False)
        return merge_topk(negs, gidxs, k)

    fn = shard_map(
        scan, mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(), P(axis, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    neg, gidx = fn(q_sigs, q_emb, row_sigs, centroids, cell_slots)
    return -neg, gidx
