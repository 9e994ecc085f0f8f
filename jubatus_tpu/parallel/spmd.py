"""SPMD multi-chip training step: data-parallel replicas × feature-sharded
weight tables.

This is the pod-scale execution path for the linear engines. The mesh has two
axes (parallel/mesh.py):

- ``replica`` (dp): each replica trains on its own microbatch stream — the
  reference's N servers (SURVEY.md §0). The mix is a psum of diffs over this
  axis.
- ``shard`` (tp): the hashed feature dimension D is sharded, so each chip
  holds [L, D/S] of every label row — the reference's CHT key-space
  partitioning (cht.cpp:107-143) as static mesh placement. Scores are
  computed as shard-local partial dot products psum'd over ``shard`` —
  collectives ride ICI.

All computation is inside one shard_map'd jitted step: per-replica vectorized
train (ops/classifier.train_rows, the single-chip path's own body), optionally
followed by the mix collective — so a mix round costs one AllReduce, no host round
trips (the north-star design, SURVEY.md §2.2).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jubatus_tpu.ops.classifier import CONFIDENCE_METHODS, train_rows
from jubatus_tpu.parallel.sharded_model import _owned


def _state_pspec(mesh: Mesh) -> P:
    return P("replica", None, "shard") if "shard" in mesh.axis_names else P("replica")


class SpmdClassifierState(NamedTuple):
    """Stacked-over-replicas classifier state.

    w, dw, prec, dprec: [R, L, D] — sharded P('replica', None, 'shard').
    """

    w: jax.Array
    dw: jax.Array
    prec: jax.Array
    dprec: jax.Array


def init_spmd_state(
    mesh: Mesh, num_labels: int, dim: int, confidence: bool = True
) -> SpmdClassifierState:
    r = mesh.shape["replica"]
    spec = NamedSharding(mesh, _state_pspec(mesh))
    shape = (r, num_labels, dim)
    zeros = jax.device_put(jnp.zeros(shape, jnp.float32), spec)
    ones = jax.device_put(jnp.ones(shape, jnp.float32), spec)
    return SpmdClassifierState(
        w=zeros, dw=zeros, prec=ones if confidence else zeros, dprec=zeros
    )


def make_spmd_train_step(mesh: Mesh, *, method: str = "AROW", param: float = 1.0,
                         mix: bool = True):
    """Build the jitted multi-chip train(+mix) step.

    Returned fn: (state, idx [R,B,K], val [R,B,K], labels [R,B],
    label_mask [L]) -> state. Batch arrays are sharded over 'replica';
    label_mask is replicated.
    """
    confidence = method in CONFIDENCE_METHODS
    n_shards = mesh.shape.get("shard", 1)
    n_replicas = mesh.shape["replica"]

    def _shard_psum(x):
        return jax.lax.psum(x, "shard") if n_shards > 1 else x

    def body(w, dw, prec, dprec, idx, val, labels, label_mask):
        # local leaves: w [1, L, Dl]; idx/val [1, B, K]; labels [1, B]
        w, dw, prec, dprec = w[0], dw[0], prec[0], dprec[0]
        idx, val, labels = idx[0], val[0], labels[0]
        if n_shards > 1:
            idx, val = _owned(idx, val, w.shape[1], "shard")
        # partial scores from the local feature shard, reduced over ICI
        w, dw, prec, dprec = train_rows(
            w, dw, prec, dprec, idx, val, labels, label_mask, param,
            method=method, reduce=_shard_psum)

        if mix:
            # THE mix round: one AllReduce over the replica axis
            total_dw = jax.lax.psum(dw, "replica")
            w = w + total_dw / n_replicas
            dw = jnp.zeros_like(dw)
            if confidence:
                total_dp = jax.lax.psum(dprec, "replica")
                prec = prec + total_dp
                dprec = jnp.zeros_like(dprec)

        return (w[None], dw[None], prec[None], dprec[None])

    state_spec = _state_pspec(mesh)
    batch_spec = P("replica")

    @jax.jit
    def step(state: SpmdClassifierState, idx, val, labels, label_mask):
        out = shard_map(
            body,
            mesh=mesh,
            in_specs=(state_spec, state_spec, state_spec, state_spec,
                      batch_spec, batch_spec, batch_spec, P()),
            out_specs=(state_spec, state_spec, state_spec, state_spec),
        )(state.w, state.dw, state.prec, state.dprec, idx, val, labels, label_mask)
        return SpmdClassifierState(*out)

    return step
