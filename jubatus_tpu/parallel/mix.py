"""The mix engine: model averaging as an XLA collective.

Reference semantics (linear_mixer.cpp:437-559, SURVEY.md §3.3): master pulls
diffs from all replicas, folds them pairwise with mixable->mix, broadcasts the
folded diff, every replica applies it via put_diff and clears its local diff.
The fold is the AllReduce combiner; because every jubatus_tpu diff is a pytree
whose mix is elementwise addition (ops/* keep updates additive by design),
the whole round is exactly `psum(diff)` + local put_diff — symmetric across
replicas, no master election, order-independent.

Two execution paths share the same Mixable protocol:

- ``allreduce_diffs``: the TPU path. Stacked per-replica diffs live sharded
  over the mesh's ``replica`` axis; a shard_map'd psum reduces them over ICI.
- ``LocalMixGroup``: the in-process path used by tests and by multi-engine
  simulation (the reference's linear_communication_stub seam,
  linear_mixer_test.cpp:65-112): N driver instances mix through host memory.

Schema sync: engines whose array rows are keyed by a dynamic vocabulary
(classifier labels) must align rows before arrays can be summed. Mixables may
implement ``sync_schema(union_of_schemas)``; the group/cluster computes the
sorted union of all replicas' schemas first (on TPU pods: a tiny host-side
allgather over DCN, out of the hot path), each replica permutes/grows its
arrays to the canonical schema, then the array psum runs.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@runtime_checkable
class Mixable(Protocol):
    """The linear-mixable protocol (reference core mixable, SURVEY.md §2.9).

    get_diff returns a pytree of arrays/scalars; mix is elementwise addition
    (performed by the engine, not the mixable); put_diff absorbs the reduced
    diff and resets local accumulation, returning False if the local model is
    obsolete (triggers full-model recovery, linear_mixer.cpp:598-632).
    """

    def get_diff(self) -> Any: ...

    def put_diff(self, diff: Any) -> bool: ...

    # Optional: a custom associative combiner ``mix(acc, diff) -> acc``
    # (the reference's mixable->mix, linear_mixer.cpp:481-499). When present
    # the group folds with it instead of elementwise pytree addition —
    # engines with sparse/dict-shaped diffs (bandit) use this to avoid
    # shipping dense zero matrices.


def tree_sum(diffs: Sequence[Any]) -> Any:
    """Host-side fold of diff pytrees (the reference's pairwise fold —
    associative here, so order is irrelevant).

    Leaves whose LEADING dimension disagrees are zero-padded to the
    larger row count before adding: row-trimmed label diffs
    (models/classifier.py _ClassifierMixable) can legitimately differ by
    a row when a replica trained a novel label between the round's
    schema sync and its get_diff — the pad reproduces the old
    full-capacity semantics (absent rows contribute zeros) instead of
    aborting the round on a shape error."""

    def add(a, b):
        an = getattr(a, "shape", None)
        bn = getattr(b, "shape", None)
        if an and bn and len(an) == len(bn) and an != bn and \
                an[1:] == bn[1:]:
            import numpy as _np

            rows = max(an[0], bn[0])
            if an[0] < rows:
                a = _np.concatenate(
                    [_np.asarray(a),
                     _np.zeros((rows - an[0],) + tuple(an[1:]),
                               _np.asarray(a).dtype)])
            if bn[0] < rows:
                b = _np.concatenate(
                    [_np.asarray(b),
                     _np.zeros((rows - bn[0],) + tuple(bn[1:]),
                               _np.asarray(b).dtype)])
        return a + b

    acc = diffs[0]
    for d in diffs[1:]:
        acc = jax.tree_util.tree_map(add, acc, d)
    return acc


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "compress"))
def _psum_stacked(stacked, *, mesh: Mesh, axis: str, compress: bool):
    """psum a pytree whose leaves are stacked [n_replicas, ...] and sharded
    over `axis`; result is replicated (every replica holds the total).

    compress=True moves f32 leaves over the interconnect as bfloat16 —
    half the ICI/DCN bytes per mix round at ~3 decimal digits of diff
    precision (the EQuARX-style quantized-allreduce tradeoff; additive
    diffs tolerate it because put_diff folds into an f32 master)."""

    def body(local):
        def one(x):
            if compress and x.dtype == jnp.float32:
                y = jnp.sum(x, axis=0).astype(jnp.bfloat16)
                return jax.lax.psum(y, axis).astype(jnp.float32)
            return jax.lax.psum(jnp.sum(x, axis=0), axis)

        return jax.tree_util.tree_map(one, local)

    return shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P())(stacked)


def allreduce_diffs(per_replica_diffs: Sequence[Any], mesh: Mesh,
                    axis: str = "replica", compress: bool = False,
                    phases: Optional[dict] = None):
    """Reduce per-replica diff pytrees to one total via an XLA collective.

    In production each replica contributes its local shard of the stacked
    array; in tests the stack is built host-side and sharded onto the mesh.
    Returns the total diff (as held by replica 0). ``compress=True``
    quantizes f32 leaves to bf16 for the wire (see _psum_stacked; the
    cast happens on-device inside the collective body, same contract as
    the cross-process engine in parallel/collective.py).

    ``phases`` (optional dict) records the same per-phase wall times the
    cross-process plane logs (ship/reduce/readback + payload MB), so the
    in-process and jax.distributed mix paths are accounted identically.
    """
    import time

    n = mesh.shape[axis]
    if len(per_replica_diffs) != n:
        raise ValueError(f"got {len(per_replica_diffs)} diffs for a {n}-replica mesh")
    t0 = time.perf_counter()
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *per_replica_diffs
    )
    sharding = NamedSharding(mesh, P(axis))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), stacked
    )
    # device_put is async: block before timestamping so transfer cost
    # does not leak into the reduce phase
    stacked = jax.block_until_ready(stacked)
    t1 = time.perf_counter()
    total = _psum_stacked(stacked, mesh=mesh, axis=axis, compress=compress)
    total = jax.block_until_ready(total)
    t2 = time.perf_counter()
    out = jax.tree_util.tree_map(
        # replicated mix total, not a sharded leaf
        lambda x: jax.device_get(x), total)  # full-gather-ok — readback
    if phases is not None:
        nbytes = sum(
            x.nbytes // (2 if compress and x.dtype == jnp.float32 else 1)
            for x in jax.tree_util.tree_leaves(total))
        phases.update(
            ship_ms=round((t1 - t0) * 1e3, 2),
            reduce_ms=round((t2 - t1) * 1e3, 2),
            readback_ms=round((time.perf_counter() - t2) * 1e3, 2),
            payload_mb=round(nbytes / 2**20, 2),
        )
    return out


class LocalMixGroup:
    """In-process mix over N mixable-bearing drivers (the stub seam).

    Drivers expose ``get_mixables() -> dict[name, Mixable]`` and optionally
    ``get_schema() / sync_schema(union)`` for row-alignment (classifier
    labels). mix() runs schema sync, then per-mixable diff reduction
    (optionally through a real device mesh), then put_diff everywhere.
    """

    def __init__(self, drivers: Sequence[Any], mesh: Optional[Mesh] = None,
                 compress: bool = False):
        if not drivers:
            raise ValueError("LocalMixGroup needs at least one driver")
        self.drivers = list(drivers)
        self.mesh = mesh
        #: ship f32 diffs over the mesh as bf16 (the --mix-bf16 tradeoff
        #: on the in-process path; cast-on-device, f32 handed back)
        self.compress = compress
        #: per-phase wall times of the last mesh-collective reduce this
        #: group ran (same keys as the cross-process engine)
        self.last_phases: Dict[str, Any] = {}

    def mix(self) -> Dict[str, Any]:
        # hold every participant's model lock for the round (deadlock-free:
        # consistent acquisition order; drivers only ever take their own)
        locks = sorted(
            (d.lock for d in self.drivers if hasattr(d, "lock")), key=id
        )
        try:
            for lk in locks:
                lk.acquire()
            return self._mix_locked()
        finally:
            for lk in reversed(locks):
                lk.release()

    def _mix_locked(self) -> Dict[str, Any]:
        # 1. schema sync (label vocab union etc.)
        schemas = [d.get_schema() for d in self.drivers if hasattr(d, "get_schema")]
        if schemas:
            union: List[str] = sorted(set().union(*map(set, schemas)))
            for d in self.drivers:
                d.sync_schema(union)
        # 2. per-mixable reduce + put
        stats: Dict[str, Any] = {}
        names = list(self.drivers[0].get_mixables().keys())
        for name in names:
            mixables = [d.get_mixables()[name] for d in self.drivers]
            diffs = [m.get_diff() for m in mixables]
            custom_mix = getattr(mixables[0], "mix", None)
            # Routing: the mesh collective handles any diff whose combine is
            # elementwise addition over a fixed-shape array pytree — i.e. no
            # custom mix, or one explicitly marked MIX_IS_SUM (WeightManager).
            # Dict-shaped sparse diffs (bandit, row stores) must fold host-side.
            summable = custom_mix is None or getattr(mixables[0], "MIX_IS_SUM", False)
            if (summable and self.mesh is not None
                    and self.mesh.shape.get("replica") == len(diffs)):
                self.last_phases = {}
                total = allreduce_diffs(diffs, self.mesh,
                                        compress=self.compress,
                                        phases=self.last_phases)
            elif custom_mix is not None:
                total = functools.reduce(custom_mix, diffs)
            else:
                total = tree_sum(diffs)
            for m in mixables:
                m.put_diff(total)
            stats[name] = jax.tree_util.tree_map(
                lambda x: getattr(x, "shape", None), total
            )
        return stats
