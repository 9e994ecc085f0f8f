"""Feature-sharded linear model state — first-class shard_map programs.

ROADMAP item 1 / ISSUE 13 tentpole: the weight matrix of the linear
engines ([L, D] classifier tables, [D] regression vector) sharded over
the FEATURE axis of a device mesh, with train/classify executing where
the shard lives. "Large Scale Distributed Linear Algebra With Tensor
Processing Units" (PAPERS.md) is the shape: distribute the matrix over
the mesh, move compute to the shard, reduce only the tiny per-example
scalars over the interconnect.

Execution model (one shard_map'd jitted program per op):

- A train flush is routed on the host (``route_rows``): shard ``n`` is
  uploaded only the entries whose column lies in its OWNED range
  ``[n * D/S, (n+1) * D/S)``, row by row, as local columns, at the
  width of the fullest row any shard holds (24 where a 39-feature flush
  is 40 wide over four shards). A chip issues no descriptor for an entry
  it does not own.
- The query and regression programs still receive the full CSR batch
  (idx/val [B, K], replicated) and mask it to the owned range
  (``_owned``): unowned entries contribute exact zeros.
- Partial scores from the local [L, D/S] slice are reduced with a
  single ``psum`` over the shard axis — the ONLY cross-shard traffic
  per step is [B, L] logits (+ [B] norms), never weight state.
- Updates scatter into the local ``dw`` slice only. The weight matrix
  is never gathered: per-device footprint stays (full size / n_shards)
  + O(batch).

The update rule and the scores are the single-chip path's own bodies
(ops/classifier.train_rows, score_rows) with their cross-shard sums
psum'd, so sharded and unsharded results are identical to f32 rounding;
parallel/spmd.py runs the same body under a data-parallel replica axis
for the pod path.

Mix integration: ``shard_chunks`` / ``assemble_chunks`` convert a
feature-sharded leaf to/from per-shard host chunks keyed by start
column (``c0``, ``c8388608``, ...), so each shard's diff enters the
chunked/tiered/quantized mix pipeline independently and no step of a
mix round materializes the full matrix in one buffer.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jubatus_tpu.core.sparse import _width_bucket
from jubatus_tpu.ops.classifier import (
    ClassifierState,
    score_rows,
    train_rows,
)

DEFAULT_AXIS = "shard"


def feature_shard_mesh(n_shards: int, err_cls=ValueError,
                       axis: str = DEFAULT_AXIS) -> Mesh:
    """A 1-D feature-shard mesh over the first ``n_shards`` LOCAL
    devices (host-major — device_put on non-addressable devices fails
    on a multi-host runtime). The ``--shard-features`` mesh builder."""
    from jubatus_tpu.parallel.mesh import host_major

    devs = host_major(jax.local_devices())[:n_shards]
    if len(devs) < n_shards:
        raise err_cls(
            f"feature sharding needs {n_shards} local devices, "
            f"have {len(devs)}")
    return Mesh(np.asarray(devs), axis_names=(axis,))


def mesh_for_features(dim: int, d_per_shard: int,
                      err_cls=ValueError) -> Optional[Mesh]:
    """The ``--shard-features D_PER_SHARD`` resolver: shard count =
    dim / d_per_shard (must divide; one shard or fewer means no mesh).
    The per-device feature budget is the HBM-capacity knob — pick the
    widest slice one device holds and the layout follows."""
    if d_per_shard <= 0:
        raise err_cls(f"--shard-features must be > 0, got {d_per_shard}")
    if dim % d_per_shard:
        raise err_cls(
            f"--shard-features {d_per_shard} does not divide the feature "
            f"dim {dim} (pick a power-of-two slice of 2^dim_bits)")
    n = dim // d_per_shard
    if n <= 1:
        return None
    return feature_shard_mesh(n, err_cls)


def state_spec(leaf, dim: int, axis: str = DEFAULT_AXIS) -> P:
    """PartitionSpec for one state leaf: trailing (feature) dim sharded
    when it spans the model dim; (1, 1) placeholders and scalars stay
    replicated."""
    shape = getattr(leaf, "shape", ())
    if len(shape) >= 1 and shape[-1] == dim:
        return P(*([None] * (len(shape) - 1)), axis)
    return P()


def place_state(mesh: Mesh, state, dim: int, axis: str = DEFAULT_AXIS):
    """Pin every feature-spanning leaf of a state pytree to the sharded
    layout (NamedSharding over ``axis``); other leaves replicate."""
    def put(a):
        return jax.device_put(
            a, NamedSharding(mesh, state_spec(a, dim, axis)))

    return jax.tree_util.tree_map(put, state)


def _owned(idx, val, d_local, axis):
    """Column-range partition of one CSR batch: local indices + values
    for the entries this shard owns, zeros elsewhere."""
    lo = jax.lax.axis_index(axis) * d_local
    li_raw = idx - lo
    owned = (li_raw >= 0) & (li_raw < d_local)
    return jnp.where(owned, li_raw, 0), jnp.where(owned, val, 0.0)


def route_rows(idx: np.ndarray, val: np.ndarray, n_shards: int,
               d_local: int, min_width: int = 0):
    """Column-range routing of one padded flush, on the host: from
    ``idx``/``val`` [B, K] (columns as the hasher gives them, in [0, D);
    column 0 is padding and no feature hashes there) to
    (``ridx``, ``rval``, ``owned``). Plane ``n`` of ``ridx``/``rval``
    [N, Ks, B] holds, row by row, the entries whose column lies in
    ``[n * d_local, (n + 1) * d_local)``, as LOCAL columns, in the order
    the row has them, padded with column 0 / value 0 as rows are padded.
    ``Ks`` is the ladder rung (core/sparse.py ``_width_bucket``) of the
    fullest row of the fullest shard, and never under ``min_width``: one
    width for all shards, since one program runs on all of them.
    ``owned`` [N] counts the entries that carry a feature by the shard
    that owns them. Raises ValueError for a column outside [0, D).

    The row index is the planes' minor-most axis because that is how the
    device lays out a [B, Ks] array of so few columns: a [Ks, B] plane is
    uploaded as it lies, where a [B, Ks] one is transposed on the host
    first, and the chips waited for that (PERF.md section 6, PR 32).
    ``train_batch`` reads a plane through its transpose, which on the
    device is the same bytes.

    Two passes over the rows, the count and the fill, with the width
    settled here between them: native/fast_ingest.cpp's, each one call
    that holds no interpreter lock (stage 11.6 ms a flush of 8,192 x 40
    over four shards, the chips never idle), or without the library
    numpy's, which serve but do not keep four chips fed (stage 22.9 ms,
    idle 13.3%, even on sorted rows' spans with no sort: PERF.md section
    6, PR 32)."""
    from jubatus_tpu.native import ingest

    idx = np.ascontiguousarray(idx, dtype=np.int32)
    val = np.ascontiguousarray(val, dtype=np.float32)
    if idx.ndim != 2 or val.shape != idx.shape:
        raise ValueError(f"idx {idx.shape} and val {val.shape} are not "
                         "one [B, K] pair")
    native = ingest.available()
    lens = (ingest.route_count if native else _route_count)(
        idx, n_shards, d_local)
    ks = max(_width_bucket(int(lens.max()) if lens.size else 1), min_width)
    if native:
        ridx, rval = ingest.route_fill(idx, val, n_shards, d_local, ks)
    else:
        ridx, rval = _route_fill(idx, val, lens, d_local, ks)
    return ridx, rval, lens.sum(axis=1)


def flush_sharding(mesh: Mesh, axis: str = DEFAULT_AXIS) -> NamedSharding:
    """Where ``route_rows``' planes go: plane n on the mesh's n-th device
    (the leading axis split, the rest whole)."""
    return NamedSharding(mesh, P(axis))


def _route_count(idx, n_shards, d_local):
    """``route_rows``' first pass without the native library: lens
    [N, B], the entries of row i that shard n owns, from one count a
    shard boundary of the entries at or past it."""
    if idx.size and not 0 <= idx.min() <= idx.max() < n_shards * d_local:
        raise ValueError(
            f"a column outside [0, {n_shards * d_local}) in a train flush")
    past = np.zeros((n_shards + 1, idx.shape[0]), dtype=np.int32)
    past[0] = np.count_nonzero(idx, axis=1)
    for n in range(1, n_shards):
        past[n] = np.count_nonzero(idx >= n * d_local, axis=1)
    return past[:-1] - past[1:]


def _route_fill(idx, val, lens, d_local, ks):
    """``route_rows``' second pass without the native library: a stable
    sort groups each row's entries by owner (padding last) and keeps
    their order within a shard, so a shard's entries are one span of the
    row, whose start and length ``lens`` gives: one gather fills the
    planes."""
    n_shards, (b, k) = len(lens), idx.shape
    owner = np.where(idx == 0, n_shards, idx // d_local)
    order = np.argsort(owner, axis=1, kind="stable")
    idx = np.take_along_axis(idx, order, axis=1)
    val = np.take_along_axis(val, order, axis=1)
    starts = np.cumsum(lens, axis=0, dtype=np.int32) - lens
    lane = np.arange(ks, dtype=np.int32)[:, None]
    at = (starts + np.arange(b, dtype=np.int32) * k)[:, None, :] + lane
    live = lane < lens[:, None, :]
    lo = (np.arange(n_shards, dtype=np.int32) * d_local)[:, None, None]
    # a dead lane reads past its span (clipped at the array's end) and
    # is zeroed: the values selected, not multiplied, so that no row's
    # NaN leaks into its neighbour
    ridx = (np.take(idx.reshape(-1), at, mode="clip") - lo) * live
    rval = np.where(live, np.take(val.reshape(-1), at, mode="clip"),
                    np.float32(0.0))
    return ridx, rval


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "method"), donate_argnums=(1,))
def train_batch(mesh: Mesh, state: ClassifierState, idx: jax.Array,
                val: jax.Array, labels: jax.Array, label_mask: jax.Array,
                param: float, *, method: str,
                axis: str = DEFAULT_AXIS) -> ClassifierState:
    """Feature-sharded vectorized microbatch update: ops.train_rows on
    each shard's slice (parallel/spmd.py runs the same body under an
    extra replica axis). ``idx``/``val`` are ``route_rows``' [N, Ks, B]
    planes, split over ``axis`` like the state's leaves: a shard is
    handed its own entries as local columns and nothing else. Labels and
    the mask are replicated. One psum of [B, L] partial scores (+ [B]
    norms) per step — weight state never crosses shards."""
    dim = state.w.shape[-1]

    def body(w, dw, prec, dprec, idx, val, labels, label_mask):
        # the shard's own [Ks, B] plane, read as [B, Ks] rows
        return train_rows(
            w, dw, prec, dprec, idx[0].T, val[0].T, labels, label_mask,
            param, method=method, reduce=lambda x: jax.lax.psum(x, axis))

    specs = tuple(state_spec(a, dim, axis) for a in state)
    out = shard_map(
        body, mesh=mesh,
        in_specs=specs + (P(axis), P(axis), P(), P()),
        out_specs=specs,
        check_vma=False,
    )(state.w, state.dw, state.prec, state.dprec,
      idx, val, labels, label_mask)
    return ClassifierState(*out)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def scores(mesh: Mesh, state: ClassifierState, idx: jax.Array,
           val: jax.Array, label_mask: jax.Array,
           axis: str = DEFAULT_AXIS) -> jax.Array:
    """Feature-sharded batch classify: each shard scores its column
    range, one psum assembles the [B, L] logits (replicated out). Same
    -inf dead-label convention as ops.scores."""
    dim = state.w.shape[-1]

    def body(w, dw, idx, val, label_mask):
        li, lv = _owned(idx, val, w.shape[1], axis)
        return score_rows(w, dw, li, lv, label_mask,
                          reduce=lambda x: jax.lax.psum(x, axis))

    spec = state_spec(state.w, dim, axis)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(state.w, state.dw, idx, val, label_mask)


# -- regression (single weight row) ------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "method"), donate_argnums=(1,))
def regression_train_batch(mesh: Mesh, state, idx: jax.Array,
                           val: jax.Array, targets: jax.Array,
                           sensitivity: float, c: float, *, method: str,
                           axis: str = DEFAULT_AXIS):
    """Feature-sharded PA regression train: the per-example sequential
    scan of ops/regression.train_batch with the prediction reduced over
    the shard axis each step (exact reference semantics preserved —
    the scan stays sequential; only the dot products are sharded)."""
    from jubatus_tpu.ops.regression import RegressionState

    dim = state.w.shape[-1]

    def body(w, dw, idx, val, targets):
        d_local = w.shape[0]

        def step(carry, ex):
            w, dw = carry
            e_idx, e_val, y = ex
            lo = jax.lax.axis_index(axis) * d_local
            li_raw = e_idx - lo
            owned = (li_raw >= 0) & (li_raw < d_local)
            li = jnp.where(owned, li_raw, 0)
            lv = jnp.where(owned, e_val, 0.0)
            pred = jax.lax.psum(
                jnp.sum((jnp.take(w, li) + jnp.take(dw, li)) * lv), axis)
            err = y - pred
            loss = jnp.abs(err) - sensitivity
            x2 = jnp.maximum(
                jax.lax.psum(jnp.sum(lv * lv), axis), 1e-12)
            if method == "PA":
                alpha = loss / x2
            elif method == "PA1":
                alpha = jnp.minimum(c, loss / x2)
            elif method == "PA2":
                alpha = loss / (x2 + 1.0 / (2.0 * c))
            else:
                raise ValueError(f"unknown regression method {method!r}")
            alpha = jnp.where(loss > 0.0, alpha, 0.0)
            dw = dw.at[li].add(jnp.sign(err) * alpha * lv)
            return (w, dw), ()

        (w, dw), _ = jax.lax.scan(step, (w, dw), (idx, val, targets))
        return w, dw

    spec = P(axis)
    out = shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, P(), P(), P()),
        out_specs=(spec, spec),
        check_vma=False,
    )(state.w, state.dw, idx, val, targets)
    return RegressionState(*out)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def regression_estimate(mesh: Mesh, state, idx: jax.Array, val: jax.Array,
                        axis: str = DEFAULT_AXIS) -> jax.Array:
    """Feature-sharded batch estimates: [B], one psum of the per-shard
    partial dot products."""
    def body(w, dw, idx, val):
        li, lv = _owned(idx, val, w.shape[0], axis)
        eff = jnp.take(w, li) + jnp.take(dw, li)
        return jax.lax.psum(jnp.einsum("bk,bk->b", eff, lv), axis)

    spec = P(axis)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, P(), P()),
        out_specs=P(),
        check_vma=False,
    )(state.w, state.dw, idx, val)


# -- per-shard diff chunking (mix-plane integration) -------------------------

def shard_chunks(arr, rows: Optional[int] = None) -> Dict[str, np.ndarray]:
    """A feature-sharded array as per-shard HOST chunks keyed by start
    column (``c0``, ``c<D/S>``, ...). Each shard's slice copies
    device→host independently — the full matrix is never materialized
    in one buffer, and each chunk enters the mix pipeline (tiered +
    quantized, PR 1/6/9) on its own. ``rows`` trims to the active label
    rows (the wire cut the classifier mixable already makes)."""
    out: Dict[str, np.ndarray] = {}
    for sh in arr.addressable_shards:
        sl = sh.index[-1]
        start = sl.start or 0
        chunk = np.asarray(sh.data)
        if rows is not None and chunk.ndim == 2 and rows < chunk.shape[0]:
            chunk = chunk[:rows]
        out[f"c{start}"] = chunk
    return out


def is_chunked(leaf) -> bool:
    """Does this diff leaf carry the per-shard chunk wire shape?"""
    return isinstance(leaf, dict) and leaf and \
        all(isinstance(k, (str, bytes))
            and (k.decode() if isinstance(k, bytes) else k).startswith("c")
            for k in leaf)


def assemble_chunks(chunks: Dict[str, np.ndarray], sharding) -> jax.Array:
    """Per-shard wire chunks back to one feature-sharded device array
    (the receive half of ``shard_chunks``): each chunk is placed
    directly on its owning shard's device — no host concatenation of
    the full matrix, no device gather. Raises ValueError on a layout
    mismatch (a peer sharded differently — the mix must not fold
    misaligned columns)."""
    items = sorted(
        ((int((k.decode() if isinstance(k, bytes) else k)[1:]), np.asarray(v))
         for k, v in chunks.items()),
        key=lambda kv: kv[0])
    widths = [v.shape[-1] for _, v in items]
    total = sum(widths)
    mesh = sharding.mesh
    devices = list(mesh.devices.flat)
    if len(items) != len(devices):
        raise ValueError(
            f"shard layout mismatch: {len(items)} wire chunks for a "
            f"{len(devices)}-shard mesh (peers must share one "
            "--shard-devices/--shard-features layout)")
    expect = 0
    for (start, v), dev in zip(items, devices):
        if start != expect:
            raise ValueError(
                f"shard layout mismatch: chunk starts at column {start}, "
                f"expected {expect}")
        expect += v.shape[-1]
    shape = items[0][1].shape[:-1] + (total,)
    return jax.make_array_from_single_device_arrays(
        shape, sharding,
        [jax.device_put(v, dev) for (_, v), dev in zip(items, devices)])


def chunk_sharding(mesh: Mesh, rank: int = 2,
                   axis: str = DEFAULT_AXIS) -> NamedSharding:
    """The trailing-dim feature sharding ``assemble_chunks`` re-places
    into (rank 2 for [L, D] tables, 1 for [D] vectors)."""
    return NamedSharding(mesh, P(*([None] * (rank - 1)), axis))
