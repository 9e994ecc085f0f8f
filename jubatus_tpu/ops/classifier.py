"""Multiclass linear classifier kernels: perceptron / PA / PA1 / PA2 / CW /
AROW / NHERD.

Rebuild of jubatus_core's classifier algorithms (method names from
/root/reference/config/classifier/*.json; consumed via
classifier_factory::create_classifier, reference
jubatus/server/server/classifier_serv.cpp:108-109) as jitted XLA programs.

Design (TPU-first, not a port):

- Weights are dense [L, D] arrays over the hashed feature space (D = 2^k),
  split as ``w`` (master, state as of last mix) + ``dw`` (local diff).
  Effective weights are w + dw; training scatters into dw only.
- Confidence-weighted methods (CW/AROW/NHERD) keep the diagonal covariance as
  *precision* (1/sigma), also split master+diff, because every update rule's
  precision increment is additive (e.g. AROW: Sigma^-1 += x x^T / r). Additive
  diffs make the distributed mix an exact psum over ICI — the reference's
  sequential get_diff/put_diff fold (linear_mixer.cpp:437-509) becomes one
  XLA collective with identical semantics regardless of node count or order.
- A training microbatch is processed with lax.scan over examples, preserving
  the reference's per-example online semantics (classifier_serv.cpp:137-143)
  while amortizing dispatch; gathers/scatters are XLA dynamic-slice ops on
  TPU. Padding entries (idx 0, val 0) are no-ops by construction.
- The parallel step and the scores address the resident tables where they
  lie (_gather_sums, _scatter_add): a step's device time follows its rows,
  B x K, not the tables, L x D, wherever the tables are large beside the
  batch.

Update rules (margin m = s_correct - s_best_wrong, loss l = max(0, 1-m),
x2 = ||x||^2, v = x'(Sigma_c + Sigma_w)x, parameter r/C/phi =
"regularization_weight"):

  perceptron: on mistake (m <= 0): w_c += x, w_w -= x
  PA:   alpha = l / (2 x2)
  PA1:  alpha = min(C, l / (2 x2))
  PA2:  alpha = l / (2 x2 + 1/(2C))
  AROW: beta = 1/(v + r); alpha = l * beta; w += alpha Sigma x;
        precision += x^2 / r
  NHERD: alpha = l / (v + r); w += alpha Sigma x;
        precision += x^2 (v + 2r) / r^2
  CW:   alpha from the Dredze/Crammer closed form with phi;
        precision += 2 alpha phi x^2
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

METHODS = ("perceptron", "PA", "PA1", "PA2", "CW", "AROW", "NHERD")
CONFIDENCE_METHODS = ("CW", "AROW", "NHERD")

_NEG = -1e30


class ClassifierState(NamedTuple):
    """Pytree of classifier model arrays.

    w, dw:       [L, D] float32 — master weights / local diff since last mix
    prec, dprec: [L, D] float32 — diagonal precision (1/sigma) master / diff.
                 For non-confidence methods these stay at their init and are
                 ignored (kept so the state pytree shape is method-independent
                 only for confidence methods; PA-family states carry (1,1)
                 placeholders to avoid wasting HBM).
    """

    w: jax.Array
    dw: jax.Array
    prec: jax.Array
    dprec: jax.Array


def init_state(num_labels: int, dim: int, confidence: bool,
               sharding=None) -> ClassifierState:
    """A fresh state. ``sharding``: where the [L, D] leaves are born (a
    mesh's feature sharding, models/classifier.py): each device gets its
    own columns and no leaf ever lies whole on one of them; None is the
    default device. The (1, 1) placeholders stay where they were."""
    shape = (num_labels, dim)
    cshape = shape if confidence else (1, 1)
    csharding = sharding if confidence else None
    return ClassifierState(
        w=jnp.zeros(shape, jnp.float32, device=sharding),
        dw=jnp.zeros(shape, jnp.float32, device=sharding),
        prec=jnp.ones(cshape, jnp.float32, device=csharding),
        dprec=jnp.zeros(cshape, jnp.float32, device=csharding),
    )


@functools.partial(jax.jit, static_argnames=("pad", "fill", "sharding"))
def _pad_rows(a, *, pad: int, fill: float, sharding):
    out = jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
    if sharding is None:
        return out
    return jax.lax.with_sharding_constraint(out, sharding)


def grow_labels(state: ClassifierState, new_num_labels: int,
                sharding=None) -> ClassifierState:
    """Label-capacity growth (recompile on next call). The new rows are
    made where the old ones lie: with ``sharding`` (init_state's) each
    device pads its own columns."""
    L = state.w.shape[0]
    if new_num_labels <= L:
        return state
    pad = new_num_labels - L

    def _pad(a, fill):
        if a.shape == (1, 1):
            return a
        return _pad_rows(a, pad=pad, fill=fill, sharding=sharding)

    return ClassifierState(
        w=_pad(state.w, 0.0),
        dw=_pad(state.dw, 0.0),
        prec=_pad(state.prec, 1.0),
        dprec=_pad(state.dprec, 0.0),
    )


# Table elements per gathered index above which the step gathers columns
# where they lie instead of packing the tables first. Measured on v5e at
# L = 8, K = 64, D = 2^25 (PERF.md section 6, PR 25): the packed copy is one
# pass over every table (9.3 ms, 0.035 ns an element) and saves three of
# four gathers (20 ns a descriptor each); a step on the packed plan took
# 33.0 ms against 38.6 at B = 4096 and 24.3 against 22.8 at B = 2048, which
# are 1024 and 2048 elements an index.
_PACK_UNDER = 1024


def _label_group(num_labels: int, dim: int) -> int:
    """Label rows that one descriptor fetches: a sublane group of 8 where
    the table lies in whole tiles of 8 rows x 128 columns, else all."""
    return 8 if num_labels % 8 == 0 and dim % 128 == 0 else num_labels


def gather_plan(num_labels: int, dim: int, n_idx: int) -> str:
    """The plan _gather_sums takes for [num_labels, dim] tables and n_idx
    gathered indices: "columns" or "packed". Static shapes only, so it is
    settled when the program is traced. Tables and descriptors both grow
    with the label groups, so the rule is one group's: what it was at 8
    labels, at any capacity."""
    return "columns" if _label_group(num_labels, dim) * dim \
        > _PACK_UNDER * n_idx else "packed"


def _by_group(table):
    """An [L, D] table of G groups of 8 label rows as [8, G * D]: group g's
    column c is column g * D + c. The tiles of a group's 8 rows lie one
    behind another, so the view is the table's own bytes (the reshapes
    are bitcasts on the TPU, as _scatter_add_tiled's are)."""
    num_labels, dim = table.shape
    g = _label_group(num_labels, dim)
    return table.reshape(num_labels // g, g, dim).transpose(1, 0, 2).reshape(
        g, -1)


def _gather_sums(pairs, idx):
    """For each (master, diff) pair of [L, D] tables: (master + diff)[:, idx]
    as [L, B, K]; idx is [B, K]. Both plans add the same two floats, so they
    agree to the bit.

    A descriptor fetches the label rows of one sublane group, 8 of them.
    Past 8 labels the tables are addressed a group at a time through
    _by_group's view, one descriptor a group and index, so the work grows
    with the groups and nothing table-sized is made that 8 labels do not
    make. Left to itself XLA first copies every [32, D] table into a
    column-major layout, 128 lanes for 32 labels: four times each table,
    20 GB at D = 2^23 (v5e's compiler, PR 34).
    """
    num_labels, dim = pairs[0][0].shape
    g = _label_group(num_labels, dim)
    if g == num_labels:
        return _gather_group(pairs, idx)
    at = idx + (jnp.arange(num_labels // g, dtype=idx.dtype)
                * dim)[:, None, None]                          # [G, B, K]
    got = _gather_group([(_by_group(m), _by_group(d)) for m, d in pairs], at)
    return [jnp.moveaxis(a, 0, 1).reshape((num_labels,) + idx.shape)
            for a in got]                  # [8, G, B, K] -> [G * 8, B, K]


def _gather_group(pairs, idx):
    """_gather_sums for tables of one label group: [g, D] tables, idx of
    any shape, the pairs' sums as [g, *idx.shape].

    columns: gather the columns out of each table as it lies; the g label
      rows of a column are the sublanes of one tile, so one descriptor
      fetches them all, and nothing table-sized is made.
    packed: stack the masters, stack the diffs, add the stacks (one pass:
      summing each pair first was three), and fetch everything a feature
      needs out of that [n*g, D] table with a single descriptor: gather
      cost is per DESCRIPTOR, not per element (docs/PERF_NOTES.md). On the
      TPU the [D, n*g] transpose is that table's own bytes.
    """
    num_labels, dim = pairs[0][0].shape
    flat = idx.reshape(-1)
    if gather_plan(num_labels, dim, flat.size) == "columns":
        with jax.named_scope("gather"):
            return [(jnp.take(m, flat, axis=1) + jnp.take(d, flat, axis=1))
                    .reshape((num_labels,) + idx.shape) for m, d in pairs]
    with jax.named_scope("pack"):
        packed = (jnp.concatenate([m for m, _ in pairs], axis=0)
                  + jnp.concatenate([d for _, d in pairs], axis=0)).T
    with jax.named_scope("gather"):
        g = jnp.take(packed, flat, axis=0).reshape(idx.shape + (-1,))
        return [jnp.moveaxis(g[..., i * num_labels:(i + 1) * num_labels], -1, 0)
                for i in range(len(pairs))]


def _scatter_add_tiled(table, rows, idx, up):
    """_scatter_add on the TPU. An f32 [L, D] array lies there in tiles of
    8 rows x 128 columns, and XLA lowers ``table.at[rows[:, None], idx]``
    as a scatter on the ROW-MAJOR flat array: it copied each 1 GiB table
    into that order and back around the scatters, 65-70 ms of a 140 ms step
    at D = 2^25 (PERF.md section 6, PR 25). Addressed in the order the
    tiles lie in, the flat view is the table's own bytes (two bitcasts) and
    the scatter runs in place."""
    num_labels, dim = table.shape
    g = 8 if num_labels % 8 == 0 else num_labels
    c = 128 if dim % 128 == 0 else dim
    r = rows[:, None]
    pos = (((r // g) * (dim // c) + idx // c) * g + r % g) * c + idx % c
    flat = table.reshape(num_labels // g, g, dim // c, c)
    flat = flat.transpose(0, 2, 1, 3).reshape(-1).at[pos].add(up)
    return flat.reshape(num_labels // g, dim // c, g, c).transpose(
        0, 2, 1, 3).reshape(num_labels, dim)


def _scatter_add(table, rows, idx, up):
    """table[rows[n], idx[n, k]] += up[n, k], duplicates summed; table is
    [L, D], rows [N], idx and up [N, K]. The platform is settled when the
    program is lowered, so each gets the scatter that runs in place there."""
    return jax.lax.platform_dependent(
        table, rows, idx, up, tpu=_scatter_add_tiled,
        default=lambda t, r, i, u: t.at[r[:, None], i].add(u))


def decide_updates(s, labels, label_mask, x2, v, x2_vec, param, *, method):
    """The per-batch update decision of train_rows, on one chip and on
    the mesh alike.

    Inputs are already globally reduced where sharded: s [B, L] raw scores,
    x2/v [B] (= ||x||^2 and x'(Sig_c+Sig_w)x), x2_vec [B, K] *local* squared
    feature values (may be a shard's slice — dp is per-feature and local).
    Returns (wrong [B], alpha [B], alpha_w [B], dp [B, K] or None): alpha
    scales the correct row's update, alpha_w the rival row's. When no rival
    label exists (single-label model) the rival score is taken as 0 — the
    reference still learns from the first label's examples — and alpha_w is
    zeroed so nothing lands on the dead slot `wrong` points at.
    """
    B = s.shape[0]
    rows = jnp.arange(B)
    s = jnp.where(label_mask[None, :], s, _NEG)
    s_correct = s[rows, labels]
    s_masked = s.at[rows, labels].set(_NEG)
    s_wrong = jnp.max(s_masked, axis=1)
    wrong = jnp.argmax(s_masked, axis=1)
    no_rival = s_wrong <= _NEG / 2
    margin = s_correct - jnp.where(no_rival, 0.0, s_wrong)
    loss = jnp.maximum(0.0, 1.0 - margin)
    live = x2 > 0.0
    alpha, dp = _alpha_and_prec(method, param, margin, loss, x2, v, x2_vec)
    alpha = jnp.where(live, alpha, 0.0)
    alpha_w = jnp.where(no_rival, 0.0, alpha)
    if dp is not None:
        dp = jnp.where((live & (alpha > 0.0))[:, None], dp, 0.0)
    return wrong, alpha, alpha_w, dp


def score_rows(w, dw, idx, val, label_mask, reduce=lambda x: x):
    """The [B, L] margins of a hashed sparse batch against w + dw, dead
    labels at -inf. ``reduce`` sums the partial scores over the shards of a
    mesh (parallel/sharded_model.py); on one chip it is the identity.

    The gather takes _gather_sums' plan: columns where the table is large
    beside the batch, the packed [D, L] copy otherwise.
    """
    # the scope is the program's own word beside XLA's op names in a
    # device capture: metadata only
    with jax.named_scope("scores"):
        (g,) = _gather_sums([(w, dw)], idx)                   # [L, B, K]
        s = reduce(jnp.einsum("lbk,bk->bl", g, val))
        return jnp.where(label_mask[None, :], s, _NEG)


@functools.partial(jax.jit, donate_argnums=())
def scores(state: ClassifierState, idx: jax.Array, val: jax.Array,
           label_mask: jax.Array) -> jax.Array:
    """Batch classify scores.

    idx/val: [B, K] hashed sparse batch; label_mask: [L] bool (live labels).
    Returns [B, L] margins with dead labels at -inf.
    """
    return score_rows(state.w, state.dw, idx, val, label_mask)


def _alpha_and_prec(method: str, param: float, margin, loss, x2, v, x2_vec):
    """Per-method update magnitude and precision increment (per-feature vec).

    Shape-polymorphic: margin/loss/x2/v are scalars (sequential path) or [B]
    (parallel path); x2_vec has one extra trailing [K] axis. Returns
    (alpha, dprec_vec) where the weight update is w_c += alpha * sigma_c * x,
    w_w -= alpha * sigma_w * x (sigma == 1 for PA-family) and dprec_vec is
    added to both rows' precision diff.
    """

    def vec(a):
        """Broadcast a per-example quantity against the per-feature axis."""
        a = jnp.asarray(a)
        return a[..., None] if jnp.ndim(x2_vec) > jnp.ndim(a) else a

    x2s = jnp.maximum(x2, 1e-12)
    if method == "perceptron":
        alpha = jnp.where(margin <= 0.0, 1.0, 0.0)
        return alpha, None
    if method == "PA":
        alpha = jnp.where(loss > 0.0, loss / (2.0 * x2s), 0.0)
        return alpha, None
    if method == "PA1":
        alpha = jnp.where(loss > 0.0, jnp.minimum(param, loss / (2.0 * x2s)), 0.0)
        return alpha, None
    if method == "PA2":
        alpha = jnp.where(loss > 0.0, loss / (2.0 * x2s + 1.0 / (2.0 * param)), 0.0)
        return alpha, None
    if method == "AROW":
        r = param
        beta = 1.0 / (v + r)
        alpha = jnp.where(loss > 0.0, loss * beta, 0.0)
        dp = jnp.where(vec(loss) > 0.0, x2_vec / r, 0.0)
        return alpha, dp
    if method == "NHERD":
        r = param
        alpha = jnp.where(loss > 0.0, loss / (v + r), 0.0)
        dp = jnp.where(vec(loss) > 0.0, x2_vec * vec(v + 2.0 * r) / (r * r), 0.0)
        return alpha, dp
    if method == "CW":
        phi = param
        m = margin
        a = 1.0 + 2.0 * phi * m
        vs = jnp.maximum(v, 1e-12)
        disc = jnp.maximum(a * a - 8.0 * phi * (m - phi * vs), 0.0)
        alpha = jnp.maximum(0.0, (-a + jnp.sqrt(disc)) / (4.0 * phi * vs))
        dp = 2.0 * vec(alpha) * phi * x2_vec
        return alpha, dp
    raise ValueError(f"unknown classifier method {method!r}")


def train_rows(w, dw, prec, dprec, idx, val, labels, label_mask, param, *,
               method: str, reduce=lambda x: x):
    """The vectorized microbatch update: the one body of the update rule,
    for one chip (train_batch_parallel) and for the mesh
    (parallel/sharded_model.py, parallel/spmd.py). Returns the four tables.

    Every example computes its margin/alpha against the batch-start snapshot
    and all updates land in one scatter-add (bounded staleness *within* a
    microbatch; batches remain sequential). This is the batching compromise
    SURVEY.md §7 hard-part (b) calls for: the per-example lax.scan path
    (train_batch_sequential) is ~40 ms/1024 examples on a v5e chip because a
    sequential scan of tiny gathers/scatters is latency-bound, while this
    path is one gather + one einsum + one scatter over the whole batch.

    On a mesh the tables are a shard's [L, D/S] slices and ``idx``/``val``
    the shard's own entries of each row as local columns (a train flush
    is routed on the host, parallel/sharded_model.py route_rows; spmd.py
    masks instead: ``val`` zero where the shard does not own the entry),
    and ``reduce`` sums over the shards the three quantities that cross
    them: the [B, L] scores, x2 and v. A padding entry's updates are
    alpha * sigma * 0 = 0, added at local column 0. Where the rows are
    slabs of a document's entries (train_batch_parallel's ``owner``)
    ``reduce`` sums the same three over a document's slabs.
    """
    confidence = method in CONFIDENCE_METHODS

    # The scopes (pack, gather, margin, scatter) are the phases' own
    # words beside XLA's op names in a device capture: metadata only.
    if confidence:                                                 # [L, B, K]
        eff_g, p_g = _gather_sums([(w, dw), (prec, dprec)], idx)
    else:
        (eff_g,) = _gather_sums([(w, dw)], idx)
    with jax.named_scope("margin"):
        s = reduce(jnp.einsum("lbk,bk->bl", eff_g, val))
        x2_vec = val * val                                         # [B, K]
        x2 = reduce(jnp.sum(x2_vec, axis=1))                       # [B]

        if confidence:
            p_c = jnp.take_along_axis(p_g, labels[None, :, None], axis=0)[0]  # [B,K]
            sig_c = 1.0 / p_c
        else:
            sig_c = jnp.ones_like(val)

        # v needs sigma of the *wrong* row, which needs the scores first; compute
        # the margin decision with a provisional v=0 only for non-confidence
        # methods (their alpha ignores v).
        if confidence:
            # first pass for `wrong` (alpha ignored), then exact v
            wrong0, _, _, _ = decide_updates(
                s, labels, label_mask, x2, jnp.zeros_like(x2), x2_vec, param,
                method=method,
            )
            p_w = jnp.take_along_axis(p_g, wrong0[None, :, None], axis=0)[0]
            # no rival label → `wrong0` points at a dead/arbitrary row; the
            # nonexistent rival carries the unit precision prior, not that
            # row's (possibly trained) precision
            no_rival = jnp.sum(label_mask) < 2
            sig_w = jnp.where(no_rival, 1.0, 1.0 / p_w)
            v = reduce(jnp.sum((sig_c + sig_w) * x2_vec, axis=1))  # [B]
        else:
            sig_w = jnp.ones_like(val)
            v = jnp.zeros_like(x2)

        wrong, alpha, alpha_w, dp = decide_updates(
            s, labels, label_mask, x2, v, x2_vec, param, method=method
        )

    with jax.named_scope("scatter"):
        # the correct row's and the rival's updates go into a table as ONE
        # scatter of [2B, K]: 11.7 ms for 2 x 524,288 updates against 2 x
        # 7.5 ms at D = 2^25, and a flush of 2,048 rows reaches the size
        # from which XLA sorts the updates first (24 against 95 ns each;
        # PERF.md section 6, PR 25)
        up_c = alpha[:, None] * sig_c * val                        # [B, K]
        up_w = alpha_w[:, None] * sig_w * val
        rows = jnp.concatenate([labels, wrong])                    # [2B]
        idx2 = jnp.concatenate([idx, idx])                         # [2B, K]
        dw = _scatter_add(dw, rows, idx2, jnp.concatenate([up_c, -up_w]))
        if confidence:
            dp_w = jnp.where((alpha_w > 0.0)[:, None], dp, 0.0)
            dprec = _scatter_add(dprec, rows, idx2,
                                 jnp.concatenate([dp, dp_w]))
    return w, dw, prec, dprec


def _over_documents(owner):
    """train_rows' ``reduce`` for a flush cut into slabs: the sum over a
    document's slabs, handed back to each of them. ``owner`` [S] numbers
    the documents in the order their slabs come (ascending, below S).
    With None every row is a document and nothing is summed."""
    if owner is None:
        return lambda x: x

    def reduce(x):
        return jax.ops.segment_sum(
            x, owner, num_segments=owner.shape[0],
            indices_are_sorted=True)[owner]
    return reduce


@functools.partial(jax.jit, static_argnames=("method",), donate_argnums=(0,))
def train_batch_parallel(
    state: ClassifierState,
    idx: jax.Array,        # [B, K] int32
    val: jax.Array,        # [B, K] float32
    labels: jax.Array,     # [B] int32 — correct label row per example
    label_mask: jax.Array, # [L] bool — live labels
    param: float,
    owner=None,            # [B] int32, or None: every row a document
    *,
    method: str,
) -> ClassifierState:
    """train_rows on one chip — the TPU hot path.

    ``owner``: the rows are slabs, pieces of a document's entries
    (models/classifier.py _train_slots cuts a flush of uneven rows so),
    ``owner[s]`` the document slab s belongs to and ``labels[s]`` that
    document's label. Scores, x2 and v are then summed over a document's
    slabs, the hook through which the mesh sums over shards, so every
    slab of a document decides the document's alpha and rival, and each
    entry's update is the one the row form makes. A slab with no entry
    is a no-op as a padding row is. With None the program is the one
    without the argument, instruction for instruction."""
    return ClassifierState(*train_rows(
        *state, idx, val, labels, label_mask, param, method=method,
        reduce=_over_documents(owner)))


@functools.partial(jax.jit, static_argnames=("method",), donate_argnums=(0,))
def train_batch_schema(
    state: ClassifierState,
    uidx: jax.Array,       # [K] int32 — the shared hashed index vector
    val: jax.Array,        # [B, K] float32
    labels: jax.Array,     # [B] int32 — correct label row per example
    label_mask: jax.Array, # [L] bool — live labels
    param: float,
    *,
    method: str,
) -> ClassifierState:
    """Vectorized microbatch update for a UNIFORM-SCHEMA batch: every
    example carries the same hashed index vector ``uidx`` (a fixed key
    schema). The driver takes it where a flush's rows say so
    (models/classifier.py _train_slots).

    Semantics are identical to train_batch_parallel (every example
    decides against the batch-start snapshot, updates land together) —
    only the execution plan differs: with one shared index vector the
    B*K-element gather collapses to K descriptors (``take(.., uidx)``),
    scoring becomes a [B,K]x[K,L] matmul, and the two B*K-element
    scatter-adds become label-grouped dense reductions (one-hot matmuls,
    [L,B]x[B,K]) followed by ONE K-column scatter. On v5e at D = 2^25,
    L = 8, K = 40 the step is 8.9 ms whatever the rows, almost all of it
    the two table-wide sums under the ``take``s, against 36.7 ms for the
    sparse plan at 8,192 rows and 8.8 at 512 (PERF.md section 6, PR 28).
    Float summation order differs from the sparse plan (dense reductions
    vs scatter order), so results agree to tolerance, not bitwise.

    Duplicate entries in ``uidx`` (e.g. width-padding zeros) are safe:
    the final ``.at[:, uidx].add`` accumulates per occurrence, exactly
    like the sparse scatter over repeated (b, k) slots, and padded
    columns carry val 0 so they contribute nothing.
    """
    confidence = method in CONFIDENCE_METHODS
    w, dw, prec, dprec = state
    num_labels = w.shape[0]

    eff_sub = jnp.take(w + dw, uidx, axis=1)                       # [L, K]
    s = val @ eff_sub.T                                            # [B, L]
    x2_vec = val * val                                             # [B, K]
    x2 = jnp.sum(x2_vec, axis=1)                                   # [B]

    if confidence:
        sig_sub = 1.0 / jnp.take(prec + dprec, uidx, axis=1)       # [L, K]
        sig_c = jnp.take(sig_sub, labels, axis=0)                  # [B, K]
        # `wrong` needs the scores only, so the provisional pass mirrors
        # train_batch_parallel exactly (alpha from it is ignored)
        wrong0, _, _, _ = decide_updates(
            s, labels, label_mask, x2, jnp.zeros_like(x2), x2_vec, param,
            method=method,
        )
        no_rival = jnp.sum(label_mask) < 2
        sig_w = jnp.where(no_rival, 1.0,
                          jnp.take(sig_sub, wrong0, axis=0))       # [B, K]
        v = jnp.sum((sig_c + sig_w) * x2_vec, axis=1)              # [B]
    else:
        sig_c = jnp.ones_like(val)
        sig_w = jnp.ones_like(val)
        v = jnp.zeros_like(x2)

    wrong, alpha, alpha_w, dp = decide_updates(
        s, labels, label_mask, x2, v, x2_vec, param, method=method
    )

    up_c = alpha[:, None] * sig_c * val                            # [B, K]
    up_w = alpha_w[:, None] * sig_w * val
    onehot_c = jax.nn.one_hot(labels, num_labels, dtype=val.dtype)  # [B, L]
    onehot_w = jax.nn.one_hot(wrong, num_labels, dtype=val.dtype)
    delta_w = onehot_c.T @ up_c - onehot_w.T @ up_w                # [L, K]
    dw = dw.at[:, uidx].add(delta_w)
    if confidence:
        dp_w = jnp.where((alpha_w > 0.0)[:, None], dp, 0.0)
        delta_p = onehot_c.T @ dp + onehot_w.T @ dp_w              # [L, K]
        dprec = dprec.at[:, uidx].add(delta_p)
    return ClassifierState(w, dw, prec, dprec)


@functools.partial(jax.jit, static_argnames=("method",), donate_argnums=(0,))
def train_batch_sequential(
    state: ClassifierState,
    idx: jax.Array,        # [B, K] int32
    val: jax.Array,        # [B, K] float32
    labels: jax.Array,     # [B] int32 — correct label row per example
    label_mask: jax.Array, # [L] bool — live labels
    param: float,
    *,
    method: str,
) -> ClassifierState:
    """Online train with exact per-example sequential semantics (lax.scan).

    Matches the reference's per-datum update loop exactly
    (classifier_serv.cpp:137-143); use train_batch_parallel for throughput.
    """
    confidence = method in CONFIDENCE_METHODS
    mask_scores = jnp.where(label_mask, 0.0, _NEG)  # [L]

    def step(carry, ex):
        w, dw, prec, dprec = carry
        e_idx, e_val, e_label = ex
        # effective weights for this example's features: [L, K]
        w_g = jnp.take(w, e_idx, axis=1) + jnp.take(dw, e_idx, axis=1)
        s = w_g @ e_val + mask_scores  # [L]
        s_correct = s[e_label]
        s_wrong = jnp.max(s.at[e_label].set(_NEG))
        wrong = jnp.argmax(s.at[e_label].set(_NEG))
        # no competitor label → rival score 0 (still learn; nothing lands on
        # the dead slot `wrong` points at)
        no_rival = s_wrong <= _NEG / 2
        margin = s_correct - jnp.where(no_rival, 0.0, s_wrong)
        loss = jnp.maximum(0.0, 1.0 - margin)
        x2_vec = e_val * e_val
        x2 = jnp.sum(x2_vec)
        live = x2 > 0.0

        if confidence:
            p_g = jnp.take(prec, e_idx, axis=1) + jnp.take(dprec, e_idx, axis=1)
            sig_c = 1.0 / p_g[e_label]  # [K]
            # nonexistent rival carries the unit precision prior
            sig_w = jnp.where(no_rival, 1.0, 1.0 / p_g[wrong])
            v = jnp.sum((sig_c + sig_w) * x2_vec)
        else:
            sig_c = sig_w = 1.0
            v = 0.0

        alpha, dp = _alpha_and_prec(method, param, margin, loss, x2, v, x2_vec)
        alpha = jnp.where(live, alpha, 0.0)
        alpha_w = jnp.where(no_rival, 0.0, alpha)

        dw = dw.at[e_label, e_idx].add(alpha * sig_c * e_val)
        dw = dw.at[wrong, e_idx].add(-alpha_w * sig_w * e_val)
        if confidence:
            dp = jnp.where(live & (alpha > 0.0), dp, 0.0)
            dprec = dprec.at[e_label, e_idx].add(dp)
            dprec = dprec.at[wrong, e_idx].add(
                jnp.where(alpha_w > 0.0, dp, 0.0)
            )
        return (w, dw, prec, dprec), alpha > 0.0

    (w, dw, prec, dprec), updated = jax.lax.scan(
        step, tuple(state), (idx, val, labels)
    )
    return ClassifierState(w, dw, prec, dprec)


def train_batch(
    state: ClassifierState,
    idx: jax.Array,
    val: jax.Array,
    labels: jax.Array,
    label_mask: jax.Array,
    param: float,
    *,
    method: str,
    mode: str = "parallel",
    owner=None,
) -> ClassifierState:
    """Train dispatcher: mode="parallel" (TPU hot path, intra-batch snapshot
    semantics; ``owner``: the rows are slabs, train_batch_parallel) or
    "sequential" (exact reference per-datum semantics, whole rows only)."""
    if mode == "parallel":
        return train_batch_parallel(state, idx, val, labels, label_mask,
                                    param, owner, method=method)
    if mode != "sequential":
        raise ValueError(f"unknown train mode {mode!r}")
    if owner is not None:
        raise ValueError("the sequential scan takes whole rows, not slabs")
    return train_batch_sequential(state, idx, val, labels, label_mask, param,
                                  method=method)


# -- mixable protocol -------------------------------------------------------
def get_diff(state: ClassifierState):
    """Local diff pytree; mix = elementwise sum (associative → psum-exact)."""
    return {"dw": state.dw, "dprec": state.dprec, "count": jnp.float32(1.0)}


def mix_diffs(lhs, rhs):
    return jax.tree_util.tree_map(lambda a, b: a + b, lhs, rhs)


@jax.jit
def put_diff(state: ClassifierState, diff) -> ClassifierState:
    """Absorb the summed cross-replica diff into the master (average weights,
    sum precision — precision is additive information like the reference's
    confidence merge) and reset local diffs.

    Accepts ROW-TRIMMED diffs: the mix plane ships only the active label
    rows ([n_labels, D], not the pow2-padded [capacity, D] tables — a 4x
    wire cut at the bench shape), applied here to the leading rows; a
    full-shape diff is the n == capacity case of the same update."""
    n = jnp.maximum(diff["count"], 1.0)
    rows = diff["dw"].shape[0]
    return ClassifierState(
        w=state.w.at[:rows].add(diff["dw"] / n),
        dw=jnp.zeros_like(state.dw),
        prec=state.prec.at[:diff["dprec"].shape[0]].add(diff["dprec"]),
        dprec=jnp.zeros_like(state.dprec),
    )
