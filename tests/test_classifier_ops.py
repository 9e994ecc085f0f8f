"""Classifier kernel tests: learning behavior of every method + mix semantics.

Mirrors the reference's test intent for classifier algorithms and the
mix-fold associativity assertion in linear_mixer_test.cpp:156-169 — here the
stronger property holds: diffs are additive so any mix order is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jubatus_tpu.core.sparse import SparseBatch
from jubatus_tpu.ops import classifier as C

DIM = 1 << 12
L = 4


def make_blobs(rng, n, n_features=16, n_classes=3, sep=3.0):
    """Sparse-ish synthetic multiclass data in the hashed index space."""
    centers = rng.normal(size=(n_classes, n_features)) * sep
    labels = rng.integers(0, n_classes, size=n)
    dense = centers[labels] + rng.normal(size=(n, n_features))
    # map features to fixed distinct hash indices (avoid 0, the padding slot)
    feat_idx = rng.choice(np.arange(1, DIM), size=n_features, replace=False)
    vectors = [
        [(int(feat_idx[j]), float(dense[i, j])) for j in range(n_features)]
        for i in range(n)
    ]
    return vectors, labels


def batchify(vectors, labels):
    sb = SparseBatch.from_vectors(vectors)
    return (
        jnp.asarray(sb.idx),
        jnp.asarray(sb.val),
        jnp.asarray(labels, jnp.int32),
    )


def accuracy(state, idx, val, labels, mask):
    s = C.scores(state, idx, val, mask)
    return float(jnp.mean(jnp.argmax(s, axis=1) == labels))


@pytest.mark.parametrize("mode", ["parallel", "sequential"])
@pytest.mark.parametrize("method", C.METHODS)
def test_method_learns_separable_data(method, mode, rng):
    vectors, labels = make_blobs(rng, 300)
    idx, val, y = batchify(vectors, labels)
    mask = jnp.array([True, True, True, False])
    state = C.init_state(L, DIM, method in C.CONFIDENCE_METHODS)
    param = 1.0
    for _ in range(3):
        state = C.train_batch(state, idx, val, y, mask, param, method=method, mode=mode)
    acc = accuracy(state, idx, val, y, mask)
    assert acc > 0.9, f"{method}/{mode} failed to learn: acc={acc}"


def test_parallel_matches_sequential_on_batch_of_one(rng):
    """With B=1 the snapshot semantics coincide: both paths must agree."""
    vectors, labels = make_blobs(rng, 20)
    mask = jnp.array([True, True, True, False])
    s_par = C.init_state(L, DIM, True)
    s_seq = C.init_state(L, DIM, True)
    for vec, lab in zip(vectors, labels):
        sb = SparseBatch.from_vectors([vec])
        args = (jnp.asarray(sb.idx), jnp.asarray(sb.val),
                jnp.asarray([lab], jnp.int32), mask, 1.0)
        s_par = C.train_batch(s_par, *args, method="AROW", mode="parallel")
        s_seq = C.train_batch(s_seq, *args, method="AROW", mode="sequential")
    np.testing.assert_allclose(np.asarray(s_par.dw), np.asarray(s_seq.dw),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_par.dprec), np.asarray(s_seq.dprec),
                               rtol=1e-5, atol=1e-6)


def test_dead_labels_never_predicted(rng):
    vectors, labels = make_blobs(rng, 100, n_classes=2)
    idx, val, y = batchify(vectors, labels)
    mask = jnp.array([True, True, False, False])
    state = C.init_state(L, DIM, False)
    state = C.train_batch(state, idx, val, y, mask, 1.0, method="PA")
    s = C.scores(state, idx, val, mask)
    assert int(jnp.max(jnp.argmax(s, axis=1))) <= 1


def test_single_label_still_learns(rng):
    """With one live label the rival score is 0 (jubatus_core calc_margin
    initializes the incorrect score to 0 when no other label exists), so the
    correct row still gets its update — and nothing lands on dead slots."""
    vectors, labels = make_blobs(rng, 10, n_classes=1)
    idx, val, y = batchify(vectors, labels)
    mask = jnp.array([True, False, False, False])
    state = C.init_state(L, DIM, False)
    state = C.train_batch(state, idx, val, y, mask, 1.0, method="PA")
    dw = np.asarray(state.dw)
    assert np.abs(dw[0]).max() > 0.0       # the live label learned
    assert np.abs(dw[1:]).max() == 0.0     # dead slots untouched


def test_padding_is_noop(rng):
    """Padded entries (idx 0, val 0) must not perturb the model."""
    vectors, labels = make_blobs(rng, 50)
    mask = jnp.array([True, True, True, False])
    sb_narrow = SparseBatch.from_vectors(vectors, min_width=16)
    sb_wide = SparseBatch.from_vectors(vectors, min_width=64)
    y = jnp.asarray(labels, jnp.int32)
    s1 = C.init_state(L, DIM, True)
    s2 = C.init_state(L, DIM, True)
    s1 = C.train_batch(s1, jnp.asarray(sb_narrow.idx), jnp.asarray(sb_narrow.val),
                       y, mask, 1.0, method="AROW")
    s2 = C.train_batch(s2, jnp.asarray(sb_wide.idx), jnp.asarray(sb_wide.val),
                       y, mask, 1.0, method="AROW")
    np.testing.assert_allclose(np.asarray(s1.dw), np.asarray(s2.dw), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1.dprec), np.asarray(s2.dprec), atol=1e-5)


def test_mix_diff_additive_and_order_free(rng):
    """Two replicas train on disjoint halves; mixing their diffs in either
    order gives the identical master — the exact-psum property that replaces
    the reference's sequential fold (linear_mixer.cpp:481-499)."""
    vectors, labels = make_blobs(rng, 200)
    half = 100
    mask = jnp.array([True, True, True, False])
    states = []
    for lo, hi in ((0, half), (half, 200)):
        idx, val, y = batchify(vectors[lo:hi], labels[lo:hi])
        st = C.init_state(L, DIM, True)
        st = C.train_batch(st, idx, val, y, mask, 1.0, method="AROW")
        states.append(st)
    d0, d1 = C.get_diff(states[0]), C.get_diff(states[1])
    m01 = C.mix_diffs(d0, d1)
    m10 = C.mix_diffs(d1, d0)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        , m01, m10)
    assert float(m01["count"]) == 2.0

    mixed0 = C.put_diff(states[0], m01)
    mixed1 = C.put_diff(states[1], m10)
    np.testing.assert_allclose(np.asarray(mixed0.w), np.asarray(mixed1.w), atol=1e-6)
    # post-mix local diffs are cleared
    assert float(jnp.abs(mixed0.dw).max()) == 0.0
    # mixed model still classifies the full set well
    idx, val, y = batchify(vectors, labels)
    acc = accuracy(mixed0, idx, val, y, mask)
    assert acc > 0.85


def test_grow_labels_preserves_model(rng):
    vectors, labels = make_blobs(rng, 100)
    idx, val, y = batchify(vectors, labels)
    mask = jnp.array([True, True, True, False])
    state = C.init_state(L, DIM, True)
    state = C.train_batch(state, idx, val, y, mask, 1.0, method="AROW")
    grown = C.grow_labels(state, 6)
    assert grown.w.shape == (6, DIM)
    np.testing.assert_allclose(np.asarray(grown.w[:L]), np.asarray(state.w))
    mask6 = jnp.concatenate([mask, jnp.array([False, False])])
    acc = accuracy(grown, idx, val, y, mask6)
    assert acc > 0.9


# -- the parallel step, flush by flush, against the per-datum rule -----------
AROW_CONF = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
             "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
CAP = 8
HOT = 7        # the column every row of the "hot_column" flush hits


def _fresh(state):
    """A copy the step may donate."""
    return C.ClassifierState(*(jnp.array(a) for a in state))


def _warm_state(rng, cap, dim, method, live):
    """A model that has learned something: masters and diffs both non-zero
    on the ``live`` first label rows, every other row as initialised."""
    conf = method in C.CONFIDENCE_METHODS
    mask = jnp.asarray(np.arange(cap) < live)
    state = C.init_state(cap, dim, conf)
    for fold in (True, False):
        idx = jnp.asarray(rng.integers(1, dim, size=(32, 6)), jnp.int32)
        val = jnp.asarray(rng.normal(size=(32, 6)), jnp.float32)
        y = jnp.asarray(rng.integers(0, live, size=32), jnp.int32)
        state = C.train_batch_parallel(state, idx, val, y, mask, 1.0,
                                       method=method)
        if fold:
            state = C.put_diff(state, C.get_diff(state))
    return state


#: (dim, the plan a flush takes there, its width): the narrow flushes the
#: scenarios were written at, the power-of-two bucket of a row of 780
#: features (a combination configuration's), and the rungs the benchmark's
#: rows ride at since ISSUE 27 (39 features at 40, 780 at 832: no multiple
#: of 128, nor a power of two), each on either side of the choice
PLANS = [(1 << 10, "packed", None), (1 << 16, "columns", None),
         (1 << 12, "packed", 1024), (1 << 19, "columns", 1024),
         (1 << 10, "packed", 40), (1 << 16, "columns", 40),
         (1 << 12, "packed", 832), (1 << 19, "columns", 832)]


def _flush(rng, scenario, dim, method, width=None):
    """(state, idx, val, labels, mask) of one flush of the scenario;
    ``width``: of that many entries a row, and three rows."""
    cap, live, b, k = CAP, 3, 24, 6
    if width:
        b, k = 3, width
    if scenario == "single_label":
        live = 1
    elif scenario == "ragged":          # B*K = 63: no multiple of 128, or of 8
        b, k = (3, width - 3) if width else (7, 9)
    elif scenario == "grown_16":
        live = 10
    elif scenario == "grown_32":        # twenty labels: four sublane groups
        live = 20
    state = _warm_state(rng, CAP, dim, method, min(live, CAP))
    if scenario in ("grown_16", "grown_32"):
        cap = int(scenario[-2:])
        state = C.grow_labels(state, cap)
        assert state.w.shape == (cap, dim)
    idx = rng.integers(1, dim, size=(b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    labels = rng.integers(0, live, size=b).astype(np.int32)
    if scenario == "hot_column":        # many rows of every label, one column
        idx[:, 0] = HOT
        labels = (np.arange(b) % live).astype(np.int32)
    elif scenario == "padding":
        idx[:, k // 2:] = 0
        val[:, k // 2:] = 0.0
    elif scenario == "uniform_rows":    # a fixed key schema: one index row,
        idx[:] = idx[0]                 # its width padding, some empty rows
        idx[:, -2:] = 0
        val[:, -2:] = 0.0
        val[1::3] = 0.0
    mask = jnp.asarray(np.arange(cap) < live)
    return state, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels), mask


def _flush_by_the_per_datum_rule(state, idx, val, labels, mask, method):
    """What a flush has to leave in (dw, dprec): every row decided by the
    sequential rule against the model as the flush found it, the rows'
    updates added up (in float64: only the order of additions is free)."""
    conf = method in C.CONFIDENCE_METHODS
    # masters + diffs folded, so that a row's diff IS its update, unrounded
    snap = C.ClassifierState(
        state.w + state.dw, jnp.zeros_like(state.dw),
        state.prec + state.dprec, jnp.zeros_like(state.dprec))
    dw = np.asarray(state.dw, np.float64)
    dprec = np.asarray(state.dprec, np.float64)
    for b in range(idx.shape[0]):
        one = C.train_batch_sequential(
            _fresh(snap), idx[b:b + 1], val[b:b + 1], labels[b:b + 1], mask,
            1.0, method=method)
        dw += np.asarray(one.dw, np.float64)
        if conf:
            dprec += np.asarray(one.dprec, np.float64)
    return dw, dprec


SCENARIOS = ["hot_column", "single_label", "padding", "grown_16", "ragged",
             "uniform_rows", "grown_32"]


@pytest.mark.parametrize("method,scenario,dim,plan,width", [
    (m, s) + p for p in PLANS for s in SCENARIOS
    # at the wide row, and at 32 label rows: one method with a precision
    # table and one without (and 32 rows at the narrow widths alone: the
    # per-datum rule is slow there)
    for m in (C.METHODS if p[2] is None and s != "grown_32"
              else ("PA", "AROW"))
    if s != "grown_32" or p[2] in (None, 40)])
def test_a_flush_is_its_rows_by_the_per_datum_rule(method, scenario, dim, plan,
                                                   width, rng):
    state, idx, val, labels, mask = _flush(rng, scenario, dim, method, width)
    assert C.gather_plan(state.w.shape[0], dim, idx.size) == plan
    before = [np.asarray(a).copy() for a in state]
    want_dw, want_dprec = _flush_by_the_per_datum_rule(
        state, idx, val, labels, mask, method)
    got = C.train_batch_parallel(_fresh(state), idx, val, labels, mask, 1.0,
                                 method=method)
    got = [np.asarray(a) for a in got]
    np.testing.assert_allclose(got[1], want_dw, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[3], want_dprec, rtol=2e-5, atol=2e-6)
    assert np.abs(got[1] - before[1]).max() > 0.0      # and it did learn
    # the masters, the dead label rows and the columns no row names are
    # the same bytes as before the step
    dead = ~np.asarray(mask)
    cold = np.ones(dim, bool)
    cold[np.asarray(idx).reshape(-1)] = False
    for was, now in zip(before, got):
        if was.shape == (1, 1):
            continue
        assert now[dead].tobytes() == was[dead].tobytes()
        assert now[:, cold].tobytes() == was[:, cold].tobytes()
    assert got[0].tobytes() == before[0].tobytes()
    assert got[2].tobytes() == before[2].tobytes()
    if scenario == "uniform_rows":
        # every column is hit by every row, the padding slot is left alone,
        # and the dense plan the driver takes for such a flush is the same
        # flush to float tolerance
        assert got[1][:, 0].tobytes() == before[1][:, 0].tobytes()
        dense = C.train_batch_schema(_fresh(state), idx[0], val, labels, mask,
                                     1.0, method=method)
        np.testing.assert_allclose(np.asarray(dense.dw), want_dw,
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(dense.dprec), want_dprec,
                                   rtol=2e-5, atol=2e-6)
    if scenario == "padding":
        # column 0 is the padding slot: (idx 0, val 0) entries leave it alone,
        # and the flush without them is the same flush
        assert got[1][:, 0].tobytes() == before[1][:, 0].tobytes()
        k = idx.shape[1] // 2
        narrow = C.train_batch_parallel(
            _fresh(state), idx[:, :k], val[:, :k], labels, mask, 1.0,
            method=method)
        np.testing.assert_allclose(got[1], np.asarray(narrow.dw),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(8, 1 << 12), (16, 1 << 10), (24, 384),
                                   (4, 256), (8, 200), (3, 50)])
def test_the_tpu_scatter_is_the_plain_scatter(shape, rng):
    """_scatter_add's TPU branch addresses the table in tile order; off the
    TPU it is still the same sum (B*K = 63, rows and columns repeated)."""
    rows_n, dim = shape
    table = jnp.asarray(rng.normal(size=shape), jnp.float32)
    rows = jnp.asarray(rng.integers(0, rows_n, size=7), jnp.int32)
    idx = jnp.asarray(rng.integers(0, dim, size=(7, 9)), jnp.int32)
    idx = idx.at[:, 0].set(5)
    up = jnp.asarray(rng.normal(size=(7, 9)), jnp.float32)
    want = table.at[rows[:, None], idx].add(up)
    got = jax.jit(C._scatter_add_tiled)(table, rows, idx, up)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    hit = np.zeros(shape, bool)
    hit[np.asarray(rows)[:, None], np.asarray(idx)] = True
    assert np.asarray(got)[~hit].tobytes() == np.asarray(table)[~hit].tobytes()
    np.testing.assert_array_equal(
        np.asarray(C._scatter_add(table, rows, idx, up)), np.asarray(want))


@pytest.mark.parametrize("dim,plan,width", PLANS)
def test_scores_are_the_same_bits_on_either_gather_plan(dim, plan, width, rng):
    state = _warm_state(rng, CAP, dim, "AROW", 3)
    shape = (3, width) if width else (7, 9)
    idx = jnp.asarray(rng.integers(0, dim, size=shape), jnp.int32)
    val = jnp.asarray(rng.normal(size=shape), jnp.float32)
    mask = jnp.asarray(np.arange(CAP) < 3)
    assert C.gather_plan(CAP, dim, idx.size) == plan
    got = np.asarray(C.scores(state, idx, val, mask))
    eff = np.asarray(state.w + state.dw)                      # [L, D]
    want = np.einsum("lbk,bk->bl", eff[:, np.asarray(idx)], np.asarray(val))
    np.testing.assert_allclose(got[:, :3], want[:, :3], rtol=1e-5,
                               atol=1e-4 if width else 1e-6)
    assert (got[:, 3:] == C._NEG).all()


@pytest.mark.parametrize("plan", ["columns", "packed"])
@pytest.mark.parametrize("cap,dim", [(16, 1 << 10), (32, 1 << 12), (32, 384),
                                     (24, 200), (12, 256)])
def test_past_eight_labels_the_gather_is_the_plain_gather_to_the_bit(
        cap, dim, plan, rng, monkeypatch):
    """Tables of several sublane groups are addressed a group at a time
    (``_by_group``), where they lie in whole tiles; what comes back is
    ``(master + diff)[:, idx]`` for every pair, on either plan, bit for
    bit, and the plan is one group's rule whatever the capacity."""
    pairs = [(jnp.asarray(rng.normal(size=(cap, dim)), jnp.float32),
              jnp.asarray(rng.normal(size=(cap, dim)), jnp.float32))
             for _ in range(2)]
    idx = jnp.asarray(rng.integers(0, dim, size=(7, 9)), jnp.int32)
    group = C._label_group(cap, dim)
    assert group == (8 if cap % 8 == 0 and dim % 128 == 0 else cap)
    assert C.gather_plan(cap, dim, 63) == C.gather_plan(group, dim, 63)
    monkeypatch.setattr(C, "gather_plan", lambda *a: plan)
    got = jax.jit(C._gather_sums)(pairs, idx)
    for (m, d), g in zip(pairs, got):
        want = (np.asarray(m) + np.asarray(d))[:, np.asarray(idx)]
        assert g.shape == (cap, 7, 9)
        assert np.asarray(g).tobytes() == want.tobytes()
    # the view is the table, group g's column c at column g * dim + c
    view = np.asarray(C._by_group(pairs[0][0]))
    table = np.asarray(pairs[0][0])
    assert view.shape == (group, cap // group * dim)
    for g in range(cap // group):
        assert np.array_equal(view[:, g * dim:(g + 1) * dim],
                              table[g * group:(g + 1) * group])


def test_a_model_grown_to_32_in_one_call_is_one_born_at_32(rng, monkeypatch):
    """Twenty labels in a model's first call: the tables go 8 -> 16 -> 32
    rows under the driver's lock before the first step. What the call
    leaves is, to the bit, what a model with 32 rows from the start holds,
    and the growth is counted, timed and shown as gauges."""
    from jubatus_tpu.models import classifier as M
    from jubatus_tpu.utils import tracing

    idx = rng.integers(1, DIM, size=(60, 24)).astype(np.int32)
    val = rng.normal(size=(60, 24)).astype(np.float32)
    names = [f"label{i % 20:02d}" for i in range(60)]
    grown = M.ClassifierDriver(AROW_CONF, dim_bits=12)
    grown.trace = reg = tracing.Registry()
    assert grown.capacity == 8
    monkeypatch.setattr(M, "_INITIAL_CAPACITY", 32)
    born = M.ClassifierDriver(AROW_CONF, dim_bits=12)
    assert born.capacity == 32
    for d in (grown, born):
        for _ in range(2):
            d.train_hashed(names, idx, val)
    assert grown.capacity == 32 and grown.labels == born.labels
    for a, b in zip(grown.state, born.state):
        assert a.shape == (32, DIM)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert grown.classify_hashed(idx, val) == born.classify_hashed(idx, val)
    assert len(grown.classify_hashed(idx[:3], val[:3])[0]) == 20
    assert reg.counters()["model.label_grow"] == 2
    assert reg.trace_status()["trace.model.grow_labels.count"] == 2
    assert reg.gauges()["model.labels_live"] == 20
    assert reg.gauges()["model.label_capacity"] == 32
    assert grown.get_status()["label_capacity"] == 32
    # and after a clear the model is born small again, and says so
    monkeypatch.setattr(M, "_INITIAL_CAPACITY", 8)
    grown.clear()
    assert reg.gauges()["model.labels_live"] == 0
    assert reg.gauges()["model.label_capacity"] == 8


@pytest.mark.parametrize("narrow,wide", [(704, 1024), (40, 64), (320, 512)])
def test_a_flush_at_a_rung_and_at_a_power_of_two_is_one_model(narrow, wide,
                                                              rng):
    """The width rule packs uneven rows at the power of two where chance
    gave a rung (704 for 1,024): the padding is (column 0, value 0)
    entries, and the model that results is the same to the bit on the
    CPU, as are the scores."""
    dim = 1 << 14
    state = C.grow_labels(_warm_state(rng, CAP, dim, "AROW", 8), 32)
    mask = jnp.asarray(np.arange(32) < 20)
    b = 12
    counts = rng.integers(1, narrow + 1, size=b)
    counts[0] = narrow
    idx = np.zeros((b, narrow), np.int32)
    val = np.zeros((b, narrow), np.float32)
    for i, n in enumerate(counts):
        idx[i, :n] = rng.integers(1, dim, size=n)
        val[i, :n] = 1.0
    labels = jnp.asarray(rng.integers(0, 20, size=b), jnp.int32)
    pad = ((0, 0), (0, wide - narrow))
    got = []
    for i, v in ((idx, val), (np.pad(idx, pad), np.pad(val, pad))):
        new = C.train_batch_parallel(_fresh(state), jnp.asarray(i),
                                     jnp.asarray(v), labels, mask, 1.0,
                                     method="AROW")
        got.append([np.asarray(a) for a in new]
                   + [np.asarray(C.scores(new, jnp.asarray(i),
                                          jnp.asarray(v), mask))])
    for a, w in zip(*got):
        assert a.tobytes() == w.tobytes()
    assert np.abs(got[0][1]).max() > 0


def _uneven_flush(rng, b, k, dim, labels=20):
    """Rows of heavy-tailed length at width ``k``: the fullest first, one
    with no feature, one with a zeroed entry in its middle (what the
    ingest's finite screen leaves of a NaN)."""
    counts = np.clip(np.floor(np.exp(rng.normal(4.5, 0.8, size=b)) * 0.73),
                     1, k).astype(int)
    counts[0], counts[3] = k, 0
    idx = np.zeros((b, k), np.int32)
    val = np.zeros((b, k), np.float32)
    for i, n in enumerate(counts):
        idx[i, :n] = np.sort(rng.choice(np.arange(1, dim), size=n,
                                        replace=False))
        val[i, :n] = rng.uniform(0.5, 2.0, size=n)
    idx[5, 1], val[5, 1] = 0, 0.0
    return idx, val, rng.integers(0, labels, size=b).astype(np.int32)


@pytest.mark.parametrize("method", ["AROW", "PA", "CW"])
@pytest.mark.parametrize("width", [1024, 704, 320])
def test_uneven_rows_that_share_no_column_train_as_one_by_one(method, width,
                                                              rng):
    """A flush of uneven rows at 32 label rows (20 live: a rival is chosen
    among 19), at the width of its fullest row. Where no two rows share a
    column every row meets the model the flush began with, so the
    vectorised step leaves what the reference's one-by-one scan leaves, up
    to the order of float additions; a row without a feature and a zeroed
    entry change nothing."""
    dim, b = 1 << 14, 96
    state = C.grow_labels(_warm_state(rng, CAP, dim, method, 8), 32)
    mask = jnp.asarray(np.arange(32) < 20)
    idx, val, labels = _uneven_flush(rng, b, width, dim)
    # the same lengths over columns no two rows share
    cols = rng.permutation(np.arange(1, dim)).astype(np.int32)
    used = idx != 0
    assert used.sum() < cols.size and used[0].all() and not used[3].any()
    idx[used] = cols[:used.sum()]
    got = [C.train_batch(_fresh(state), jnp.asarray(idx), jnp.asarray(val),
                         jnp.asarray(labels), mask, 1.0, method=method,
                         mode=mode) for mode in ("parallel", "sequential")]
    for a, w in zip(*got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(got[0].dw) - np.asarray(state.dw)).max() > 0
    # rows 24 to 31 hold no live label and are never written
    assert not np.asarray(got[0].dw)[20:].any()


@pytest.mark.parametrize("kind,width", [
    ("click_log", 40), ("cross", 832), ("text", 1024), ("text", 128)])
def test_the_driver_runs_a_flush_at_the_width_it_came_at(kind, width, rng):
    """Rows that are alike and rows of uneven length run as they come: one
    program a width, the rows and the entries (those that carry a feature,
    and those the width makes of them) counted as the program ran them."""
    from jubatus_tpu.models import classifier as M
    from jubatus_tpu.utils import tracing

    dim, b = 1 << 14, 200
    if kind == "text":
        idx, val, _ = _uneven_flush(rng, b, width, dim)
    else:
        idx = rng.integers(1, dim, size=(b, width)).astype(np.int32)
        idx[:, width - (1 if kind == "click_log" else 52):] = 0
        val = (idx != 0).astype(np.float32)
    names = [f"label{i % 20:02d}" for i in range(b)]
    d = M.ClassifierDriver(AROW_CONF, dim_bits=14)
    d.trace = reg = tracing.Registry()
    for _ in range(2):
        assert d.train_hashed(names, idx, val) == b
    c = reg.counters()
    assert [k for k in c if k.startswith("step.train.width_")] \
        == [f"step.train.width_{width}"]
    assert c[f"step.train.width_{width}"] == 2
    assert c["step.train.rows"] == 2 * b
    assert c["step.train.rows_padded"] == 2 * 256
    assert c["step.train.entries"] == 2 * np.count_nonzero(idx)
    assert c["step.train.entries_padded"] == 2 * b * width
    if kind == "text" and width == 1024:
        # uneven rows, nine entries in ten padding: handed over as slabs
        # of 64 entries, each with its label and its document's number
        assert c["step.train.slab_flushes"] == 2
        slabs = c["step.train.slabs_padded"] // 2
        assert slabs * 64 * 2 <= 256 * width
        assert c["step.train.upload_bytes"] == 2 * slabs * (64 * 8 + 8)
    else:
        assert "step.train.slab_flushes" not in c
        assert c["step.train.upload_bytes"] \
            == 2 * (256 * width * 8 + 256 * 4)
    assert d.update_count == 2 * b and d.capacity == 32
    assert len(d.classify_hashed(idx[:8], val[:8])[0]) == 20
    assert reg.counters()[f"step.classify.width_{width}"] == 1


def _row(idx, val, i, n, rng, dim):
    """Row ``i`` with ``n`` entries, packed from column 0."""
    idx[i], val[i] = 0, 0.0
    idx[i, :n] = np.sort(rng.choice(np.arange(1, dim), size=n, replace=False))
    val[i, :n] = rng.uniform(0.5, 2.0, size=n)


def _model(rng, cap, dim, method):
    """A warm model of ``cap`` label rows, its mask and how many are live."""
    if cap == CAP:
        return _warm_state(rng, CAP, dim, method, 5), \
            jnp.asarray(np.arange(CAP) < 5), 5
    return C.grow_labels(_warm_state(rng, CAP, dim, method, 8), cap), \
        jnp.asarray(np.arange(cap) < 20), 20


def _in_rows_and_in_slabs(state, idx, val, labels, mask, method):
    """The flush trained as it came, in its row bucket, and as the slabs
    the driver cuts it into: the four tables of each."""
    from jubatus_tpu.core.sparse import _bucket
    from jubatus_tpu.models import classifier as M

    b = len(labels)
    bsz = _bucket(b, 16)
    slabs = M._cut_slabs(idx, val, labels, bsz)
    assert slabs is not None
    sidx, sval, slabels, owner, n, entries = slabs
    assert entries == np.count_nonzero(idx) == np.count_nonzero(sidx)
    assert np.array_equal(sval != 0, sidx != 0)
    assert (np.diff(owner) >= 0).all() and owner.max() < len(owner)
    pad = ((0, bsz - b), (0, 0))
    rows = C.train_batch_parallel(
        _fresh(state), jnp.asarray(np.pad(idx, pad)),
        jnp.asarray(np.pad(val, pad)),
        jnp.asarray(np.pad(labels, (0, bsz - b))), mask, 1.0, method=method)
    cut = C.train_batch_parallel(
        _fresh(state), jnp.asarray(sidx), jnp.asarray(sval),
        jnp.asarray(slabels), mask, 1.0, jnp.asarray(owner), method=method)
    return [np.asarray(a) for a in rows], [np.asarray(a) for a in cut], slabs


@pytest.mark.parametrize("cap", [CAP, 32])
@pytest.mark.parametrize("method", ["AROW", "PA"])
def test_a_flush_in_slabs_is_the_flush_in_rows(method, cap, rng):
    """Uneven rows at 1,024 wide, among them rows of 0, 1, 64, 65, 984 and
    1,024 entries, cut into slabs of 64: a document's scores, x2 and v are
    summed over its slabs (two float32 reductions where the rows' were
    one) and every slab decides its document's alpha and rival, so each
    entry's update is the one the row form makes: the tables agree within
    1e-6, at 8 and at 32 label rows."""
    dim, b, k = 1 << 14, 96, 1024
    state, mask, live = _model(rng, cap, dim, method)
    idx, val, labels = _uneven_flush(rng, b, k, dim, labels=live)
    for i, n in ((6, 1), (7, 64), (8, 65), (9, 984)):
        _row(idx, val, i, n, rng, dim)
    rows, cut, (sidx, _v, slabels, owner, n, _e) = _in_rows_and_in_slabs(
        state, idx, val, labels, mask, method)
    counts = np.count_nonzero(idx, axis=1)
    assert n == np.sum(-(-counts // 64)) and n < len(sidx) < 2 * n
    # the row with no entry has no slab; the others' slabs carry their label
    docs = np.flatnonzero(counts)
    assert 3 not in docs
    assert np.array_equal(slabels[:n], labels[docs][owner[:n]])
    assert np.array_equal(np.bincount(owner[:n]), -(-counts[docs] // 64))
    for a, w in zip(cut, rows):
        np.testing.assert_allclose(a, w, rtol=1e-6, atol=1e-6)
    assert np.abs(rows[1] - np.asarray(state.dw)).max() > 0.01


@pytest.mark.parametrize("cap", [CAP, 32])
@pytest.mark.parametrize("method", ["AROW", "PA"])
def test_where_every_row_is_one_slab_the_tables_agree_to_the_bit(method, cap,
                                                                 rng):
    """Rows of at most 64 entries at 128 wide: a sum over one slab is the
    slab's own float, so the slab program leaves, to the bit, what the row
    program leaves of the same arrays with every slab a document; against
    the rows at 128 wide only the order XLA sums a row's entries in
    differs (a reduction over 128 lanes where it was 64)."""
    dim, b = 1 << 14, 90
    state, mask, live = _model(rng, cap, dim, method)
    idx = np.zeros((b, 128), np.int32)
    val = np.zeros((b, 128), np.float32)
    for i in range(b):
        _row(idx, val, i, int(rng.integers(0, 65)), rng, dim)
    _row(idx, val, 0, 64, rng, dim)
    _row(idx, val, 1, 0, rng, dim)
    labels = rng.integers(0, live, size=b).astype(np.int32)
    rows, cut, (sidx, sval, slabels, owner, n, _e) = _in_rows_and_in_slabs(
        state, idx, val, labels, mask, method)
    assert np.array_equal(owner[:n], np.arange(n))
    alone = C.train_batch_parallel(
        _fresh(state), jnp.asarray(sidx), jnp.asarray(sval),
        jnp.asarray(slabels), mask, 1.0, method=method)
    for a, w, r in zip(cut, alone, rows):
        assert a.tobytes() == np.asarray(w).tobytes()
        np.testing.assert_allclose(a, r, rtol=1e-6, atol=1e-6)
    assert np.abs(rows[1] - np.asarray(state.dw)).max() > 0.01


def test_padding_slabs_and_padding_documents_change_nothing(rng):
    """A slab with no entry is a no-op as a padding row is, wherever the
    bucket puts it, and so is a document with no entry among the others:
    twice the padding slabs, and empty documents strewn through the flush,
    leave the same tables to the bit. An entry zeroed in place (what the
    ingest's finite screen leaves of a NaN) at the head of a slab does not
    hide the slab's other entries."""
    from jubatus_tpu.models import classifier as M

    dim, b, k = 1 << 14, 60, 512
    state, mask, live = _model(rng, 32, dim, "AROW")
    idx, val, labels = _uneven_flush(rng, b, k, dim, labels=live)
    _row(idx, val, 2, 200, rng, dim)
    idx[2, 128], val[2, 128] = 0, 0.0       # the head of its third slab
    rows, cut, (sidx, sval, slabels, owner, n, entries) = \
        _in_rows_and_in_slabs(state, idx, val, labels, mask, "AROW")
    assert entries == np.count_nonzero(idx)
    for a, w in zip(cut, rows):
        np.testing.assert_allclose(a, w, rtol=1e-6, atol=1e-6)
    # twice the bucket: the padding slabs are a document of their own, last
    s_b = len(sidx)
    more = C.train_batch_parallel(
        _fresh(state), jnp.asarray(np.pad(sidx, ((0, s_b), (0, 0)))),
        jnp.asarray(np.pad(sval, ((0, s_b), (0, 0)))),
        jnp.asarray(np.pad(slabels, (0, s_b))), mask, 1.0,
        jnp.asarray(np.concatenate([
            np.where(np.arange(s_b) < n, owner, 2 * s_b - 1),
            np.full(s_b, 2 * s_b - 1, np.int32)])), method="AROW")
    for a, w in zip(more, cut):
        assert np.asarray(a).tobytes() == w.tobytes()
    # empty documents among the others (a label each, as the wire gives)
    at = np.sort(rng.choice(b, size=9, replace=False))
    widx, wval = np.insert(idx, at, 0, axis=0), np.insert(val, at, 0, axis=0)
    wlabels = np.insert(labels, at, 1)
    wide = M._cut_slabs(widx, wval, wlabels, 128)
    assert wide[4] == n and np.array_equal(wide[0], sidx) \
        and np.array_equal(wide[2], slabels) and np.array_equal(wide[3], owner)


@pytest.mark.parametrize("mode", ["parallel", "sequential", "sharded"])
def test_the_driver_cuts_uneven_rows_on_one_chip_and_counts_both_forms(mode,
                                                                       rng):
    """A flush of uneven rows through ``_train_slots``. On one chip in
    parallel mode it is cut: the old counters describe the flush as it
    arrives (its rows, the width its requests were packed at, the entries
    at that width) exactly as they did, and five new ones what the device
    is handed. The sequential scan and the mesh take it in rows."""
    from jax.sharding import Mesh

    from jubatus_tpu.models import classifier as M
    from jubatus_tpu.utils import tracing

    dim, b, k = 1 << 14, 200, 1024
    idx, val, _ = _uneven_flush(rng, b, k, dim)
    names = [f"label{i % 20:02d}" for i in range(b)]
    kw = {"parallel": {}, "sequential": {"train_mode": "sequential"},
          "sharded": {"mesh": Mesh(np.asarray(jax.devices()[:4]),
                                   axis_names=("shard",))}}[mode]
    d = M.ClassifierDriver(AROW_CONF, dim_bits=14, **kw)
    d.trace = reg = tracing.Registry()
    assert d.train_hashed(names, idx, val) == b
    c = reg.counters()
    assert (c["step.train.rows"], c["step.train.rows_padded"]) == (b, 256)
    assert c["step.train.width_1024"] == 1
    assert c["step.train.entries"] == np.count_nonzero(idx)
    assert c["step.train.entries_padded"] == b * k
    programs = {p: v for p, v in c.items()
                if p.startswith("step.train.program_")}
    if mode != "parallel":
        assert not any(p.startswith("step.train.slab") for p in c)
        if mode == "sequential":
            assert c["step.train.entries_issued"] == 256 * k
            assert programs == {"step.train.program_scan_256x1024": 1}
        else:
            assert c["step.train.entries_issued"] \
                == c["step.train.shard_entries_issued"]
            assert len(programs) == 1 \
                and next(iter(programs)).startswith("step.train.program_mesh_")
        return
    slabs = int(np.sum(-(-np.count_nonzero(idx, axis=1) // 64)))
    bucket = 1 << (slabs - 1).bit_length()
    assert c["step.train.slab_flushes"] == 1
    assert c["step.train.slabs"] == slabs
    assert c["step.train.slabs_padded"] == bucket
    assert c["step.train.entries_issued"] == bucket * 64
    assert bucket * 64 * M._SLAB_GAIN <= 256 * k
    assert programs == {f"step.train.program_slabs_{bucket}x64": 1}
    assert c["step.train.upload_bytes"] == bucket * (64 * 8 + 8)
    # and the model is the one the rows give
    want = M.ClassifierDriver(AROW_CONF, dim_bits=14,
                              train_mode="parallel")
    gain, M._SLAB_GAIN = M._SLAB_GAIN, float("inf")
    try:
        want.train_hashed(names, idx, val)
    finally:
        M._SLAB_GAIN = gain
    for a, w in zip(d.state, want.state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)
    assert d.classify_hashed(idx[:4], val[:4])[0][0][0] \
        == want.classify_hashed(idx[:4], val[:4])[0][0][0]


def test_diff_and_checkpoint_round_trip_in_the_tables_own_shape(rng):
    """get_diff -> put_diff and pack -> unpack meet [L, D] tables, as
    before: the step addresses them where they lie and reshapes nothing."""
    from jubatus_tpu.models.classifier import ClassifierDriver

    state = _warm_state(rng, CAP, DIM, "AROW", 3)
    diff = C.get_diff(state)
    assert diff["dw"].shape == diff["dprec"].shape == (CAP, DIM)
    eff = np.asarray(state.w + state.dw)
    eff_p = np.asarray(state.prec + state.dprec)
    mixed = C.put_diff(state, diff)
    np.testing.assert_array_equal(np.asarray(mixed.w), eff)
    np.testing.assert_array_equal(np.asarray(mixed.prec), eff_p)
    assert not np.asarray(mixed.dw).any() and not np.asarray(mixed.dprec).any()

    a = ClassifierDriver(AROW_CONF, dim_bits=12)
    b = ClassifierDriver(AROW_CONF, dim_bits=12)
    idx = rng.integers(1, DIM, size=(20, 5)).astype(np.int32)
    val = rng.normal(size=(20, 5)).astype(np.float32)
    a.train_hashed([("x", "y")[i % 2] for i in range(20)], idx, val)
    packed = a.pack()
    assert packed["w"].shape == packed["prec"].shape == (a.capacity, DIM)
    b.unpack(packed)
    assert a.classify_hashed(idx, val) == b.classify_hashed(idx, val)


@pytest.mark.parametrize("dim_bits,plan,width", [
    (10, "packed", 5), (16, "columns", 5), (16, "packed", 1024),
    (18, "columns", 40), (16, "packed", 832)])
def test_a_flush_is_counted_under_the_plan_its_shapes_settled_on(
        dim_bits, plan, width, rng):
    """And its entries beside its rows: those that carry a feature, those
    its rows have at the program's width, the bytes its stage uploaded."""
    from jubatus_tpu.models.classifier import ClassifierDriver
    from jubatus_tpu.utils import tracing

    d = ClassifierDriver(AROW_CONF, dim_bits=dim_bits)
    d.trace = reg = tracing.Registry()
    idx = rng.integers(1, 1 << dim_bits, size=(20, width)).astype(np.int32)
    val = rng.normal(size=(20, width)).astype(np.float32)
    idx[:, width - width // 4:] = 0         # a quarter of the width is padding
    val[:, width - width // 4:] = 0.0
    for _ in range(2):
        d.train_hashed([("x", "y")[i % 2] for i in range(20)], idx, val)
    counters = reg.counters()
    plans = {k: v for k, v in counters.items()
             if k.startswith(("step.train.plan_", "step.train.width_"))}
    assert plans == {"step.train.plan_" + plan: 2,
                     f"step.train.width_{width}": 2}
    assert counters["step.train.entries"] == 2 * 20 * (width - width // 4)
    assert counters["step.train.entries_padded"] == 2 * 20 * width
    # 20 rows run in the 32-row program: index, value and label arrays;
    # at 1,024 wide their 240 slabs of 64 entries in the 256-slab program
    # (half the entries the rows issue in their bucket of 32), each slab
    # with its label and its document's number
    if width == 1024:
        assert counters["step.train.program_slabs_256x64"] == 2
        assert counters["step.train.upload_bytes"] == 2 * 256 * (64 * 8 + 8)
    else:
        assert counters[f"step.train.program_rows_32x{width}"] == 2
        assert counters["step.train.upload_bytes"] \
            == 2 * 32 * (width * 8 + 4)


# -- the compiled programs, for the chip that is described and not attached --
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the absent compiler raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,plan", [(256, "columns"), (8192, "packed")])
def test_no_program_relayouts_the_tables(rows, plan, one_chip):
    """The v5e's compiler, off the chip, at D = 2^22: the step scatters into
    the tables where they lie (no ``while`` copying a table into the
    scatter's layout and back, nothing table-sized but the in-place
    scatters) and the column plan makes no table-sized temporary at all.
    The relayout came from a one-line indexing choice and can come back
    the same way."""
    import re

    dim, k = 1 << 22, 64
    table = CAP * dim * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = C.ClassifierState(*[sds((CAP, dim), jnp.float32)] * 4)
    idx, val = sds((rows, k), jnp.int32), sds((rows, k), jnp.float32)
    labels, mask = sds((rows,), jnp.int32), sds((CAP,), jnp.bool_)
    assert C.gather_plan(CAP, dim, rows * k) == plan
    train = C.train_batch_parallel.lower(
        state, idx, val, labels, mask, 1.0, method="AROW").compile()
    scores = C.scores.lower(state, idx, val, mask).compile()
    for name, prog, pairs in (("train", train, 2), ("scores", scores, 1)):
        text = prog.as_text()
        entry = text[text.index("ENTRY"):]
        assert " while(" not in entry, name
        relaid = [m[0] for m in re.finditer(r"= f32\[([\d,]+)\]\S* copy\(", entry)
                  if np.prod([int(d) for d in m[1].split(",")]) >= CAP * dim]
        assert not relaid, (name, relaid)
        temp = prog.memory_analysis().temp_size_in_bytes
        if plan == "columns":
            assert temp < table // 4, (name, temp)
        else:       # the packed copy, made in one pass, and nothing more
            assert temp < pairs * table * 1.05, (name, temp)
    # the four scatters run in place on the donated diffs
    assert train.memory_analysis().alias_size_in_bytes == 4 * table


@pytest.mark.parametrize("k,entries", [(1024, 8.39e6), (832, 6.82e6)])
def test_the_wide_programs_fit_the_chip(k, entries, one_chip):
    """A combination configuration's flush at the benchmark's size (D =
    2^25, 8,192 rows; PERF.md section 4) at the width its 780 features ride
    at (832) and at the power of two they rode at (1,024): the train and
    scores programs compile for the v5e, take the packed plan, scatter in
    place, and leave most of the chip's 16e9 B free. Their temporaries are
    the packed copy ([16, D] and [8, D]) and the gathered entries."""
    dim = 1 << 25
    table = CAP * dim * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = C.ClassifierState(*[sds((CAP, dim), jnp.float32)] * 4)
    mask = sds((CAP,), jnp.bool_)
    temps = {}
    for name, rows in (("train", 8192), ("train_512", 512), ("scores", 512)):
        idx, val = sds((rows, k), jnp.int32), sds((rows, k), jnp.float32)
        assert C.gather_plan(CAP, dim, rows * k) == "packed"
        if name == "scores":
            prog = C.scores.lower(state, idx, val, mask).compile()
        else:
            prog = C.train_batch_parallel.lower(
                state, idx, val, sds((rows,), jnp.int32), mask, 1.0,
                method="AROW").compile()
            assert prog.memory_analysis().alias_size_in_bytes == 4 * table
        m = prog.memory_analysis()
        temps[name] = m.temp_size_in_bytes
        assert m.temp_size_in_bytes + m.argument_size_in_bytes < 8e9, name
    # the packed copy and what the gathered entries (8.39M at 1,024, 6.82M
    # at 832; a sixteenth of that at 512 rows) take beside it: 16 sublanes
    # of f32 an entry
    assert 2 * table <= temps["train_512"] < 2 * table * 1.05, temps
    assert 2 * table <= temps["train"] < 2 * table + entries * 64 * 1.05, temps
    assert table <= temps["scores"] < table * 1.05, temps


@pytest.mark.parametrize("rows", [
    512, pytest.param(8192, marks=pytest.mark.slow)])
def test_the_step_at_32_label_rows_fits_the_chip(rows, one_chip):
    """The text deployment's programs at the benchmark's size (news20_arow:
    D = 2^23, label capacity 32; PERF.md section 4): the train step at the
    width of 1,024 (a lone 500-document call here; the window's flush of
    8,000 in 8,192 x 1,024 takes half a minute to compile and is held by
    the slow case) and the scores of the quality plane's 8 rows at a call's
    width. Left to itself XLA copies every [32, D] table into a
    column-major layout first, 128 lanes for 32 labels: 20 GB, which the
    v5e's compiler refuses at every shape. Addressed a sublane group at a
    time the tables stay where they lie: the packed plan's temporaries are
    the packed copy and the gathered entries ([4 x D, 16] and [4 x rows x
    1,024, 16]), the column plan makes nothing table-sized, and the
    scatters run in place."""
    cap, dim, k = 32, 1 << 23, 1024
    table = cap * dim * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = C.ClassifierState(*[sds((cap, dim), jnp.float32)] * 4)
    mask = sds((cap,), jnp.bool_)
    idx, val = sds((16, k), jnp.int32), sds((16, k), jnp.float32)
    assert C.gather_plan(cap, dim, 16 * k) == "columns"
    scores = C.scores.lower(state, idx, val, mask).compile()
    assert scores.memory_analysis().temp_size_in_bytes < table // 64
    idx, val = sds((rows, k), jnp.int32), sds((rows, k), jnp.float32)
    assert C.gather_plan(cap, dim, rows * k) == "packed"
    train = C.train_batch_parallel.lower(
        state, idx, val, sds((rows,), jnp.int32), mask, 1.0,
        method="AROW").compile()
    m = train.memory_analysis()
    assert m.alias_size_in_bytes == 4 * table
    # half the tables' bytes for the packed copy, and 16 sublanes of f32
    # for each of the 4 x rows x 1,024 gathered entries beside it
    assert 2 * table <= m.temp_size_in_bytes \
        < 2 * table + 4 * rows * k * 64 * 1.1
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 9e9


@pytest.mark.parametrize("slabs", [1024, 16384])
def test_the_slab_step_at_32_label_rows_fits_the_chip(slabs, one_chip):
    """The text deployment's train programs since PR 35 (news20_arow: D =
    2^23, label capacity 32): a lone 500-document call's 1,024 slabs of 64
    entries and the window's flush of 8,000 documents in 16,384. The
    v5e's compiler takes the packed plan, scatters in place, and the
    temporaries are the packed copy and the gathered entries, an eighth
    of what the rows at 8,192 x 1,024 gather; the sums over a document's
    slabs make nothing table-sized."""
    cap, dim, w = 32, 1 << 23, 64
    table = cap * dim * 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = C.ClassifierState(*[sds((cap, dim), jnp.float32)] * 4)
    assert C.gather_plan(cap, dim, slabs * w) == "packed"
    train = C.train_batch_parallel.lower(
        state, sds((slabs, w), jnp.int32), sds((slabs, w), jnp.float32),
        sds((slabs,), jnp.int32), sds((cap,), jnp.bool_), 1.0,
        sds((slabs,), jnp.int32), method="AROW").compile()
    m = train.memory_analysis()
    assert m.alias_size_in_bytes == 4 * table
    assert 2 * table <= m.temp_size_in_bytes \
        < 2 * table + 4 * slabs * w * 64 * 1.2
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 7e9
