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
    state = _warm_state(rng, CAP, dim, method, min(live, CAP))
    if scenario == "grown_16":
        cap = 16
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
             "uniform_rows"]


@pytest.mark.parametrize("method,scenario,dim,plan,width", [
    (m, s) + p for p in PLANS for s in SCENARIOS
    # at the wide row: one method with a precision table and one without
    for m in (C.METHODS if p[2] is None else ("PA", "AROW"))])
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
    # 20 rows run in the 32-row program: index, value and label arrays
    assert counters["step.train.upload_bytes"] == 2 * 32 * (width * 8 + 4)


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
