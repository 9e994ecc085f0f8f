"""The train step's two forms of reading the precision (ops/classifier.py
``prec_rows``): beside the weights at every label row addressed, in the
same descriptors, up to four label groups; past them at the two rows an
update touches, the correct label's and the rival's, once the scores have
named the rival, with the scores gathered an entry's whole row at a time
out of an entry-major copy of the weights (``_gather_scores``).

The two forms read the same precision bits (the same two floats added) and
decide the same updates; the scores of the two-row form are summed in
another order (``_gather_scores``' reduction against the einsum), so the
weights they leave agree to float32 rounding, not to the bit: a margin is
a difference of two sums of up to K products, and the update divides by
it, so an error of a few ulps of the scores moves the weights by about
1e-6 of their size. That is the tolerance below (rtol 2e-5, atol 2e-6),
the same order as the mesh's parity tests (3e-5). The precision diffs
agree to the bit where the decisions alone set them (AROW, NHERD: x^2
over r, and v, a sum of the same precision bits) and to the same rounding
where the step size does (CW's 2 alpha phi x^2)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from jubatus_tpu.ops import classifier as C
from jubatus_tpu.parallel import sharded_model as sm


@pytest.mark.parametrize("method,cap,dim,rows,want", [
    ("AROW", 256, 1 << 20, 240, 2),      # wili2018_arow: 30 groups
    ("AROW", 256, 1 << 20, 40, 2),       # 5 groups
    ("AROW", 32, 1 << 23, 32, 32),       # 4 groups: beside the weights
    ("AROW", 32, 1 << 23, 24, 24),       # news20_arow: 3 groups
    ("AROW", 8, 1 << 25, 8, 8),          # the Criteo cells: 1 group
    ("CW", 64, 1 << 12, 64, 2),
    ("NHERD", 64, 1 << 12, 48, 2),
    ("AROW", 48, 200, 48, 48),           # no whole tiles: one group
    ("PA", 256, 1 << 20, 240, 0),        # no precision at all
    ("perceptron", 8, 1 << 12, 8, 0)])
def test_prec_rows_and_label_groups(method, cap, dim, rows, want):
    assert C.prec_rows(method, cap, dim, rows) == want
    groups = C.label_groups(cap, dim, rows)
    assert groups == (rows // 8 if dim % 128 == 0 and cap % 8 == 0 else 1)
    assert (C.prec_rows("AROW", cap, dim, rows) == 2) \
        == (groups > C._PREC_FULL_UPTO)


@pytest.fixture
def forms():
    """Run a step in either form, whatever its label groups: the rule's
    bound moved around each call, traces dropped on either side so that
    no program traced in a forced form outlives the call."""
    def run(two, fn, *args, **kw):
        bound = C._PREC_FULL_UPTO
        jax.clear_caches()
        C._PREC_FULL_UPTO = -1 if two else 10 ** 6
        try:
            return fn(*args, **kw)
        finally:
            C._PREC_FULL_UPTO = bound
            jax.clear_caches()
    return run


def _state(rng, cap, dim):
    """A model that has learned something on every row: masters and diffs
    of every sign, precisions above their prior."""
    w, dw = (jnp.asarray(rng.normal(size=(cap, dim)) / 4, jnp.float32)
             for _ in range(2))
    prec = jnp.asarray(1.0 + np.abs(rng.normal(size=(cap, dim))), jnp.float32)
    dprec = jnp.asarray(np.abs(rng.normal(size=(cap, dim))) / 4, jnp.float32)
    return C.ClassifierState(w, dw, prec, dprec)


def _fresh(state):
    return C.ClassifierState(*(jnp.array(a) for a in state[:4]))


def _flush(rng, b, k, dim, live, slabs):
    """Rows of up to k entries, padding behind them, labels among the live
    slots; as slabs, pieces of 9 documents, each with its document's
    label."""
    idx = rng.integers(1, dim, size=(b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    cut = rng.integers(k // 2, k + 1, size=b)
    pad = np.arange(k)[None, :] >= cut[:, None]
    idx[pad], val[pad] = 0, 0.0
    labels = rng.choice(live, size=b)
    owner = None
    if slabs:
        owner = np.sort(rng.integers(0, 9, size=b)).astype(np.int32)
        labels = labels[owner]
        owner = jnp.asarray(owner)
    return (jnp.asarray(idx), jnp.asarray(val),
            jnp.asarray(labels, jnp.int32), owner)


def _agree(full, two, method="AROW"):
    for name, a, c in zip(("w", "dw", "prec", "dprec"), full[:4], two[:4]):
        a, c = np.asarray(a), np.asarray(c)
        if name == "prec" or (name == "dprec" and method != "CW"):
            assert a.tobytes() == c.tobytes(), name
        else:
            np.testing.assert_allclose(c, a, rtol=2e-5, atol=2e-6,
                                       err_msg=name)


#: label rows addressed -> (capacity, columns): one table of 4 rows that
#: lies in no whole tile, one group, four groups, thirty
SHAPES = {4: (4, 256), 8: (8, 1 << 10), 32: (32, 1 << 10), 240: (256, 1 << 10)}


#: AROW at every shape in both forms of a flush; the other two methods
#: with a precision at thirty groups, in rows
CASES = [(rows, slabs, "AROW") for rows in sorted(SHAPES)
         for slabs in (False, True)] + [(240, False, "CW"),
                                       (240, False, "NHERD")]


@pytest.mark.parametrize("plan", ["columns", "packed"])
@pytest.mark.parametrize("rows,slabs,method", CASES)
def test_the_two_row_read_is_the_full_gather_to_float32_rounding(
        rows, slabs, method, plan, forms, rng, monkeypatch):
    """Two flushes in a row, the second against the first's diffs, each
    form from the same state; every label live up to ``rows`` but two
    slots left free, so that dead rows are addressed too."""
    cap, dim = SHAPES[rows]
    live = np.setdiff1d(np.arange(rows), [1, rows - 2])
    mask = jnp.asarray(np.isin(np.arange(rows), live))
    monkeypatch.setattr(C, "gather_plan", lambda *a: plan)
    full = two = _state(rng, cap, dim)
    for _ in range(2):
        idx, val, labels, owner = _flush(rng, 40, 24, dim, live, slabs)
        args = (idx, val, labels, mask, 0.5 if method == "CW" else 1.0,
                owner)
        a = forms(False, C.train_batch_parallel, _fresh(full), *args,
                  method=method, token=True)
        c = forms(True, C.train_batch_parallel, _fresh(two), *args,
                  method=method, token=True)
        assert int(a.updated) == int(c.updated) > 0
        _agree(a, c, method)
        full, two = a, c


def test_the_mesh_reads_its_own_entries_precision(forms, rng):
    """Two shards of 512 columns at 240 label rows: each shard reads the
    precision of its own entries at the two rows; the mesh's tables agree
    with its full gather to the same rounding, and with one chip's."""
    cap, dim, rows, n = 256, 1 << 10, 240, 2
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("shard",))
    live = np.arange(235)
    mask = jnp.asarray(np.arange(rows) < 235)
    state = _state(rng, cap, dim)
    idx, val, labels, _ = _flush(rng, 48, 32, dim, live, False)
    ridx, rval, _ = sm.route_rows(np.asarray(idx), np.asarray(val), n,
                                  dim // n, 0)
    ridx, rval = jax.device_put((ridx, rval), sm.flush_sharding(mesh))
    out = {}
    for two in (False, True):
        placed = sm.place_state(mesh, _fresh(state), dim)
        out[two] = forms(two, sm.train_batch, mesh, placed, ridx, rval,
                         labels, mask, 1.0, method="AROW", token=True)
    assert int(out[False].updated) == int(out[True].updated) > 0
    _agree(out[False], out[True])
    one = forms(True, C.train_batch_parallel, _fresh(state), idx, val,
                labels, mask, 1.0, method="AROW", token=True)
    for a, c in zip(one[:4], out[True][:4]):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=3e-5,
                                   atol=3e-5)


@pytest.mark.parametrize("plan", ["columns", "packed"])
@pytest.mark.parametrize("cap,rows,block", [
    (256, 240, None),        # wili2018_arow: 30 groups, two rows of lanes
    (64, 40, None),          # 5 groups: one row of lanes, 88 of them zeros
    (256, 240, 4 * 9)])      # two blocks of 4 rows of 9 entries
def test_the_scores_are_the_group_gathers(cap, rows, block, plan, rng,
                                          monkeypatch):
    """``_gather_scores`` is ``(master + diff)[:rows, idx] . val``, on
    either plan, row for row, and in blocks of rows where a flush is
    gathered a block at a time."""
    dim = 1 << 10
    m, d = (jnp.asarray(rng.normal(size=(cap, dim)), jnp.float32)
            for _ in range(2))
    idx = jnp.asarray(rng.integers(0, dim, size=(8, 9)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(8, 9)), jnp.float32)
    want = np.einsum("lbk,bk->bl", (np.asarray(m) + np.asarray(d))[
        :rows, np.asarray(idx)], np.asarray(val))
    monkeypatch.setattr(C, "gather_plan", lambda *a: plan)
    if block is not None:
        monkeypatch.setattr(C, "_SCORE_BLOCK", block)
    # the plan and the block are read as the function is traced, and jax
    # keeps a trace by the function and its shapes: drop the traces on
    # either side, so that this call traces under its own plan and block
    jax.clear_caches()
    try:
        got = jax.jit(C._gather_scores, static_argnums=3)(
            (m, d), idx, val, rows)
    finally:
        jax.clear_caches()
    assert got.shape == (8, rows)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_a_block_edge_inside_a_document_changes_no_update(forms, rng,
                                                          monkeypatch):
    """A flush of slabs scored in blocks whose edges fall inside
    documents: each slab's scores are its own, and the sum over a
    document's slabs comes after the blocks, so the step in blocks of 8
    slabs leaves the tables the full gather leaves, to the same rounding
    as the step in one block."""
    cap, dim, rows = 256, 1 << 10, 240
    live = np.setdiff1d(np.arange(rows), [1, rows - 2])
    mask = jnp.asarray(np.isin(np.arange(rows), live))
    state = _state(rng, cap, dim)
    idx, val, labels, owner = _flush(rng, 40, 24, dim, live, True)
    edges = np.flatnonzero(np.diff(np.asarray(owner)) == 0) + 1
    assert np.any(edges % 8 == 0)            # some document spans an edge
    args = (idx, val, labels, mask, 1.0, owner)
    full = forms(False, C.train_batch_parallel, _fresh(state), *args,
                 method="AROW", token=True)
    one = forms(True, C.train_batch_parallel, _fresh(state), *args,
                method="AROW", token=True)
    monkeypatch.setattr(C, "_SCORE_BLOCK", 8 * 24)
    blocked = forms(True, C.train_batch_parallel, _fresh(state), *args,
                    method="AROW", token=True)
    assert int(full.updated) == int(blocked.updated) > 0
    _agree(full, blocked)
    for a, c in zip(one, blocked):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("cap,dim,rows", [
    (256, 1 << 12, 240),     # two rows of lanes, every one a table row
    (256, 1 << 11, 200),     # the tables' dead rows past the rows asked
    (64, 1 << 11, 40),       # one row of lanes, 64 of them zeros
    (48, 384, 40)])          # columns in blocks of 128
def test_the_chips_copy_is_the_transposed_sum(cap, dim, rows, rng):
    """``_entry_major``'s Pallas pass (the TPU's branch, run here by
    Pallas's interpreter) makes what the plain branch makes, bit for
    bit: master + diff, label rows along the lanes, zeros past the
    tables' rows."""
    from jax.experimental.pallas import tpu as pltpu

    m, d = (jnp.asarray(rng.normal(size=(cap, dim)), jnp.float32)
            for _ in range(2))
    wide = -(-rows // 128) * 128
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(C._entry_major_tpu(m, d, wide))
    plain = np.asarray(jax.jit(C._entry_major, static_argnums=1)(
        (m, d), rows))
    want = np.zeros((dim, wide), np.float32)
    top = min(cap, wide)
    want[:, :top] = (np.asarray(m) + np.asarray(d))[:top].T
    assert got.tobytes() == want.tobytes() == plain.tobytes()


@pytest.mark.parametrize("method,cap,dim,rows,n_idx,want", [
    ("AROW", 256, 1 << 20, 240, 1 << 21, 2),     # wili2018_arow, entry-major
    ("AROW", 256, 1 << 20, 40, 1 << 21, 1),      # 5 groups in one row of lanes
    ("AROW", 256, 1 << 20, 240, 64, 30),         # a lone slab: the column plan
    ("AROW", 32, 1 << 23, 24, 1 << 20, 3),       # news20_arow: a group at a time
    ("AROW", 8, 1 << 25, 8, 1 << 18, 1),         # the Criteo cells
    ("PA", 256, 1 << 20, 240, 1 << 21, 30)])     # no precision: the group form
def test_weight_fetches(method, cap, dim, rows, n_idx, want):
    assert C.weight_fetches(method, cap, dim, rows, n_idx) == want


def test_a_flush_counts_its_label_groups_and_precision_cells(rng):
    """``step.train.label_groups_issued``, ``.weight_fetches`` and
    ``.prec_gathered`` a flush: twenty labels address 3 groups, fetch the
    weights of each entry a group at a time and read the precision of 24
    rows at every entry; 235 labels address 30, fetch each entry's
    weights as 2 rows of lanes and read the precision at 2."""
    from jubatus_tpu.models.classifier import ClassifierDriver
    from jubatus_tpu.utils import tracing

    conf = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
            "converter": {"string_rules": [], "num_rules": []}}
    for n_labels, groups, fetches, prec in ((2, 1, 1, 8), (20, 3, 3, 24),
                                            (235, 30, 2, 2)):
        d = ClassifierDriver(conf, dim_bits=10)
        d.trace = reg = tracing.Registry()
        idx = rng.integers(1, 1 << 10, size=(470, 16)).astype(np.int32)
        val = rng.normal(size=(470, 16)).astype(np.float32)
        d.train_hashed([f"l{i % n_labels:03d}" for i in range(470)], idx,
                       val)
        c = reg.counters()
        assert c["step.train.label_groups_issued"] == groups
        assert c["step.train.weight_fetches"] \
            == fetches * c["step.train.entries_issued"]
        assert c["step.train.prec_gathered"] \
            == prec * c["step.train.entries_issued"]
        assert len(d.classify_hashed(idx[:2], val[:2])[0]) == n_labels


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


def _lower_slab_step(one_chip, slabs, cap=256, dim=1 << 20, rows=240):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = C.ClassifierState(*[sds((cap, dim), jnp.float32)] * 4)
    return C.train_batch_parallel.lower(
        state, sds((slabs, 64), jnp.int32), sds((slabs, 64), jnp.float32),
        sds((slabs,), jnp.int32), sds((rows,), jnp.bool_), 1.0,
        sds((slabs,), jnp.int32), method="AROW", token=True)


@pytest.fixture(scope="module")
def compiled(one_chip):
    """The v5e's compile of a slab step, once a shape in this module."""
    done = {}

    def get(slabs, **shape):
        key = (slabs,) + tuple(sorted(shape.items()))
        if key not in done:
            done[key] = _lower_slab_step(one_chip, slabs, **shape).compile()
        return done[key]
    return get


#: W's three programs: the lone calls' 2,048 slabs and the two buckets
#: its flushes of 8,000 paragraphs reach
W_SLABS = [1 << 11, 1 << 15, 1 << 16]


@pytest.mark.parametrize("slabs", W_SLABS)
def test_the_step_at_240_label_rows_fits_the_chip(slabs, compiled):
    """wili2018_arow's train program (D = 2^20, 256 label rows, 240
    addressed) at each slab bucket it runs: the v5e's compiler takes it
    with the four tables donated and scattered in place, and its
    temporaries are the entry-major copy of the weights (one table's
    bytes: 240 rows padded to 256 lanes) and one block's gathered rows,
    under two tables, whatever the entries."""
    cap, dim = 256, 1 << 20
    table = cap * dim * 4
    assert C.prec_rows("AROW", cap, dim, 240) == 2
    m = compiled(slabs).memory_analysis()
    assert m.alias_size_in_bytes == 4 * table
    assert table < m.temp_size_in_bytes < 2 * table
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 7e9


def _gathers(text):
    """(result, operand's shape and layout, slice sizes) of each gather
    in an optimized HLO module's text."""
    shapes = dict(re.findall(r"%([\w.\-]+) = (\S+) ", text))
    return [(out, shapes[arg], sizes) for out, arg, sizes in re.findall(
        r"= (\S+) gather\(%([\w.\-]+),.*?slice_sizes=\{([\d,]+)\}",
        text)]


@pytest.mark.parametrize("slabs", W_SLABS)
def test_the_scores_gather_whole_rows_of_lanes(slabs, compiled):
    """The scores of W's step gather each entry's weights as one row of
    a [D, 256] copy laid out lanes-minor, the 240 label rows along the
    lanes (two runs of 512 B an entry), out of the Pallas pass that adds
    and transposes the tables; no gather of an 8-row label group is
    left. A copy XLA lays out itself is ``{0,1}``, the tables' own
    bytes, and a row of it lies across 30 tiles."""
    text = compiled(slabs).as_text()
    gathers = _gathers(text)
    rows = [(operand, out) for out, operand, sizes in gathers
            if sizes == "1,256"]
    assert rows and all(
        operand.startswith("f32[1048576,256]{1,0:") for operand, _ in rows)
    assert not [g for g in gathers
                if g[2] == "1,8" or re.match(r"f32\[\d+,8\]", g[1])]
    assert 'custom_call_target="tpu_custom_call"' in text


def test_the_step_at_40_label_rows_fits_the_chip(compiled):
    """Five label groups, where the lanes hold the most padding (40 rows
    in 128), at a news20-sized table (64 rows of 2^23 columns) and 16,384
    slabs: the v5e's compiler takes the entry-major step, its copy
    [2^23, 128] beside the four tables within the chip's 15.75 GiB."""
    cap, dim = 64, 1 << 23
    assert C.weight_fetches("AROW", cap, dim, 40, (1 << 14) * 64) == 1
    prog = compiled(1 << 14, cap=cap, dim=dim, rows=40)
    m = prog.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 15.75 * 2 ** 30
    assert any(operand.startswith("f32[8388608,128]{1,0:")
               for _, operand, sizes in _gathers(prog.as_text())
               if sizes == "1,128")


@pytest.mark.parametrize("cap,dim,rows", [(512, 1 << 20, 504),
                                           (1024, 1 << 18, 1000)])
def test_the_copy_keeps_to_its_vmem_past_256_label_rows(cap, dim, rows,
                                                         compiled):
    """Past 256 label rows the Pallas pass that makes the entry-major
    copy takes fewer columns a grid step, so that a step's blocks stay
    within the v5e's 16 MiB of scoped VMEM (at 1,024 rows, blocks of
    1,024 columns asked 24 MiB): the compiler takes the step, and its
    scores gather rows of a [D, rows padded to 128 lanes] copy."""
    wide = -(-rows // 128) * 128
    prog = compiled(1 << 12, cap=cap, dim=dim, rows=rows)
    m = prog.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 15.75 * 2 ** 30
    assert any(operand.startswith(f"f32[{dim},{wide}]{{1,0:")
               for _, operand, sizes in _gathers(prog.as_text())
               if sizes == f"1,{wide}")


def test_the_full_gather_at_240_label_rows_does_not_fit(one_chip):
    """The same program with the precision gathered beside the weights at
    every row (the form up to four label groups, and the parent's at
    any): the v5e's compiler refuses it at 32,768 slabs."""
    bound = C._PREC_FULL_UPTO
    jax.clear_caches()
    C._PREC_FULL_UPTO = 10 ** 6
    try:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            _lower_slab_step(one_chip, 1 << 15).compile()
    finally:
        C._PREC_FULL_UPTO = bound
        jax.clear_caches()
