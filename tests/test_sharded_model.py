"""Feature-sharded linear model tests (parallel/sharded_model.py,
ISSUE 13 tentpole): shard_map'd train/classify must match the
single-device kernels to f32 rounding across shard counts, the drivers
must route through the sharded path transparently, and the per-shard
diff chunks must fold/apply without ever materializing the matrix.
ISSUE 32: a train flush is routed by column range on the host
(``route_rows``) and each shard is handed its own entries alone."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.core.sparse import _width_bucket
from jubatus_tpu.ops import classifier as cops
from jubatus_tpu.ops import regression as rops
from jubatus_tpu.parallel import sharded_model as sm

D, L, B, K = 512, 4, 48, 8
SHARD_COUNTS = (2, 4, 8)   # >= 3 shard counts per the acceptance criteria


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("shard",))


def _batch(rng, b=B, k=K, dim=D):
    # column 0 is padding: no feature hashes there
    idx = rng.integers(1, dim, (b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    labels = rng.integers(0, 3, b).astype(np.int32)
    mask = np.zeros(L, bool)
    mask[:3] = True
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels),
            jnp.asarray(mask))


def _routed(mesh, idx, val, dim=D, min_width=0):
    """A flush as the driver's stage hands it to the mesh step: routed
    by column range on the host, plane n on device n."""
    n = mesh.shape["shard"]
    ridx, rval, _ = sm.route_rows(np.asarray(idx), np.asarray(val), n,
                                  dim // n, min_width)
    return jax.device_put((ridx, rval), sm.flush_sharding(mesh))


def _parsed_rows(rng, b, k, dim, fill):
    """Rows as the native parser leaves them: ``fill[i]`` distinct
    columns of row i sorted ascending, padding (column 0 / value 0)
    behind them."""
    idx = np.zeros((b, k), np.int32)
    val = np.zeros((b, k), np.float32)
    for i, f in enumerate(fill):
        idx[i, :f] = np.sort(rng.choice(np.arange(1, dim), f, replace=False))
        val[i, :f] = rng.normal(size=f)
    return idx, val


def _assert_routed(idx, val, n, d_local, ridx, rval, owned):
    """Every entry of [B, K] lands in exactly one shard's [Ks, B] plane,
    at its local column, with its value and in its row's order; the rest
    of every plane is column 0 / value 0; ``owned`` counts them by
    shard."""
    b = idx.shape[0]
    assert ridx.shape == rval.shape
    assert (ridx.shape[0], ridx.shape[2]) == (n, b)
    assert ridx.dtype == np.int32 and rval.dtype == np.float32
    assert ridx.flags.c_contiguous and rval.flags.c_contiguous
    landed = 0
    for s in range(n):
        mine = (idx != 0) & (idx // d_local == s)
        assert owned[s] == mine.sum()
        for i in range(b):
            m = int(mine[i].sum())
            assert ridx[s, :m, i].tolist() \
                == (idx[i][mine[i]] - s * d_local).tolist(), (s, i)
            assert rval[s, :m, i].tolist() == val[i][mine[i]].tolist()
            assert not ridx[s, m:, i].any() and not rval[s, m:, i].any()
            landed += m
    assert landed == np.count_nonzero(idx)
    ks = ridx.shape[1]
    fullest = max(int(((idx != 0) & (idx // d_local == s)).sum(axis=1).max())
                  for s in range(n))
    assert ks >= fullest and ks == _width_bucket(ks)     # a ladder rung
    return ks, fullest


def _router(how, monkeypatch):
    """``route_rows`` as a server calls it (the native library's passes,
    where it is built) or with numpy's, which serve without the library."""
    from jubatus_tpu.native import ingest

    if how == "numpy":
        monkeypatch.setattr(ingest, "available", lambda: False)
    elif not ingest.available():
        pytest.skip("native toolchain unavailable")
    return sm.route_rows


ROUTERS = pytest.mark.parametrize("how", ("native", "numpy"))


@ROUTERS
@pytest.mark.parametrize("n_shards", (2, 4))
@pytest.mark.parametrize("rows", ("parsed", "as_drawn"))
def test_route_rows_lands_every_entry_once(rows, n_shards, how, rng,
                                            monkeypatch):
    """The routing alone, on a seeded batch: the parser's sorted rows and
    rows in any other order land the same entries, in their row's order,
    and both implementations the same planes."""
    route = _router(how, monkeypatch)
    dim, b, k = 1 << 12, 64, 40
    fill = rng.integers(0, 40, b)
    idx, val = _parsed_rows(rng, b, k, dim, fill)
    if rows == "as_drawn":
        for i in range(b):          # shuffle each row, padding and all
            perm = rng.permutation(k)
            idx[i], val[i] = idx[i][perm], val[i][perm]
    ridx, rval, owned = route(idx, val, n_shards, dim // n_shards)
    ks, fullest = _assert_routed(idx, val, n_shards, dim // n_shards,
                                 ridx, rval, owned)
    assert ks == _width_bucket(fullest)
    # a floor under the width holds, and changes nothing else
    wide = route(idx, val, n_shards, dim // n_shards, ks + 8)
    assert wide[0].shape[1] == ks + 8
    np.testing.assert_array_equal(wide[0][:, :ks], ridx)
    np.testing.assert_array_equal(wide[1][:, :ks], rval)
    assert not wide[0][:, ks:].any() and not wide[1][:, ks:].any()
    # a column past the last range, or under the first, is refused
    for bad in (dim, -3):
        off = idx.copy()
        off[3, 0] = bad
        with pytest.raises(ValueError, match="outside"):
            route(off, val, n_shards, dim // n_shards)


def _edge_rows(case, dim, n):
    d_local = dim // n
    k = 40
    idx = np.zeros((4, k), np.int32)
    if case == "one_shard_holds_a_whole_row":
        idx[0, :39] = 2 * d_local + 1 + np.arange(39)   # all 39 in shard 2
        idx[1, :3] = (5, d_local + 5, 3 * d_local + 5)
    elif case == "a_padding_row":
        idx[0, :4] = (7, d_local + 1, d_local + 2, 3 * d_local + 9)
        idx[2, :2] = (1, dim - 1)   # rows 1 and 3 carry nothing
    elif case == "first_and_last_cell_of_each_range":
        cells = [c for s in range(n)
                 for c in (s * d_local, (s + 1) * d_local - 1)][1:]
        idx[0, :len(cells)] = cells         # column 0 itself is padding
        idx[1, :2] = (d_local - 1, d_local)
    else:
        assert case == "nothing_at_all"
    val = np.where(idx != 0, idx.astype(np.float32) / dim + 1.0,
                   np.float32(0.0))
    return idx, val


@ROUTERS
@pytest.mark.parametrize("case", (
    "one_shard_holds_a_whole_row", "a_padding_row",
    "first_and_last_cell_of_each_range", "nothing_at_all"))
def test_route_rows_edges(case, how, monkeypatch):
    dim, n = 1 << 10, 4
    idx, val = _edge_rows(case, dim, n)
    ridx, rval, owned = _router(how, monkeypatch)(idx, val, n, dim // n)
    ks, fullest = _assert_routed(idx, val, n, dim // n, ridx, rval, owned)
    assert ks == _width_bucket(fullest)
    if case == "one_shard_holds_a_whole_row":
        assert ks == 40 and owned.tolist() == [1, 1, 39, 1]
    elif case == "first_and_last_cell_of_each_range":
        # a range's first cell is local column 0 of its owner, its last
        # the owner's last: neither falls to the neighbour
        assert ridx[1, :2, 0].tolist() == [0, dim // n - 1]
        assert ridx[0, 0, 1] == dim // n - 1 and ridx[1, 0, 1] == 0
        assert rval[1, 0, 0] == val[0, 1] != 0
    elif case == "nothing_at_all":
        assert ks == 8 and owned.tolist() == [0, 0, 0, 0]


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
# all seven: the mesh runs the body they share (ops.train_rows)
@pytest.mark.parametrize("method", cops.METHODS)
# 40: a rung of the width ladder that is no power of two (39 features)
@pytest.mark.parametrize("k", (K, 40))
def test_train_and_scores_parity(method, n_shards, k, rng):
    conf = method in cops.CONFIDENCE_METHODS
    mesh = _mesh(n_shards)
    idx, val, labels, mask = _batch(rng, k=k)
    ref = cops.train_batch(cops.init_state(L, D, conf), idx, val, labels,
                           mask, 1.0, method=method)
    st = sm.place_state(mesh, cops.init_state(L, D, conf), D)
    # two consecutive batches: the second trains against the first's
    # diffs, so divergence would compound — parity must hold after both
    idx2, val2, labels2, _ = _batch(rng, k=k)
    ref = cops.train_batch(ref, idx2, val2, labels2, mask, 1.0,
                           method=method)
    st = sm.train_batch(mesh, st, *_routed(mesh, idx, val), labels, mask,
                        1.0, method=method)
    st = sm.train_batch(mesh, st, *_routed(mesh, idx2, val2), labels2, mask,
                        1.0, method=method)
    for name, (a, b) in zip(("w", "dw", "prec", "dprec"), zip(ref, st)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5, err_msg=name)
    qi, qv, _, _ = _batch(rng, k=k)
    np.testing.assert_allclose(
        np.asarray(sm.scores(mesh, st, qi, qv, mask)),
        np.asarray(cops.scores(ref, qi, qv, mask)),
        rtol=3e-5, atol=3e-5)


# AROW keeps a confidence table, PA does not: the two shapes of the rule
@pytest.mark.parametrize("method", ("AROW", "PA"))
def test_routed_step_matches_one_device_on_parsed_rows(method, rng):
    """The routed train_batch against ops.train_batch_parallel on one
    device, on rows as the parser leaves them (sorted, short rows and
    padding rows among them), two flushes in a row; a shard's partial score sums the very entries it
    summed under the mask, so the tables agree to f32 rounding."""
    dim, b, k, n = 1 << 12, 64, 40, 4
    conf = method in cops.CONFIDENCE_METHODS
    mesh = _mesh(n)
    ref = cops.init_state(L, dim, conf)
    st = sm.place_state(mesh, cops.init_state(L, dim, conf), dim)
    mask = jnp.asarray(np.arange(L) < 3)
    for _ in range(2):
        fill = rng.integers(0, 40, b)
        fill[5] = fill[17] = 0
        idx, val = _parsed_rows(rng, b, k, dim, fill)
        labels = jnp.asarray(rng.integers(0, 3, b).astype(np.int32))
        ref = cops.train_batch_parallel(
            ref, jnp.asarray(idx), jnp.asarray(val), labels, mask, 1.0,
            method=method)
        ridx, rval = _routed(mesh, idx, val, dim)
        assert ridx.shape[1] < k     # narrower than the flush
        assert [sh.data.shape for sh in ridx.addressable_shards] \
            == [(1, ridx.shape[1], b)] * n
        st = sm.train_batch(mesh, st, ridx, rval, labels, mask, 1.0,
                            method=method)
    for name, (a, c) in zip(("w", "dw", "prec", "dprec"), zip(ref, st)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=3e-5, atol=3e-5, err_msg=name)
    assert np.abs(np.asarray(st.dw)).sum() > 0


def test_per_device_footprint_is_sliced(rng):
    """The acceptance criterion's memory shape: each device holds
    exactly D/S columns of every feature-spanning leaf — never the
    full matrix."""
    mesh = _mesh(4)
    st = sm.place_state(mesh, cops.init_state(L, D, True), D)
    for leaf in st:
        for shard in leaf.addressable_shards:
            assert shard.data.shape[-1] == D // 4


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("method", ("PA", "PA1", "PA2"))
def test_regression_parity(method, n_shards, rng):
    mesh = _mesh(n_shards)
    idx = jnp.asarray(rng.integers(0, D, (24, K)).astype(np.int32))
    val = jnp.asarray(rng.normal(size=(24, K)).astype(np.float32))
    tgt = jnp.asarray(rng.normal(size=24).astype(np.float32))
    ref = rops.train_batch(rops.init_state(D), idx, val, tgt, 0.1, 1.0,
                           method=method)
    st = sm.place_state(mesh, rops.init_state(D), D)
    st = sm.regression_train_batch(mesh, st, idx, val, tgt, 0.1, 1.0,
                                   method=method)
    np.testing.assert_allclose(np.asarray(ref.dw), np.asarray(st.dw),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(sm.regression_estimate(mesh, st, idx, val)),
        np.asarray(rops.estimate(ref, idx, val)), rtol=3e-5, atol=3e-5)


def test_chunk_roundtrip_and_layout_validation(rng):
    mesh = _mesh(4)
    st = sm.place_state(mesh, cops.init_state(L, D, True), D)
    idx, val, labels, mask = _batch(rng)
    st = sm.train_batch(mesh, st, *_routed(mesh, idx, val), labels, mask,
                        1.0, method="AROW")
    chunks = sm.shard_chunks(st.dw)
    assert set(chunks) == {f"c{i * (D // 4)}" for i in range(4)}
    assert all(c.shape == (L, D // 4) for c in chunks.values())
    assert sm.is_chunked(chunks) and not sm.is_chunked({"x": 1}) \
        and not sm.is_chunked(np.zeros(3))
    back = sm.assemble_chunks(chunks, sm.chunk_sharding(mesh, rank=2))
    np.testing.assert_allclose(np.asarray(back), np.asarray(st.dw))
    # row trimming rides the chunker
    trimmed = sm.shard_chunks(st.dw, rows=2)
    assert all(c.shape == (2, D // 4) for c in trimmed.values())
    # a peer with a different layout must be rejected, not mis-folded
    wrong = dict(chunks)
    wrong.pop(f"c{D // 4}")
    with pytest.raises(ValueError, match="layout mismatch"):
        sm.assemble_chunks(wrong, sm.chunk_sharding(mesh, rank=2))


def _driver(conf, **kw):
    from jubatus_tpu.server.factory import create_driver

    return create_driver("classifier", dict(conf), **kw)


CONF = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
        "converter": {"num_rules": [{"key": "*", "type": "num"}]}}


def _datum(rng):
    return Datum({f"f{j}": float(v)
                  for j, v in enumerate(rng.normal(size=8))})


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_driver_classify_parity_across_shard_counts(n_shards, rng):
    plain = _driver(CONF)
    shard = _driver(CONF, mesh=_mesh(n_shards))
    data = [("a" if i % 2 else "b", _datum(rng)) for i in range(64)]
    plain.train(data)
    shard.train(data)
    q = [_datum(rng) for _ in range(8)]
    for ra, rb in zip(plain.classify(q), shard.classify(q)):
        for (la, sa), (lb, sb) in zip(ra, rb):
            assert la == lb
            np.testing.assert_allclose(sa, sb, rtol=1e-4, atol=1e-4)
    stats = shard.shard_stats()
    assert stats["count"] == n_shards
    assert stats["bytes_per_shard"] == stats["bytes_in_use"] // n_shards
    assert shard.get_status()["shard.count"] == n_shards


def test_mix_round_through_sharded_layout(rng):
    """One full get_diff→fold→put_diff round with per-shard chunks:
    two sharded replicas fold to the same model an unsharded pair does,
    and the wire carries chunk dicts (never one full-matrix leaf)."""
    a, b = _driver(CONF, mesh=_mesh(4)), _driver(CONF, mesh=_mesh(4))
    pa, pb = _driver(CONF), _driver(CONF)
    data_a = [("a" if i % 2 else "b", _datum(rng)) for i in range(32)]
    data_b = [("b" if i % 3 else "a", _datum(rng)) for i in range(32)]
    for d, data in ((a, data_a), (b, data_b), (pa, data_a), (pb, data_b)):
        d.train(data)
        d.sync_schema(["a", "b"])   # the mix round's schema phase
    mix_a = a.get_mixables()["classifier"]
    mix_b = b.get_mixables()["classifier"]
    da, db = mix_a.get_diff(), mix_b.get_diff()
    assert sm.is_chunked(da["dw"]) and sm.is_chunked(db["dw"])
    total = {
        "dw": {k: da["dw"][k] + db["dw"][k] for k in da["dw"]},
        "dprec": {k: da["dprec"][k] + db["dprec"][k] for k in da["dprec"]},
        "count": np.float32(da["count"] + db["count"]),
        "label_counts": da["label_counts"] + db["label_counts"],
    }
    mix_a.put_diff(total)
    mix_b.put_diff(total)
    # the unsharded control round
    pma = pa.get_mixables()["classifier"]
    pmb = pb.get_mixables()["classifier"]
    pda, pdb = pma.get_diff(), pmb.get_diff()
    ptotal = {k: (pda[k] + pdb[k] if not isinstance(pda[k], dict) else pda[k])
              for k in pda}
    pma.put_diff(ptotal)
    q = [_datum(rng) for _ in range(6)]
    for ra, rb, rc in zip(a.classify(q), b.classify(q), pa.classify(q)):
        da_, db_, dc_ = dict(ra), dict(rb), dict(rc)
        assert set(da_) == set(db_) == set(dc_)
        for lab in da_:
            np.testing.assert_allclose(da_[lab], db_[lab],
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(da_[lab], dc_[lab],
                                       rtol=1e-4, atol=1e-4)


def test_unsharded_member_applies_sharded_diff(rng):
    """Mixed fleets: an unsharded replica receiving per-shard chunks
    reassembles on host and stays score-identical."""
    shard = _driver(CONF, mesh=_mesh(4))
    plain = _driver(CONF)
    data = [("a" if i % 2 else "b", _datum(rng)) for i in range(32)]
    shard.train(data)
    plain.set_label("a")
    plain.set_label("b")
    for d in (shard, plain):
        d.sync_schema(["a", "b"])   # the mix round's schema phase
    diff = shard.get_mixables()["classifier"].get_diff()
    plain.get_mixables()["classifier"].put_diff(diff)
    shard.get_mixables()["classifier"].put_diff(diff)
    q = [_datum(rng) for _ in range(6)]
    for ra, rb in zip(plain.classify(q), shard.classify(q)):
        da_, db_ = dict(ra), dict(rb)
        assert set(da_) == set(db_)
        for lab in da_:
            np.testing.assert_allclose(da_[lab], db_[lab],
                                       rtol=1e-4, atol=1e-4)


def test_shard_features_flag_resolution():
    from jubatus_tpu.parallel.sharded_model import mesh_for_features

    # dim 2^18 (driver default) / 2^16 per shard = 4 shards
    drv = _driver(CONF, shard_features=1 << 16)
    assert drv._mesh is not None and drv._mesh.shape["shard"] == 4
    assert mesh_for_features(256, 256) is None      # one shard = no mesh
    with pytest.raises(ValueError, match="does not divide"):
        mesh_for_features(256, 100)
    with pytest.raises(ValueError, match="local devices"):
        mesh_for_features(256, 16)  # 16 shards > 8 virtual devices


def test_jubactl_renders_shard_layout():
    """ISSUE 13 satellite: status --all and the watch view surface the
    shard layout from the shard.* gauges."""
    from jubatus_tpu.cmd.jubactl import _fmt_shard_layout, _watch_node_row

    st = {"driver.shard.count": 8, "driver.shard.rows": 1200,
          "driver.shard.rows_per_shard": [150] * 8,
          "driver.shard.bytes_in_use": 256 * 2 ** 20,
          "driver.shard.topk_merge_ms": 12.5,
          "health.status": "ok"}
    line = _fmt_shard_layout(st)
    assert line.startswith("shards: 8 ×")
    assert "150/150" in line and "topk_merge 12.5 ms" in line
    row = _watch_node_row("n1", {"status": st}, active=True)
    assert "sh 8x1200r" in row
    # feature-sharded (no rows_per_shard): MB-per-shard form
    st2 = {"driver.shard.count": 4,
           "driver.shard.bytes_in_use": 2048 * 2 ** 20,
           "health.status": "ok"}
    assert "512MB" in _watch_node_row("n2", {"status": st2}, active=True)
    assert _fmt_shard_layout({"health.status": "ok"}) == ""


def test_sequential_mode_keeps_gspmd_path(rng):
    """train_mode="sequential" (exact per-datum semantics) still works
    under a mesh — the GSPMD-partitioned kernels serve it."""
    from jubatus_tpu.models.classifier import ClassifierDriver

    drv = ClassifierDriver(dict(CONF), train_mode="sequential",
                           mesh=_mesh(4))
    ref = ClassifierDriver(dict(CONF), train_mode="sequential")
    data = [("a" if i % 2 else "b", _datum(rng)) for i in range(16)]
    drv.train(data)
    ref.train(data)
    q = [_datum(rng) for _ in range(4)]
    for ra, rb in zip(ref.classify(q), drv.classify(q)):
        for (la, sa), (lb, sb) in zip(ra, rb):
            assert la == lb
            np.testing.assert_allclose(sa, sb, rtol=1e-4, atol=1e-4)
