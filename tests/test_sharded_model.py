"""Feature-sharded linear model tests (parallel/sharded_model.py,
ISSUE 13 tentpole): shard_map'd train/classify must match the
single-device kernels to f32 rounding across shard counts, the drivers
must route through the sharded path transparently, and the per-shard
diff chunks must fold/apply without ever materializing the matrix."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.ops import classifier as cops
from jubatus_tpu.ops import regression as rops
from jubatus_tpu.parallel import sharded_model as sm

D, L, B, K = 512, 4, 48, 8
SHARD_COUNTS = (2, 4, 8)   # >= 3 shard counts per the acceptance criteria


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("shard",))


def _batch(rng, b=B, k=K, dim=D):
    idx = rng.integers(0, dim, (b, k)).astype(np.int32)
    val = rng.normal(size=(b, k)).astype(np.float32)
    labels = rng.integers(0, 3, b).astype(np.int32)
    mask = np.zeros(L, bool)
    mask[:3] = True
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(labels),
            jnp.asarray(mask))


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
    st = sm.train_batch(mesh, st, idx, val, labels, mask, 1.0,
                        method=method)
    st = sm.train_batch(mesh, st, idx2, val2, labels2, mask, 1.0,
                        method=method)
    for name, (a, b) in zip(("w", "dw", "prec", "dprec"), zip(ref, st)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5, err_msg=name)
    qi, qv, _, _ = _batch(rng, k=k)
    np.testing.assert_allclose(
        np.asarray(sm.scores(mesh, st, qi, qv, mask)),
        np.asarray(cops.scores(ref, qi, qv, mask)),
        rtol=3e-5, atol=3e-5)


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
    st = sm.train_batch(mesh, st, idx, val, labels, mask, 1.0,
                        method="AROW")
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
