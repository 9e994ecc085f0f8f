"""Feature-sharded classifier driver (models/classifier.py mesh mode):
one server's [L, D] tables span a local device mesh via GSPMD — results
must match the single-device driver through the full lifecycle (train,
classify, label churn, schema sync, save/load), and the state must
actually be sharded."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.models.classifier import ClassifierConfigError, ClassifierDriver

CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_rules": [{"key": "*", "type": "space",
                          "sample_weight": "bin", "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
    },
}


@pytest.fixture(scope="module")
def mesh():
    return Mesh(jax.devices()[:8], axis_names=("shard",))



def _train_both(a, b, rng, n=40):
    for i in range(n):
        x = float(rng.normal())
        lbl = "pos" if x > 0 else "neg"
        d = Datum({"x": x, "b": 1.0, "w": f"tok{i % 7}"})
        a.train([(lbl, d)])
        b.train([(lbl, d)])


def test_sharded_matches_dense_lifecycle(mesh, rng):
    dense = ClassifierDriver(CONF, dim_bits=12)
    shard = ClassifierDriver(CONF, dim_bits=12, mesh=mesh)
    # state really lives sharded
    assert "shard" in str(shard.state.w.sharding)
    assert len(shard.state.w.addressable_shards) == 8
    _train_both(dense, shard, rng)
    assert dense.get_labels() == shard.get_labels()
    q = [Datum({"x": 0.7, "b": 1.0}), Datum({"x": -0.7, "b": 1.0})]
    for rd, rs in zip(dense.classify(q), shard.classify(q)):
        assert [l for l, _ in rd] == [l for l, _ in rs]
        np.testing.assert_allclose([s for _, s in rd], [s for _, s in rs],
                                   rtol=1e-5, atol=1e-6)

    # label churn: grow past capacity (8) and delete — sharding must stick
    for i in range(10):
        shard.set_label(f"extra{i}")
        dense.set_label(f"extra{i}")
    assert shard.capacity == dense.capacity > 8
    assert "shard" in str(shard.state.w.sharding)
    shard.delete_label("extra3")
    dense.delete_label("extra3")
    assert dense.get_labels().keys() == shard.get_labels().keys()

    # schema sync rebuild keeps placement
    union = sorted(shard.get_labels())
    shard.sync_schema(union)
    dense.sync_schema(union)
    assert "shard" in str(shard.state.w.sharding)
    for rd, rs in zip(dense.classify(q), shard.classify(q)):
        np.testing.assert_allclose(sorted(s for _, s in rd),
                                   sorted(s for _, s in rs),
                                   rtol=1e-5, atol=1e-6)


def test_sharded_save_load_roundtrip(mesh, rng, tmp_path):
    from jubatus_tpu.framework import load_model, save_model

    shard = ClassifierDriver(CONF, dim_bits=12, mesh=mesh)
    dense = ClassifierDriver(CONF, dim_bits=12)
    _train_both(dense, shard, rng, n=20)
    path = str(tmp_path / "s.jubatus")
    save_model(path, shard, config=json.dumps(CONF))
    # a sharded checkpoint loads into a DENSE driver (envelope is host-side)
    dense2 = ClassifierDriver(CONF, dim_bits=12)
    load_model(path, dense2, expected_config=json.dumps(CONF))
    q = [Datum({"x": 0.4, "b": 1.0})]
    np.testing.assert_allclose(
        [s for _, s in dense.classify(q)[0]],
        [s for _, s in dense2.classify(q)[0]], rtol=1e-5, atol=1e-6)
    # ... and back into a sharded one, which re-places the arrays
    shard2 = ClassifierDriver(CONF, dim_bits=12, mesh=mesh)
    load_model(path, shard2, expected_config=json.dumps(CONF))
    assert "shard" in str(shard2.state.w.sharding)
    np.testing.assert_allclose(
        [s for _, s in shard.classify(q)[0]],
        [s for _, s in shard2.classify(q)[0]], rtol=1e-5, atol=1e-6)


def test_indivisible_dim_rejected():
    import jax
    from jax.sharding import Mesh

    mesh3 = Mesh(np.array(jax.devices()[:3]), axis_names=("shard",))
    with pytest.raises(ClassifierConfigError, match="not divisible"):
        ClassifierDriver(CONF, dim_bits=4, mesh=mesh3)  # 16 features / 3 devs


def test_server_level_shard_devices(rng):
    """EngineServer --shard-devices: full RPC stack on a sharded model."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer(
        "classifier", CONF,
        ServerArgs(engine="classifier", shard_devices=4))
    assert len(srv.driver.state.w.addressable_shards) == 4
    port = srv.start(0)
    try:
        with ClassifierClient("127.0.0.1", port, "sd") as c:
            assert c.train([["up", Datum({"x": 1.0}).to_msgpack()],
                            ["down", Datum({"x": -1.0}).to_msgpack()]]) == 2
            (res,) = c.classify([Datum({"x": 0.9}).to_msgpack()])
            assert max(res, key=lambda e: e[1])[0] == "up"
    finally:
        srv.stop()


def test_sharded_regression_matches_dense(mesh, rng):
    from jubatus_tpu.models.regression import RegressionDriver

    cfg = {"method": "PA1",
           "parameter": {"sensitivity": 0.1, "regularization_weight": 1.0},
           "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    dense = RegressionDriver(cfg, dim_bits=12)
    shard = RegressionDriver(cfg, dim_bits=12, mesh=mesh)
    assert len(shard.state.w.addressable_shards) == 8
    for _ in range(30):
        x = float(rng.uniform(-1, 1))
        d = Datum({"x": x, "b": 1.0})
        dense.train([(2.0 * x + 1.0, d)])
        shard.train([(2.0 * x + 1.0, d)])
    q = [Datum({"x": 0.5, "b": 1.0}), Datum({"x": -0.5, "b": 1.0})]
    np.testing.assert_allclose(shard.estimate(q), dense.estimate(q),
                               rtol=1e-5, atol=1e-6)
    shard.clear()
    assert "shard" in str(shard.state.w.sharding)
    assert shard.estimate(q) == [0.0, 0.0]


def test_factory_mesh_routing(mesh):
    """--shard-devices routes per engine family: feature-sharding for the
    linear engines, NNBackend row-sharding for instance engines with hash
    methods, a clear error for everything else."""
    from jubatus_tpu.server.factory import create_driver

    with pytest.raises(ValueError, match="not supported"):
        create_driver("stat", {"window_size": 10}, mesh=mesh)
    # instance engine + hash method → backend mesh attached
    nn = create_driver("nearest_neighbor", {
        "method": "lsh", "parameter": {"hash_num": 16},
        "converter": {"num_rules": [{"key": "*", "type": "num"}]},
    }, mesh=mesh)
    assert nn.backend._mesh is mesh
    # instance-classifier hash method too
    cnn = create_driver("classifier", {
        "method": "NN", "parameter": {"method": "lsh",
                                      "parameter": {"hash_num": 8}},
        "converter": {"num_rules": [{"key": "*", "type": "num"}]},
    }, mesh=mesh)
    assert cnn.backend._mesh is mesh
    # exact methods have no sharded scan → NNBackend rejects
    with pytest.raises(ValueError, match="hash methods"):
        create_driver("recommender", {
            "method": "inverted_index", "parameter": {},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]},
        }, mesh=mesh)
    # anomaly rides sharded_distances (LOF needs full vectors)
    an = create_driver("anomaly", {
        "method": "lof",
        "parameter": {"nearest_neighbor_num": 5,
                      "reverse_nearest_neighbor_num": 10,
                      "method": "lsh", "parameter": {"hash_num": 8}},
        "converter": {"num_rules": [{"key": "*", "type": "num"}]},
    }, mesh=mesh)
    assert an.backend._mesh is mesh


def test_sharded_nn_server_end_to_end(rng):
    """--shard-devices on a nearest_neighbor server: rows are served from
    the row-sharded table over RPC."""
    from jubatus_tpu.client import NearestNeighborClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "lsh", "parameter": {"hash_num": 64},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    srv = EngineServer("nearest_neighbor", conf,
                       ServerArgs(engine="nearest_neighbor", shard_devices=8))
    assert srv.driver.backend._mesh is not None
    port = srv.start(0)
    try:
        with NearestNeighborClient("127.0.0.1", port, "snn") as c:
            for i in range(20):
                c.set_row(f"r{i}", Datum({"x": float(i), "y": float(i % 5)}))
            near = c.neighbor_row_from_id("r3", 5)
            assert any(r == "r3" for r, _ in near)
            assert len(near) == 5
    finally:
        srv.stop()


@pytest.mark.slow
def test_sharded_servers_mix_across_cluster(rng):
    """Intra-server feature sharding composes with cross-server mixing:
    two servers, each spanning 4 local devices, average models over the
    RPC mix plane and converge to shared knowledge."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    store = _Store()
    servers = []
    for _ in range(2):
        args = ServerArgs(
            engine="classifier", coordinator="(shared)", name="shmix",
            listen_addr="127.0.0.1", shard_devices=4,
            interval_sec=1e9, interval_count=1 << 30,
        )
        srv = EngineServer("classifier", CONF, args,
                           coord=MemoryCoordinator(store))
        srv.start(0)
        servers.append(srv)
    clients = [ClassifierClient("127.0.0.1", s.args.rpc_port, "shmix")
               for s in servers]
    try:
        for _ in range(10):
            clients[0].train([["pos", Datum({"x": 1.0}).to_msgpack()]])
            clients[1].train([["neg", Datum({"x": -1.0}).to_msgpack()]])
        assert clients[0].do_mix() is True
        for c in clients:
            assert set(c.get_labels()) == {"pos", "neg"}
            (r,) = c.classify([Datum({"x": 1.0}).to_msgpack()])
            assert max(r, key=lambda e: e[1])[0] == "pos"
        # sharding survived the mix round's put_diff
        for s in servers:
            assert "shard" in str(s.driver.state.w.sharding)
    finally:
        for c in clients:
            c.close()
        for s in servers:
            s.stop()


# -- the state is born in its layout (ISSUE 31) -------------------------------

DIM_BITS = 13       # a width of its own: nothing else in this process has it


def _whole_on_one_device(dim):
    """Live arrays of ``dim`` columns that one device holds whole."""
    import gc

    gc.collect()
    return [a for a in jax.live_arrays()
            if a.ndim >= 1 and a.shape[-1] == dim
            and any(s.data.shape[-1] == dim for s in a.addressable_shards)]


def _assert_born_sharded(drv, n):
    dim = drv.converter.dim
    for name, leaf in zip(drv.state._fields, drv.state):
        assert leaf.shape == (drv.capacity, dim), name
        assert leaf.sharding.is_equivalent_to(drv._sharding, 2), name
        assert [s.data.shape for s in leaf.addressable_shards] \
            == [(drv.capacity, dim // n)] * n, name
        assert len({s.device for s in leaf.addressable_shards}) == n
    assert _whole_on_one_device(dim) == []


@pytest.mark.parametrize("step", ["construction", "clear", "grow_labels",
                                  "load"])
def test_no_table_ever_lies_whole_on_one_device(step, rng, tmp_path):
    """At 2^28 columns one [8, D] table is 8.59e9 B and two exceed a
    chip, so every leaf has to be made in its four column ranges: where
    the driver is built, cleared, grown and loaded."""
    from jubatus_tpu.framework import load_model, save_model

    mesh4 = Mesh(np.asarray(jax.devices()[:4]), axis_names=("shard",))
    drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    _assert_born_sharded(drv, 4)
    if step == "construction":
        return
    plain = ClassifierDriver(CONF, dim_bits=DIM_BITS)
    _train_both(plain, drv, rng, n=12)
    if step == "clear":
        drv.clear()
        assert drv.get_labels() == {} and drv.capacity == 8
    elif step == "grow_labels":
        for i in range(9):
            drv.set_label(f"extra{i}")
        assert drv.capacity == 16
    else:
        path = str(tmp_path / "s.jubatus")
        save_model(path, drv, config=json.dumps(CONF))
        drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
        load_model(path, drv, expected_config=json.dumps(CONF))
        q = [Datum({"x": 0.4, "b": 1.0})]
        np.testing.assert_allclose(
            [s for _, s in plain.classify(q)[0]],
            [s for _, s in drv.classify(q)[0]], rtol=1e-5, atol=1e-6)
    del plain
    _assert_born_sharded(drv, 4)


@pytest.mark.parametrize("how", ["clear", "load"])
def test_the_old_state_is_let_go_before_the_new_one_is_made(
        how, rng, monkeypatch, tmp_path):
    """Two states of 8.59e9 B a chip do not fit 16e9: when the new
    leaves are made, no table of the old state is alive."""
    from jubatus_tpu.framework import load_model, save_model
    from jubatus_tpu.models import classifier as model

    mesh4 = Mesh(np.asarray(jax.devices()[:4]), axis_names=("shard",))
    drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    _train_both(ClassifierDriver(CONF, dim_bits=DIM_BITS), drv, rng, n=8)
    path = str(tmp_path / "s.jubatus")
    save_model(path, drv, config=json.dumps(CONF))
    dim = drv.converter.dim
    alive = []

    def tables():
        import gc

        gc.collect()
        return [a for a in jax.live_arrays()
                if a.ndim == 2 and a.shape[-1] == dim]

    real_init, real_put = model.ops.init_state, model.jax.device_put

    def init_state(*a, **k):
        alive.append(len(tables()))
        return real_init(*a, **k)

    def device_put(x, *a, **k):
        alive.append(len(tables()))
        return real_put(x, *a, **k)

    monkeypatch.setattr(model.ops, "init_state", init_state)
    monkeypatch.setattr(model.jax, "device_put", device_put)
    if how == "clear":
        drv.clear()
    else:
        load_model(path, drv, expected_config=json.dumps(CONF))
    assert alive and alive[0] == 0, alive
    assert len(tables()) == 4


def test_the_shard_counters_add_up(rng):
    """What a flush stamps for the mesh: issued = shards x padded rows x
    width, the shards' owned entries are the entries that carry a
    feature, and the plan is the one a shard's slice settles."""
    from jubatus_tpu.ops import classifier as ops
    from jubatus_tpu.utils.tracing import Registry

    n, width, rows = 4, 40, 300
    mesh4 = Mesh(np.asarray(jax.devices()[:n]), axis_names=("shard",))
    drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    drv.trace = Registry()
    dim = drv.converter.dim
    idx = rng.integers(1, dim, (rows, width)).astype(np.int32)
    idx[:, 0] = 5                # a key every row carries: one column
    idx[:, 30:] = 0              # the width's padding
    val = (idx != 0).astype(np.float32)
    owned = drv._shard_entries(idx)
    assert owned.tolist() == np.bincount(
        idx[idx != 0] // (dim // n), minlength=n).tolist()
    assert owned.sum() == np.count_nonzero(idx)
    drv.train_hashed(["a" if i % 2 else "b" for i in range(rows)], idx, val)
    c = drv.trace.counters()
    assert c["step.train.shard_entries"] == c["step.train.entries"] \
        == rows * 30
    assert c["step.train.shard_entries_issued"] == n * 512 * width
    assert c["step.train.shard_entries_owned_max"] == owned.max()
    # the fixed key's column is shard 0's: it owns more than a quarter
    assert owned.argmax() == 0 and owned.max() > owned.sum() / n
    plan = ops.gather_plan(8, dim // n, 512 * width)
    assert c[f"step.train.plan_{plan}"] == 1
    # one chip stamps its plan from the whole table and no shard counter
    one = ClassifierDriver(CONF, dim_bits=DIM_BITS)
    one.trace = Registry()
    one.train_hashed(["a", "b"] * (rows // 2), idx, val)
    c1 = one.trace.counters()
    assert not [k for k in c1 if "shard_entries" in k]
    assert c1[f"step.train.plan_{ops.gather_plan(8, dim, 512 * width)}"] == 1
