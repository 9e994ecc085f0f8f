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


def _whole_on_one_device(dim, before=()):
    """Live arrays of ``dim`` columns that one device holds whole, born
    since ``before`` (``jax.live_arrays()`` as the test began: what an
    earlier file of the same worker left alive is not this driver's)."""
    import gc

    gc.collect()
    old = {id(a) for a in before}
    return [a for a in jax.live_arrays()
            if id(a) not in old and a.ndim >= 1 and a.shape[-1] == dim
            and any(s.data.shape[-1] == dim for s in a.addressable_shards)]


def _assert_born_sharded(drv, n, before=()):
    dim = drv.converter.dim
    for name, leaf in zip(drv.state._fields, drv.state):
        assert leaf.shape == (drv.capacity, dim), name
        assert leaf.sharding.is_equivalent_to(drv._sharding, 2), name
        assert [s.data.shape for s in leaf.addressable_shards] \
            == [(drv.capacity, dim // n)] * n, name
        assert len({s.device for s in leaf.addressable_shards}) == n
    assert _whole_on_one_device(dim, before) == []


@pytest.mark.parametrize("step", ["construction", "clear", "grow_labels",
                                  "load"])
def test_no_table_ever_lies_whole_on_one_device(step, rng, tmp_path):
    """At 2^28 columns one [8, D] table is 8.59e9 B and two exceed a
    chip, so every leaf has to be made in its four column ranges: where
    the driver is built, cleared, grown and loaded."""
    from jubatus_tpu.framework import load_model, save_model

    before = jax.live_arrays()  # held to the end: no id comes round again
    mesh4 = Mesh(np.asarray(jax.devices()[:4]), axis_names=("shard",))
    drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    _assert_born_sharded(drv, 4, before)
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
    _assert_born_sharded(drv, 4, before)


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
    routed width, the shards' owned entries are the entries that carry a
    feature, the routed width is counted by name, and the plan is the one
    a shard's slice and its plane of the flush settle."""
    from jubatus_tpu.core.sparse import _width_bucket
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
    owned = np.bincount(idx[idx != 0] // (dim // n), minlength=n)
    by_row = [np.count_nonzero((idx != 0) & (idx // (dim // n) == s), axis=1)
              for s in range(n)]
    ks = _width_bucket(int(np.max(by_row)))
    assert 8 <= ks < width
    drv.train_hashed(["a" if i % 2 else "b" for i in range(rows)], idx, val)
    c = drv.trace.counters()
    assert c["step.train.shard_entries"] == c["step.train.entries"] \
        == rows * 30
    assert c["step.train.shard_entries_issued"] == n * 512 * ks
    assert c["step.train.shard_entries_owned_max"] == owned.max()
    assert c[f"step.train.shard_width_{ks}"] == 1
    assert c["step.train.width_40"] == 1
    assert c["step.train.entries_padded"] == rows * width
    # the routed arrays' bytes summed over the chips, and the labels
    assert c["step.train.upload_bytes"] == n * 512 * ks * 8 + 512 * 4
    # the fixed key's column is shard 0's: it owns more than a quarter
    assert owned.argmax() == 0 and owned.max() > owned.sum() / n
    plan = ops.gather_plan(8, dim // n, 512 * ks)
    assert c[f"step.train.plan_{plan}"] == 1
    # one chip stamps its plan from the whole table and no shard counter
    one = ClassifierDriver(CONF, dim_bits=DIM_BITS)
    one.trace = Registry()
    one.train_hashed(["a", "b"] * (rows // 2), idx, val)
    c1 = one.trace.counters()
    assert not [k for k in c1 if "shard_" in k]
    assert c1[f"step.train.plan_{ops.gather_plan(8, dim, 512 * width)}"] == 1
    # ... and builds the same model
    q = [Datum({"x": 0.4, "b": 1.0})]
    np.testing.assert_allclose(
        [s for _, s in one.classify(q)[0]],
        [s for _, s in drv.classify(q)[0]], rtol=1e-5, atol=1e-6)


# -- a chip is handed only the entries it owns (ISSUE 32) ---------------------

def _flush_of_width(rng, dim, n, rows, fullest):
    """[rows, 40] sorted rows whose fullest shard row holds ``fullest``
    entries (row 0, shard 1); every other row 2 entries a shard."""
    d_local = dim // n
    idx = np.zeros((rows, 40), np.int32)
    for i in range(rows):
        per = [fullest if (i == 0 and s == 1) else 2 for s in range(n)]
        cols = np.concatenate([
            s * d_local + 1 + rng.choice(d_local - 1, m, replace=False)
            for s, m in enumerate(per)])
        idx[i, :len(cols)] = np.sort(cols)
    return idx, (idx != 0).astype(np.float32)


def test_the_routed_width_never_shrinks(rng):
    """A flush that would settle on 16 after one at 24 runs at 24 and
    compiles nothing: a server's traffic makes one routed width a row
    bucket, not one for every short call. ``clear`` does not reset it.
    The floor holds among flushes of one width K and is no wider than
    K's rung: a wide flush leaves the narrow ones as they were."""
    from jubatus_tpu.parallel import sharded_model as sm
    from jubatus_tpu.utils.tracing import Registry

    n = 4
    mesh4 = Mesh(np.asarray(jax.devices()[:n]), axis_names=("shard",))
    drv = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    drv.trace = Registry()
    dim = drv.converter.dim
    labels = ["a", "b"] * 24
    wide = _flush_of_width(rng, dim, n, 48, 20)
    short = _flush_of_width(rng, dim, n, 48, 10)
    assert sm.route_rows(*wide, n, dim // n)[0].shape[1] == 24
    assert sm.route_rows(*short, n, dim // n)[0].shape[1] == 16
    drv.train_hashed(labels, *wide)
    compiled = sm.train_batch._cache_size()
    drv.train_hashed(labels, *short)
    drv.clear()
    drv.train_hashed(labels, *short)
    assert sm.train_batch._cache_size() == compiled
    c = drv.trace.counters()
    assert {k: v for k, v in c.items() if "shard_width" in k} \
        == {"step.train.shard_width_24": 3}
    assert c["step.train.shard_entries_issued"] == 3 * n * 64 * 24
    # a fuller row still widens it
    drv.train_hashed(labels, *_flush_of_width(rng, dim, n, 48, 30))
    assert drv.trace.counters()["step.train.shard_width_32"] == 1
    # the floor is a flush width's own: an 8-wide flush after those runs
    # at 8 and issues no more than it would have under the mask
    narrow = wide[0][:, :8], wide[1][:, :8]
    drv.train_hashed(labels, *narrow)
    c = drv.trace.counters()
    assert c["step.train.width_8"] == c["step.train.shard_width_8"] == 1
    assert c["step.train.shard_entries_issued"] \
        == n * 64 * (3 * 24 + 32 + 8)
    # and the 40-wide flushes keep theirs
    drv.train_hashed(labels, *short)
    assert drv.trace.counters()["step.train.shard_width_32"] == 2
    # and a server that starts on the short flush takes the short width
    fresh = ClassifierDriver(CONF, dim_bits=DIM_BITS, mesh=mesh4)
    fresh.trace = Registry()
    fresh.train_hashed(labels, *short)
    assert fresh.trace.counters()["step.train.shard_width_16"] == 1


CRITEO_LIKE = {
    "method": "AROW", "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_rules": [{"key": "*", "type": "str", "sample_weight": "bin",
                          "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
        "hash_max_size": 1 << 16}}


def _criteo_like(rng, rows):
    """13 integer keys and 26 categorical: 39 features, width 40."""
    return [["click" if i % 3 else "skip", Datum(
        {f"I{k}": float(rng.integers(1, 50)) for k in range(13)}
        | {f"C{k}": f"v{int(rng.integers(1 << 20))}" for k in range(26)}
    ).to_msgpack()] for i in range(rows)]


def test_a_sharded_server_through_the_entry_point_counts_what_it_issues(
        rng, tmp_path):
    """``tests/perfbench/test_sharded4.py::test_a_real_sharded_server_
    holds_four_shards_and_counts_what_they_own`` with what the chips issue
    since ISSUE 32 (that file is the benchmark's and holds the masked
    flush's ``4 * 512 * 40``: ROADMAP R-B1): through the entry point and
    ``--shard-devices 4``, a 300-row train call issues 4 x 512 x the
    routed width, every entry is owned by one shard, one routed width is
    counted, and ``clear`` leaves the layout and the width."""
    import socket
    import subprocess
    import sys
    import time

    from jubatus_tpu.client import ClassifierClient

    conf = tmp_path / "model.json"
    conf.write_text(json.dumps(CRITEO_LIKE))
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    log = open(tmp_path / "server.log", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "jubatus_tpu.server", "classifier",
         "-f", str(conf), "-d", str(tmp_path), "-p", str(port),
         "--shard-devices", "4"], stdout=log, stderr=log)

    def status(c):
        (st,) = c.get_status().values()
        return st, {k[len("trace.counter.step.train."):]: v
                    for k, v in st.items()
                    if k.startswith("trace.counter.step.train.")}

    try:
        deadline = time.monotonic() + 300
        while True:
            assert proc.poll() is None, \
                (tmp_path / "server.log").read_text()[-3000:]
            try:
                with ClassifierClient("127.0.0.1", port, "", timeout=30) as c:
                    st, _ = status(c)
                break
            except (OSError, RuntimeError):
                assert time.monotonic() < deadline
                time.sleep(0.25)
        dim = CRITEO_LIKE["converter"]["hash_max_size"]
        assert st["driver.shard.count"] == 4
        assert len(set(st["driver.shard.devices"])) == 4
        assert st["driver.shard.shard_shape"] == [8, dim // 4]
        rows = _criteo_like(rng, 300)
        with ClassifierClient("127.0.0.1", port, "", timeout=300) as c:
            assert c.train(rows) == 300
            time.sleep(1.1)             # the status sample is cached for 1 s
            st, count = status(c)
            assert count["width_40"] == 1
            (ks,) = [int(k[len("shard_width_"):]) for k in count
                     if k.startswith("shard_width_")]
            # 39 features over four ranges: the fullest row of the
            # fullest shard holds well under the flush's 40
            assert 16 <= ks <= 32 and count[f"shard_width_{ks}"] == 1
            assert count.get("plan_packed", 0) \
                + count.get("plan_columns", 0) == 1
            assert count["shard_entries_issued"] == 4 * 512 * ks
            assert count["shard_entries"] == count["entries"] > 300 * 35
            assert count["shard_entries"] / 4 \
                <= count["shard_entries_owned_max"] < count["shard_entries"]
            # what the benchmark's reader makes of them: under what
            # handing every shard every entry of the 512 x 40 flush cost
            # (85.7%); what is left is the 212 padding rows and the rows
            # shorter than the fullest
            masked = 100 * (1 - count["shard_entries"]
                            / count["shard_entries_issued"])
            assert 100 * (1 - 300 * 39 / (4 * 512 * ks)) <= masked \
                < 100 * (1 - 300 * 39 / (4 * 512 * 40)) - 5
            assert c.clear() is True
            assert c.train(rows) == 300
            time.sleep(1.1)
            st, count = status(c)
        assert st["driver.shard.count"] == 4
        assert st["driver.shard.shard_shape"] == [8, dim // 4]
        assert count[f"shard_width_{ks}"] == 2
        assert count["shard_entries_issued"] == 2 * 4 * 512 * ks
    finally:
        proc.terminate()
        try:
            proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)
        log.close()


REHEARSAL = """
import json, os, pathlib, sys
sys.path.insert(0, {tests!r})
import pbtest_util as u
from test_rehearsal import BURST_PLAN, TRAIN
# a checkout of its own: the run directory is <root>/.perfbench_run/<cell>,
# and the benchmark's own test of this cell may be running in the repo's
root, bench = u.make_checkout(pathlib.Path({tmp!r}))
u.add_cell(root, bench, "criteo_arow_sharded4.t_train",
           "criteo_arow_sharded4", "t_train",
           u.small_traffic(TRAIN, plan=BURST_PLAN),
           like="criteo_arow_sharded4.train")
res = u.rehearse(root, "criteo_arow_sharded4.t_train", trace=True,
                 server_entry=[sys.executable, os.path.join(
                     {tests!r}, "faulty_sharded_server.py"), {fault!r}])
print("RESULT " + json.dumps({{
    "correct": res["correct"], "failed": res["failed"],
    "compared": res["compared"],
    "metrics": {{k: v["value"] for k, v in res["metrics"].items()}}}}))
"""


@pytest.mark.parametrize("fault", ["none", "shard_drops_updates"])
def test_the_sharded_cells_rehearsal_is_correct_on_routed_flushes(
        tmp_path, fault):
    """Both cases of ``tests/perfbench/test_sharded4.py::test_the_
    rehearsal_is_correct_and_a_shard_that_drops_its_updates_is_not`` with
    the share of descriptors that routed flushes leave (that file is the
    benchmark's and holds ``75 <=``, what handing every shard every entry
    cost: ROADMAP R-B1): the cell's kind of run through a real
    ``--shard-devices 4`` server is ``correct`` against the one-chip
    cells' reference, and is not where a shard drops its updates. A
    process of its own, so that its time limit is its own."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(
            tests=os.path.join(repo, "tests", "perfbench"),
            tmp=str(tmp_path), fault=fault)],
        cwd=repo, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    m, gap = res["metrics"], res["compared"]["score_gap"]
    # short flushes in power-of-two row buckets at a routed width of 16
    # or 24 where the flush is 40 wide: row padding, no mask
    assert 40 <= m["step.train_shard_masked_share"] < 75
    assert 25 <= m["step.train_shard_owned_max_share"] < 100
    assert "step.train_hbm_roofline.mesh" not in m      # a device number
    if fault == "none":
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0
    else:
        assert res["correct"] is False
        assert not gap["value"] <= gap["limit"]
