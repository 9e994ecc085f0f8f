"""A combination-rule configuration on the server's normal path (ISSUE 26,
28): the native parse makes the cross product on its own threads
(``fv.combine.native``), whatever the rows; what the native parser does not
serve is expanded by the Python converter (``fv.combine.generic``). Each
request is counted once, by the counter of the path it took, and both build
the same model. The service hands the expanded rows to the driver, which
takes its dense plan where a flush's rows all carry one index row."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_rules": [{"key": "*", "type": "str", "sample_weight": "bin",
                          "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
        "combination_types": {"comb": {"method": "mul"}},
        "combination_rules": [{"key_left": "*", "key_right": "*",
                               "type": "comb"}],
        "hash_max_size": 1 << 18,
    },
}
PATHS = ("native", "generic")
N_NUM, N_STR = 5, 4
PAIRS = (N_NUM + N_STR) * (N_NUM + N_STR - 1) // 2


def _rows(n, seed, uniform):
    """Rows whose string VALUES differ (so their feature names do), or
    rows of numeric keys alone (one index row for all)."""
    from jubatus_tpu.client import Datum

    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        d = {f"n{k}": float(rng.integers(0, 4)) for k in range(N_NUM)}
        if not uniform:
            # string keys that sort before the numeric ones: a pair's name
            # then ends in "@num" behind the string half's "#bin/bin"
            d |= {f"c{k}": f"v{int(rng.integers(1000))}"
                  for k in range(N_STR)}
        rows.append(("pos" if i % 2 else "neg", Datum(d)))
    return rows


def _server(native: bool, monkeypatch):
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    if not native:
        monkeypatch.setenv("JUBATUS_TPU_NATIVE_INGEST", "0")
    srv = EngineServer(
        "classifier", CONF,
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        quality_sample=0.0))
    port = srv.start(0)
    if native and "train_raw" not in srv.coalescers:
        srv.stop()
        pytest.skip("no native ingest here: the raw path is not registered")
    return srv, port


def _counts(srv):
    c = srv.rpc.trace.counters()
    return {p: c.get(f"fv.combine.{p}", 0) for p in PATHS}


def _scores(client, rows):
    return np.array([[s for _lb, s in sorted(
        (str(r[0]), float(r[1])) for r in ranked)]
        for ranked in client.classify([d for _l, d in rows])])


def test_each_path_is_counted_once_and_all_build_the_same_model(monkeypatch):
    from jubatus_tpu.client import ClassifierClient

    mixed, uniform = _rows(40, 1, False), _rows(40, 2, True)
    probe = _rows(30, 3, False) + _rows(10, 4, True)
    seen = {}
    for native in (True, False):
        srv, port = _server(native, monkeypatch)
        try:
            with ClassifierClient("127.0.0.1", port, "") as c:
                before = _counts(srv)
                assert c.train(mixed) == 40
                after_mixed = _counts(srv)
                assert c.train(uniform) == 40
                after_uniform = _counts(srv)
                seen[native] = _scores(c, probe)
            took = {p: after_mixed[p] - before[p] for p in PATHS}
            took_u = {p: after_uniform[p] - after_mixed[p] for p in PATHS}
            if native:
                assert took == {"native": 1, "generic": 0}
                assert took_u == {"native": 1, "generic": 0}
                counters = srv.rpc.trace.counters()
                assert "fv.combine.device" not in counters
                # the parse threads made every pair of every row, and the
                # probe's classify was counted by its rows' path too
                assert counters["fv.combine.rows"] == 80 + 40
                assert counters["fv.combine.slots"] == \
                    40 * PAIRS + 40 * (N_NUM * (N_NUM - 1) // 2) \
                    + 30 * PAIRS + 10 * (N_NUM * (N_NUM - 1) // 2)
                hist = srv.rpc.trace.trace_status()
                assert hist["trace.fv.combine.count"] >= 3
                # one request shape: both calls were flushes of the one kind
                assert srv.ingest_stats == {"sparse_flushes": 2,
                                            "sparse_query_flushes": 1}
            else:
                assert took == {"native": 0, "generic": 1}
                assert took_u == {"native": 0, "generic": 1}
        finally:
            srv.stop()
    np.testing.assert_allclose(seen[True], seen[False], rtol=2e-5, atol=2e-6)


def test_a_uniform_feed_is_expanded_by_the_parser_and_rides_the_dense_plan(
        monkeypatch):
    """Whole flushes of one schema: the host's cross product, counted
    ``native``, and the driver's dense plan on the expanded rows."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.ops import classifier as ops

    calls = []
    real = ops.train_batch_schema
    monkeypatch.setattr(ops, "train_batch_schema",
                        lambda *a, **k: calls.append(a[2].shape) or real(
                            *a, **k))
    srv, port = _server(True, monkeypatch)
    try:
        with ClassifierClient("127.0.0.1", port, "") as c:
            for seed in range(3):
                assert c.train(_rows(20, seed, True)) == 20
        assert _counts(srv) == {"native": 3, "generic": 0}
        # [rows (bucketed), the base columns and every pair of them]
        wide = N_NUM + N_NUM * (N_NUM - 1) // 2
        assert len(calls) == 3 and all(
            shape[0] == 32 and shape[1] >= wide for shape in calls), calls
        assert srv.rpc.trace.counters()["step.train.plan_schema"] == 3
        assert srv.ingest_stats["sparse_flushes"] == 3
    finally:
        srv.stop()


def test_a_flush_of_both_kinds_of_request_trains_every_row(monkeypatch):
    """Uniform requests and requests of differing schemas that meet in one
    flush are one batch of expanded rows, all of them applied."""
    from jubatus_tpu.client import ClassifierClient

    srv, port = _server(True, monkeypatch)
    try:
        co = srv.coalescers["train_raw"]
        with ClassifierClient("127.0.0.1", port, "") as c:
            c.train(_rows(4, 9, False))
            before = co.stats()["item_count"]
            with srv.driver.lock:   # hold the device stage: calls queue up
                threads = [threading.Thread(
                    target=lambda u=u: ClassifierClient(
                        "127.0.0.1", port, "").train(_rows(10, 20 + u, u % 2)))
                    for u in range(6)]
                for t in threads:
                    t.start()
                time.sleep(1.0)
            for t in threads:
                t.join(60)
            assert co.stats()["item_count"] - before == 60
            assert sum(c.get_labels().values()) == 64
        assert _counts(srv) == {"native": 1 + 6, "generic": 0}
    finally:
        srv.stop()
