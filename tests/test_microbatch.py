"""Microbatch coalescer tests (server/microbatch.py) — unit-level queue
semantics plus a live-server test showing concurrent train RPCs really
merge into fewer device flushes with no lost or double-counted items."""

from __future__ import annotations

import threading
import time

import pytest

from jubatus_tpu.server.microbatch import Coalescer


def test_lone_submit_is_passthrough():
    seen = []
    co = Coalescer(lambda b: (seen.append(list(b)), len(b))[1])
    assert co.submit([1, 2, 3]) == 3
    assert seen == [[1, 2, 3]]
    assert co.stats()["flush_count"] == 1


def test_empty_submit():
    co = Coalescer(lambda b: len(b))
    assert co.submit([]) == 0
    assert co.stats()["flush_count"] == 0


def test_concurrent_submits_coalesce_and_conserve():
    flushed = []
    gate = threading.Event()

    def flush(batch):
        if not gate.is_set():   # first flush blocks so the rest pile up
            gate.set()
            time.sleep(0.15)
        flushed.append(list(batch))
        return len(batch)

    co = Coalescer(flush)
    results = []

    def worker(base):
        results.append(co.submit([base * 10 + j for j in range(3)]))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
        time.sleep(0.005)
    for t in threads:
        t.join()

    all_items = [x for b in flushed for x in b]
    assert sorted(all_items) == sorted(i * 10 + j
                                       for i in range(12) for j in range(3))
    assert len(all_items) == 36
    # piling up must have produced real coalescing
    assert len(flushed) < 12
    assert co.stats()["item_count"] == 36
    assert max(len(b) for b in flushed) > 3


def test_max_batch_splits():
    sizes = []
    gate = threading.Event()

    def slow_first(batch):
        if not gate.is_set():
            gate.set()
            time.sleep(0.1)
        sizes.append(len(batch))

    co = Coalescer(slow_first, max_batch=5)
    threads = [threading.Thread(target=co.submit, args=([j, j, j],))
               for j in range(6)]
    for t in threads:
        t.start()
        time.sleep(0.005)
    for t in threads:
        t.join()
    assert sum(sizes) == 18
    assert all(s <= 5 or s == 3 for s in sizes)  # ≤ max, except lone-first


def test_oversized_single_submit_flushes_alone():
    sizes = []
    co = Coalescer(lambda b: sizes.append(len(b)), max_batch=4)
    co.submit(list(range(10)))
    assert sizes == [10]


def test_error_propagates_to_contributors_only():
    def flush(batch):
        if "bad" in batch:
            raise RuntimeError("poison")
        return len(batch)

    co = Coalescer(flush)
    with pytest.raises(RuntimeError, match="poison"):
        co.submit(["bad"])
    assert co.submit(["ok"]) == 1  # queue recovers after a failed flush


def test_timeout_withdraws_queued_items():
    """A timed-out submit whose items are still queued withdraws them —
    TimeoutError then guarantees the model was NOT updated."""
    gate = threading.Event()
    release = threading.Event()

    def flush(batch):
        gate.set()
        release.wait(5)
        return len(batch)

    co = Coalescer(flush)
    t = threading.Thread(target=co.submit, args=([1],))
    t.start()
    assert gate.wait(2)
    with pytest.raises(TimeoutError, match="NOT updated"):
        co.submit([2], timeout=0.1)
    release.set()
    t.join()
    assert co.stats()["item_count"] == 1  # withdrawn item never flushed


def test_zero_timeout_means_wait_forever():
    co = Coalescer(lambda b: len(b))
    assert co.submit([1, 2], timeout=0) == 2


@pytest.mark.slow
def test_server_train_rpcs_coalesce():
    """N concurrent clients training against one server: every example
    lands exactly once and the device saw fewer flushes than RPCs."""
    from jubatus_tpu.client import ClassifierClient, Datum
    from jubatus_tpu.server import EngineServer

    conf = {
        "method": "PA",
        "parameter": {},
        "converter": {"num_rules": [{"key": "*", "type": "num"}]},
    }
    srv = EngineServer("classifier", conf)
    port = srv.start(0)
    try:
        n_clients, per_client = 8, 5

        def client_work(ci):
            with ClassifierClient("127.0.0.1", port, "mb") as c:
                for j in range(per_client):
                    lbl = "pos" if (ci + j) % 2 == 0 else "neg"
                    got = c.train([(lbl, Datum({"x": float(ci - j)})),
                                   (lbl, Datum({"x": float(j - ci)}))])
                    assert got == 2

        threads = [threading.Thread(target=client_work, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        total = n_clients * per_client * 2
        assert srv.driver.update_count == total
        st = next(iter(srv.get_status().values()))
        # train traffic flows through the native-ingest fast coalescer
        # when eligible (train_raw), the converter path otherwise — the
        # combined counters must account for every example either way
        items = (st["microbatch.train.item_count"]
                 + st.get("microbatch.train_raw.item_count", 0))
        flushes = (st["microbatch.train.flush_count"]
                   + st.get("microbatch.train_raw.flush_count", 0))
        assert items == total
        assert flushes <= n_clients * per_client
        # model still serves
        with ClassifierClient("127.0.0.1", port, "mb") as c:
            assert len(c.classify([Datum({"x": 1.0}).to_msgpack()])) == 1
    finally:
        srv.stop()


def test_split_results_each_ticket_gets_its_slice():
    """Query-plane mode: the flush returns per-item results and every
    submitter receives exactly its own rows, under real concurrency."""
    import threading

    from jubatus_tpu.server.microbatch import Coalescer

    def flush(items):
        return [f"r{x}" for x in items]

    co = Coalescer(flush, max_batch=64, split_results=True)
    out = {}
    barrier = threading.Barrier(8)

    def worker(k):
        barrier.wait()
        out[k] = co.submit([k * 10 + j for j in range(3)])

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for k in range(8):
        assert out[k] == [f"r{k * 10 + j}" for j in range(3)], out[k]


def test_split_results_wrong_length_surfaces_error():
    from jubatus_tpu.server.microbatch import Coalescer

    co = Coalescer(lambda items: ["only-one"], max_batch=8,
                   split_results=True)
    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="split flush returned"):
        co.submit(["a", "b"])


# -- PipelinedCoalescer (ISSUE 5: host/device overlap) -----------------------

def test_pipelined_basic_result_delivery():
    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    preps, flushes = [], []

    def prep(items):
        preps.append(list(items))
        return [x * 2 for x in items]

    def flush(prepared):
        flushes.append(list(prepared))
        return sum(prepared)

    co = PipelinedCoalescer(prep, flush, max_batch=64)
    assert co.submit([1, 2, 3]) == 12
    assert preps == [[1, 2, 3]] and flushes == [[2, 4, 6]]
    st = co.stats()
    assert st["flush_count"] == 1 and st["item_count"] == 3
    assert "overlap_fraction" in st


def test_pipelined_overlaps_prep_with_device():
    """While the device worker sleeps on batch N, the flusher must prep
    batch N+1 — overlap_seconds > 0 proves the stages really ran
    concurrently."""
    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    order = []

    def prep(items):
        order.append(("prep", tuple(items)))
        time.sleep(0.05)
        return items

    def flush(prepared):
        order.append(("flush", tuple(prepared)))
        time.sleep(0.1)
        return len(prepared)

    co = PipelinedCoalescer(prep, flush, max_batch=4)
    results = []
    threads = [threading.Thread(
        target=lambda i=i: results.append(co.submit([i])))
        for i in range(6)]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join()
    assert len(results) == 6
    st = co.stats()
    assert st["item_count"] == 6
    assert st["device_seconds"] > 0
    assert st["overlap_seconds"] > 0  # prep ran under an active flush
    assert 0 < st["overlap_fraction"] <= 1.0


def test_pipelined_prep_error_fails_only_that_batch():
    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    def prep(items):
        if any(x < 0 for x in items):
            raise ValueError("bad featurize")
        return items

    co = PipelinedCoalescer(prep, lambda p: len(p), max_batch=64)
    with pytest.raises(ValueError, match="bad featurize"):
        co.submit([-1])
    assert co.submit([1, 2]) == 2  # queue recovered
    assert co.stats()["flush_count"] == 2


def test_pipelined_device_error_propagates():
    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    def flush(prepared):
        raise RuntimeError("device on fire")

    co = PipelinedCoalescer(lambda i: i, flush, max_batch=64)
    with pytest.raises(RuntimeError, match="device on fire"):
        co.submit([1])
    # a later submit still works end to end after the error
    co2_calls = []
    co._flush = lambda p: (co2_calls.append(p), len(p))[1]
    assert co.submit([5, 6]) == 2


def test_pipelined_stamps_fv_spans():
    from jubatus_tpu.server.microbatch import PipelinedCoalescer
    from jubatus_tpu.utils.tracing import Registry

    reg = Registry()
    co = PipelinedCoalescer(lambda i: i, lambda p: len(p),
                            max_batch=64, trace=reg)
    assert co.submit([1, 2]) == 2
    status = reg.trace_status()
    assert any(k.startswith("trace.fv.convert.") for k in status)
    assert any(k.startswith("trace.microbatch..device_stage.")
               for k in status)


def _piled_up(make, n=6):
    """``n`` submitters of two items each, every one under a trace
    context of its own, against a coalescer whose first flush blocks so
    that the rest pile up. Returns (registry, coalescer, contexts)."""
    from jubatus_tpu.utils import tracing

    reg = tracing.Registry()
    gate = threading.Event()

    def flush(batch):
        if not gate.is_set():
            gate.set()
            time.sleep(0.15)
        return list(batch)

    co = make(flush, reg)
    ctxs = [tracing.new_root() for _ in range(n)]

    def worker(i):
        with tracing.use_trace(ctxs[i]):
            co.submit([i, i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    return reg, co, ctxs


@pytest.mark.parametrize("kind", ["single", "pipelined"])
def test_phase_spans_per_ticket_per_turn_per_flush(kind):
    """queue_wait and flush_wait once per ticket under the TICKET's
    trace id (the flusher measures them on its own thread), flusher_turn
    once per turn under the flusher's, device_stage once per flush."""
    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    def make(flush, reg):
        if kind == "single":
            return Coalescer(flush, max_batch=8, split_results=True,
                             trace=reg, name="q")
        return PipelinedCoalescer(lambda items: items, flush, max_batch=8,
                                  trace=reg, name="q")

    reg, co, ctxs = _piled_up(make)
    st = reg.trace_status()
    flushes = co.stats()["flush_count"]
    assert 1 < flushes < len(ctxs)          # they did pile up
    assert st["trace.microbatch.q.queue_wait.count"] == len(ctxs)
    assert st["trace.microbatch.q.flush_wait.count"] == len(ctxs)
    assert st["trace.microbatch.q.device_stage.count"] == flushes
    turns = st["trace.microbatch.q.flusher_turn.count"]
    assert 1 <= turns <= flushes
    waited = []
    for ctx in ctxs:
        spans = {r["name"]: r for r in reg.get_spans(ctx.trace_id)}
        assert {"microbatch.q.queue_wait",
                "microbatch.q.flush_wait"} <= set(spans)
        assert all(r["span_id"] == ctx.span_id for r in spans.values())
        waited.append(spans["microbatch.q.queue_wait"]["duration_ms"])
    # the first ticket was claimed at once; those behind its 150 ms flush
    # waited in the queue for most of it
    assert waited[0] < 50 and max(waited) > 80
    # a turn belongs to the submitter whose thread served as the flusher
    turn_ids = {r["trace_id"] for r in reg.recent_spans()
                if r["name"] == "microbatch.q.flusher_turn"}
    assert turn_ids and turn_ids <= {c.trace_id for c in ctxs}
    if kind == "pipelined":
        # the device worker's thread has no request of its own
        assert not any(r["name"] == "microbatch.q.device_stage"
                       for r in reg.recent_spans())


def test_flush_fill_histogram_adds_up_to_flush_count():
    sizes = iter([1, 100, 4000, 7000, 8000, 8192, 9000])
    co = Coalescer(lambda b: len(b), max_batch=8192,
                   weigher=lambda item: item)
    for n in sizes:
        co.submit([n])
    st = co.stats()
    fill = [st[f"flushes_fill_{k}"] for k in range(9)]
    assert sum(fill) == st["flush_count"] == 7
    # floor(8 x rows / max_batch): 8,000 of 8,192 is k = 7; a full claim
    # and an oversized lone submit are k = 8
    assert fill == [2, 0, 0, 1, 0, 0, 1, 1, 2]


@pytest.mark.parametrize("kind", ["single", "pipelined"])
def test_a_registry_that_raises_costs_no_answer(kind):
    """The per-ticket records run ahead of the tickets' events (and of
    the device slot's release): a registry that fails must not leave a
    submitter waiting, nor the device worker dead with the slot."""
    from jubatus_tpu.server.microbatch import PipelinedCoalescer
    from jubatus_tpu.utils.tracing import Registry

    class Broken(Registry):
        def record_each(self, name, records):
            raise RuntimeError("registry on fire")

    if kind == "single":
        co = Coalescer(lambda b: len(b), max_batch=8, trace=Broken(),
                       name="q")
    else:
        co = PipelinedCoalescer(lambda items: items, lambda p: len(p),
                                max_batch=8, trace=Broken(), name="q")
    for n in (1, 2, 3):     # three flushes: the slot came back each time
        assert co.submit(list(range(n)), timeout=5.0) == n
    assert co.stats()["flush_count"] == 3


def test_pipelined_weigher_bounds_examples():
    """max_batch counts examples via the weigher, exactly like the
    single-stage coalescer."""
    import numpy as np

    from jubatus_tpu.server.microbatch import PipelinedCoalescer

    sizes = []

    def prep(items):
        return items

    def flush(prepared):
        sizes.append(sum(a.shape[0] for a in prepared))
        return sizes[-1]

    gate = threading.Event()

    def slow_first_flush(prepared):
        if not gate.is_set():
            gate.set()
            time.sleep(0.1)
        return flush(prepared)

    co = PipelinedCoalescer(prep, slow_first_flush, max_batch=8,
                            weigher=lambda a: a.shape[0])
    threads = [threading.Thread(
        target=lambda: co.submit([np.zeros((4, 2))]))
        for _ in range(6)]
    for t in threads:
        t.start()
        time.sleep(0.005)
    for t in threads:
        t.join()
    assert sum(sizes) == 24
    assert all(s <= 8 for s in sizes)


# -- backpressure gauges (ISSUE 12): the autoscaler's primary signal ---------

def test_queue_depth_and_arrival_rate_in_stats():
    release = threading.Event()
    started = threading.Event()

    def blocking_flush(batch):
        started.set()
        release.wait(5.0)
        return len(batch)

    co = Coalescer(blocking_flush, max_batch=4)
    t1 = threading.Thread(target=lambda: co.submit([1, 2]))
    t1.start()
    assert started.wait(5.0)
    # the flusher claimed its own items: queue is empty while it runs
    assert co.queue_depth() == 0
    t2 = threading.Thread(target=lambda: co.submit([3, 4, 5]))
    t2.start()
    deadline = time.monotonic() + 5.0
    while co.queue_depth() != 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    st = co.stats()
    assert st["queue_depth"] == 3          # queued behind the flush
    assert st["arrival_per_sec"] > 0.0     # 5 examples just arrived
    release.set()
    t1.join()
    t2.join()
    st = co.stats()
    assert st["queue_depth"] == 0          # drained back to idle
    assert co.queue_depth() == 0


def test_queue_depth_uses_weigher_examples():
    release = threading.Event()
    started = threading.Event()

    def blocking_flush(batch):
        started.set()
        release.wait(5.0)
        return len(batch)

    co = Coalescer(blocking_flush, max_batch=100,
                   weigher=lambda item: item["n"])
    t1 = threading.Thread(target=lambda: co.submit([{"n": 10}]))
    t1.start()
    assert started.wait(5.0)
    t2 = threading.Thread(target=lambda: co.submit([{"n": 7}, {"n": 5}]))
    t2.start()
    deadline = time.monotonic() + 5.0
    while co.queue_depth() != 12 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert co.queue_depth() == 12          # examples, not items
    release.set()
    t1.join()
    t2.join()


def test_timeout_withdrawal_returns_queue_depth():
    release = threading.Event()
    started = threading.Event()

    def blocking_flush(batch):
        started.set()
        release.wait(5.0)
        return len(batch)

    co = Coalescer(blocking_flush, max_batch=2)
    t1 = threading.Thread(target=lambda: co.submit([1, 2]))
    t1.start()
    assert started.wait(5.0)
    with pytest.raises(TimeoutError):
        co.submit([3, 4], timeout=0.05)
    assert co.queue_depth() == 0           # withdrawn items left no ghost
    release.set()
    t1.join()


def test_server_gauges_microbatch_signals(tmp_path):
    """The telemetry tick gauges microbatch.queue_depth /
    microbatch.arrival_per_sec into the server registry (-> /metrics,
    timeseries ring — what the autoscaler polls)."""
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.client import Datum
    from jubatus_tpu.rpc.client import RpcClient

    conf = {"method": "PA", "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    srv = EngineServer(
        "classifier", conf,
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        telemetry_interval=0.0, datadir=str(tmp_path)))
    try:
        port = srv.start(0)
        with RpcClient("127.0.0.1", port, timeout=30.0) as c:
            c.call("train", "",
                   [["a", Datum({"f0": 1.0}).to_msgpack()]])
        srv._model_health_tick()
        g = srv.rpc.trace.gauges()
        assert g.get("microbatch.queue_depth") == 0.0
        assert "microbatch.arrival_per_sec" in g
        st = next(iter(srv.get_status().values()))
        mb = [k for k in st if k.startswith("microbatch.")
              and k.endswith(".queue_depth")]
        assert mb, "per-coalescer queue_depth missing from get_status"
    finally:
        srv.stop()
