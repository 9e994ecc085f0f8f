"""One request, one timeline (ISSUE 24): the phase spans and counters of
the path from the RPC to the device step — where they are recorded, under
whose trace id, how much of a call and of a flush they cover, that a
driver without a server records none, and that in a device capture they
stand on the profiler's clock with no Python frame beside them."""

from __future__ import annotations

import glob
import threading
import time

import numpy as np
import pytest

from jubatus_tpu.utils import tracing

CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_rules": [{"key": "*", "type": "str", "sample_weight": "bin",
                          "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
        "hash_max_size": 1 << 18,
    },
}

CLASSIFY_STEPS = ["step.classify.stage", "step.classify.lock_wait",
                  "step.classify.dispatch", "step.classify.wait",
                  "classify.encode"]
TRAIN_STEPS = ["step.train.lock_wait", "step.train.stage",
               "step.train.dispatch"]


def _rows(n, seed):
    from jubatus_tpu.client import Datum

    rng = np.random.default_rng(seed)
    return [Datum({f"n{k}": float(rng.normal()) for k in range(12)}
                  | {f"s{k}": f"v{int(rng.integers(1000))}"
                     for k in range(12)}) for _ in range(n)]


@pytest.fixture(scope="module")
def server():
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer(
        "classifier", CONF,
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        quality_sample=0.0))
    port = srv.start(0)
    if "classify_raw" not in srv.coalescers:
        srv.stop()
        pytest.skip("no native ingest here: the raw path is not registered")
    yield srv, port
    srv.stop()


def _lone_call(server, method, n_rows):
    """One call alone in the server, under a trace id of the test's own,
    after a call of the same shape has compiled its programs. Returns the
    server's spans of that trace, summed by name."""
    from jubatus_tpu.client import ClassifierClient

    srv, port = server
    with ClassifierClient("127.0.0.1", port, "") as c:
        def call(seed):
            rows = _rows(n_rows, seed)
            if method == "train":
                assert c.train([("pos" if i % 2 else "neg", d)
                                for i, d in enumerate(rows)]) == n_rows
            else:
                assert len(c.classify(rows)) == n_rows

        if method == "classify":
            c.train([("pos", _rows(1, 5)[0]), ("neg", _rows(1, 6)[0])])
        call(1)
        ctx = tracing.new_root()
        with tracing.use_trace(ctx):
            call(2)
    by_name = {}
    for r in srv.rpc.trace.get_spans(ctx.trace_id):
        by_name.setdefault(r["name"], []).append(r["duration_ms"])
    return {k: sum(v) for k, v in by_name.items()}, by_name


def _undisturbed(server, method, n_rows, covered):
    """The spans of a lone call whose phases came to ``covered`` of the
    whole. What no phase covers is a thread waiting to be woken (the
    interpreter hands the lock over every 5 ms at worst) and the
    machine's other work, so a call that the tests' neighbours disturbed
    is taken again, a few times."""
    for _ in range(5):
        ms, by_name = _lone_call(server, method, n_rows)
        if covered(ms) >= 0.9:
            break
    return ms, by_name


@pytest.mark.parametrize("method,queue", [
    ("classify", "classify_raw"), ("train", "train_raw")])
def test_a_lone_calls_phases_carry_its_trace_id_and_cover_it(
        server, method, queue):
    phases = ["fv.convert", f"microbatch.{queue}.queue_wait",
              f"microbatch.{queue}.flush_wait"]

    def covered(ms):
        return sum(ms[p] for p in phases) / ms[f"rpc.{method}"]

    ms, _ = _undisturbed(server, method, 4000, covered)
    assert set(phases) <= set(ms), sorted(ms)
    assert covered(ms) >= 0.9, ms
    # it found the coalescer idle, so its own thread served as the flusher
    assert f"microbatch.{queue}.flusher_turn" in ms
    assert ms[f"microbatch.{queue}.queue_wait"] < 0.1 * ms[f"rpc.{method}"]


def test_a_lone_classifys_step_phases_cover_its_device_stage(server):
    def covered(ms):
        return sum(ms[p] for p in CLASSIFY_STEPS) \
            / ms["microbatch.classify_raw.device_stage"]

    ms, by_name = _undisturbed(server, "classify", 4000, covered)
    assert set(CLASSIFY_STEPS) <= set(ms), sorted(ms)
    # the answer's rows are built once, in the driver: one record a flush
    assert len(by_name["classify.encode"]) == 1
    assert covered(ms) >= 0.9, ms
    assert ms["microbatch.classify_raw.device_stage"] \
        <= ms["microbatch.classify_raw.flush_wait"] * 1.05 + 0.1


def test_the_drivers_rows_are_the_answer_on_the_wire(server):
    """The service hands the driver's rows of (label, score) tuples to the
    packer as they are: the bytes are those of lists of [label, score],
    in both wire formats, and a client reads what it always read."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.rpc.server import build_response

    srv, port = server
    rows = [[("neg", -0.25), ("pos", 1.5)], [("neg", 0.0), ("pos", 2.0)]]
    as_lists = [[[lab, s] for lab, s in row] for row in rows]
    for legacy in (False, True):
        assert build_response(7, None, rows, legacy=legacy) \
            == build_response(7, None, as_lists, legacy=legacy)
    with ClassifierClient("127.0.0.1", port, "") as c:
        c.train([("pos", _rows(1, 5)[0]), ("neg", _rows(1, 6)[0])])
        out = c.classify(_rows(3, 9))
    assert len(out) == 3
    for row in out:
        assert sorted(e[0] for e in row) == ["neg", "pos"]
        assert all(isinstance(e[1], float) for e in row)


def test_train_steps_are_recorded_on_the_device_workers_thread(server):
    """A train flush belongs to many requests: its step phases are
    recorded under no trace id, and counted."""
    srv, _port = server
    before = srv.rpc.trace.trace_status()
    c0 = srv.rpc.trace.counters()
    ms, _ = _lone_call(server, "train", 600)
    assert not set(TRAIN_STEPS) & set(ms)
    after = srv.rpc.trace.trace_status()
    for name in TRAIN_STEPS + ["microbatch.train_raw.device_stage"]:
        assert after[f"trace.{name}.count"] \
            - before.get(f"trace.{name}.count", 0) == 2, name
    c1 = srv.rpc.trace.counters()
    gain = {k: c1[k] - c0.get(k, 0) for k in c1 if k.startswith("step.train")}
    assert gain["step.train.rows"] == 1200
    assert gain["step.train.rows_padded"] == 2 * 1024
    st = next(iter(srv.get_status().values()))
    assert sum(st[f"microbatch.train_raw.flushes_fill_{k}"]
               for k in range(9)) == st["microbatch.train_raw.flush_count"]


def test_tokens_terms_widths_and_a_label_growth_are_counted_where_they_happen(
        server):
    """The parser counts the tokens its string rules cut and the distinct
    terms that became entries as it goes (12 whole string values a row
    here); a classify flush counts its width as a train flush does; nine
    labels more than the model's 8 rows make the tables grow once, on the
    device worker's thread, under no request's trace id, as one
    ``model.grow_labels`` span and one count, with the gauges beside."""
    from jubatus_tpu.client import ClassifierClient

    srv, port = server
    reg = srv.rpc.trace
    c0, st0 = reg.counters(), reg.trace_status()
    ms, _ = _lone_call(server, "classify", 30)       # two calls of 30 rows
    ms_t, _ = _lone_call(server, "train", 50)        # two calls of 50
    c1 = reg.counters()
    gain = {k: c1[k] - c0.get(k, 0) for k in c1}
    assert gain["fv.tokens"] == gain["fv.terms"] == 12 * (2 * 30 + 2 * 50 + 2)
    assert gain.get("fv.pack.pow2", 0) == 0          # rows alike: the rung
    assert gain["step.classify.width_24"] == 2
    assert gain["step.train.width_24"] >= 2
    assert "model.grow_labels" not in ms and "model.grow_labels" not in ms_t
    ctx = tracing.new_root()
    with ClassifierClient("127.0.0.1", port, "") as c, tracing.use_trace(ctx):
        assert c.train([(f"label{i}", d)
                        for i, d in enumerate(_rows(9, 3))]) == 9
    names = {r["name"] for r in reg.get_spans(ctx.trace_id)}
    assert "rpc.train" in names and "model.grow_labels" not in names
    st1 = reg.trace_status()
    assert st1["trace.model.grow_labels.count"] \
        - st0.get("trace.model.grow_labels.count", 0) == 1
    assert reg.counters()["model.label_grow"] \
        - c0.get("model.label_grow", 0) == 1
    assert reg.gauges()["model.label_capacity"] == 16
    assert reg.gauges()["model.labels_live"] == 11
    status = next(iter(srv.get_status().values()))
    assert status["driver.label_capacity"] == 16


def test_the_quality_planes_scoring_shows_under_the_train_calls_trace():
    """Every admitted train call scores a few rows with the current model
    before it is queued: that wait for the device is a ``step.classify.*``
    under the TRAIN call's trace id."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer(
        "classifier", CONF,
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        quality_sample=1.0))
    port = srv.start(0)
    try:
        if "train_raw" not in srv.coalescers:
            pytest.skip("no native ingest here")
        with ClassifierClient("127.0.0.1", port, "") as c:
            c.train([("pos", _rows(1, 5)[0]), ("neg", _rows(1, 6)[0])])
            ctx = tracing.new_root()
            with tracing.use_trace(ctx):
                c.train([("pos", d) for d in _rows(40, 7)])
        names = {r["name"] for r in srv.rpc.trace.get_spans(ctx.trace_id)}
        assert {"rpc.train", "step.classify.wait",
                "step.classify.lock_wait"} <= names, sorted(names)
    finally:
        srv.stop()


def test_a_driver_without_a_server_records_nothing():
    from jubatus_tpu.models.classifier import ClassifierDriver

    d = ClassifierDriver(CONF, dim_bits=14)
    assert d.trace is None
    idx = np.arange(1, 33, dtype=np.int32).reshape(4, 8)
    val = np.ones((4, 8), np.float32)
    assert d.train_hashed(["a", "b", "a", "b"], idx, val) == 4
    assert len(d.classify_hashed(idx, val)) == 4
    # handed a registry, the same calls record their phases
    d.trace = reg = tracing.Registry()
    d.train_hashed(["a", "b", "a", "b"], idx, val)
    d.classify_hashed(idx, val)
    st = reg.trace_status()
    for name in ["step.train.stage", "step.train.dispatch"] + CLASSIFY_STEPS:
        assert st[f"trace.{name}.count"] == 1, name
    assert reg.counters()["step.classify.rows"] == 4
    assert reg.counters()["step.classify.rows_padded"] == 16


@pytest.mark.parametrize("plan", ["schema", "columns"])
def test_the_driver_settles_the_train_plan_from_its_rows(plan):
    """train_indexed takes the dense plan where every row carries the
    same index row and the sparse one otherwise; both go through the one
    tail, so they record the same phases."""
    from jubatus_tpu.models.classifier import ClassifierDriver

    d = ClassifierDriver(CONF, dim_bits=14)
    d.trace = reg = tracing.Registry()
    idx = np.tile(np.arange(1, 7, dtype=np.int32), (3, 1))
    if plan != "schema":
        idx[2, 0] = 9
    val = np.ones((3, 6), np.float32)
    lidx = np.array([0, 1, 0], np.int32)
    assert d.train_indexed(["a", "b"], lidx, idx, val) == 3
    assert len(d.classify_hashed(idx, val)) == 3
    st = reg.trace_status()
    for name in ["step.train.stage", "step.train.dispatch"] + CLASSIFY_STEPS:
        assert st[f"trace.{name}.count"] == 1, name
    c = reg.counters()
    assert (c["step.train.rows"], c["step.train.rows_padded"]) == (3, 16)
    assert [k for k in c if k.startswith("step.train.plan_")] == \
        [f"step.train.plan_{plan}"]


def test_named_scopes_are_in_the_lowered_programs():
    import jax.numpy as jnp

    from jubatus_tpu.ops import classifier as ops

    state = ops.init_state(8, 1 << 10, True)
    idx = jnp.zeros((16, 4), jnp.int32)
    val = jnp.zeros((16, 4), jnp.float32)
    labels = jnp.zeros((16,), jnp.int32)
    mask = jnp.ones((8,), bool)
    text = ops.train_batch_parallel.lower(
        state, idx, val, labels, mask, 1.0, method="AROW").as_text(
            debug_info=True)
    for scope in ("pack", "gather", "margin", "scatter"):
        assert f"jit(train_batch_parallel)/{scope}/" in text, scope
    text = ops.scores.lower(state, idx, val, mask).as_text(debug_info=True)
    assert "jit(scores)/scores/" in text


def test_peak_bytes_in_use_is_sampled_beside_bytes_in_use(monkeypatch):
    import jax

    from jubatus_tpu.utils import runtime_telemetry as rt

    class Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    jax.devices()       # the sample reads devices only of a live backend
    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 10, "peak_bytes_in_use": 70}),
        Dev({"bytes_in_use": 5, "peak_bytes_in_use": 30})])
    out = rt._jax_sample()
    # a device's bytes are the fullest local device's; the sum is beside it
    assert out["jax_device_bytes_in_use"] == 10
    assert out["jax_device_peak_bytes_in_use"] == 70
    assert out["jax_devices_bytes_in_use_total"] == 15
    assert out["jax_devices_peak_bytes_in_use_total"] == 100
    # a backend that reports nothing (the CPU) leaves the keys out
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(None)])
    out = rt._jax_sample()
    assert not [k for k in out if "bytes_in_use" in k]
    reg = tracing.Registry()
    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev({"bytes_in_use": 1, "peak_bytes_in_use": 2})])
    # on one device every reading is what the sum was
    sample = rt.RuntimeTelemetry(reg, interval_sec=0).sample()
    assert sample["jax_device_peak_bytes_in_use"] \
        == sample["jax_devices_peak_bytes_in_use_total"] == 2
    assert sample["jax_device_bytes_in_use"] == 1
    assert reg.gauges()["jax_device_peak_bytes_in_use"] == 2.0


def test_a_capture_holds_the_programs_spans_and_no_python_frame(tmp_path):
    """``DeviceCapture`` turns the Python tracer off: the host plane holds
    the registry's annotations (and the runtime's own events), so an idle
    gap is charged to a span's name, never to a Python frame."""
    import jax
    import jax.profiler

    from jubatus_tpu.utils.profiler import DeviceCapture, SamplingProfiler

    reg = tracing.Registry()
    reg.annotate = jax.profiler.TraceAnnotation
    prof = SamplingProfiler(reg, hz=0)
    stop = threading.Event()

    def work():
        while not stop.is_set():
            with reg.span("unit.phase"):
                time.sleep(0.005)
            prof.sample_once()

    th = threading.Thread(target=work, daemon=True)
    th.start()
    try:
        doc = DeviceCapture(str(tmp_path / "prof")).capture(0.4)
    finally:
        stop.set()
        th.join(10)
    if "error" in doc:
        pytest.skip(f"no profiler backend here: {doc['error']}")
    (path,) = glob.glob(doc["artifact"] + "/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes if plane.name.startswith("/host:CPU")
        for line in plane.lines for e in line.events}
    assert {"unit.phase", "profiler.sample_once"} <= names
    # the Python tracer's events are "$file.py:line function"
    assert not any(n.startswith("$") for n in names), sorted(names)[:20]
    assert reg.trace_status()["trace.unit.phase.count"] >= 10
    assert "trace.profiler.sample_once.count" not in reg.trace_status()
