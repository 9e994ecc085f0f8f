"""Tracing subsystem tests (SURVEY.md §5: the improvement over the
reference's timing-log-only observability)."""

from __future__ import annotations

import pytest

from jubatus_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh():
    tracing.reset()
    yield
    tracing.reset()


def test_span_aggregates():
    for _ in range(3):
        with tracing.span("unit.op"):
            pass
    st = tracing.trace_status()
    assert st["trace.unit.op.count"] == 3
    assert st["trace.unit.op.mean_ms"] >= 0.0
    assert st["trace.unit.op.max_ms"] >= st["trace.unit.op.mean_ms"]


def test_span_records_on_exception():
    with pytest.raises(ValueError):
        with tracing.span("unit.boom"):
            raise ValueError("x")
    assert tracing.trace_status()["trace.unit.boom.count"] == 1


def test_record_external():
    tracing.record("ext", 0.25)
    st = tracing.trace_status()
    assert st["trace.ext.last_ms"] == 250.0


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation``: a factory of
    scopes that logs what was opened and closed."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        import contextlib

        @contextlib.contextmanager
        def scope():
            self.log.append(("open", name))
            try:
                yield
            finally:
                self.log.append(("close", name))

        return scope()


def test_span_opens_and_closes_the_annotation_it_was_given():
    reg = tracing.Registry()
    reg.annotate = ann = _Annotations()
    with reg.span("unit.outer"):
        with reg.span("unit.inner"):
            pass
    with pytest.raises(ValueError):
        with reg.span("unit.boom"):
            raise ValueError("x")
    assert ann.log == [("open", "unit.outer"), ("open", "unit.inner"),
                       ("close", "unit.inner"), ("close", "unit.outer"),
                       ("open", "unit.boom"), ("close", "unit.boom")]
    assert reg.trace_status()["trace.unit.boom.count"] == 1
    # an annotation alone bills no span
    with reg.annotation("unit.bare"):
        pass
    assert ann.log[-2:] == [("open", "unit.bare"), ("close", "unit.bare")]
    assert "trace.unit.bare.count" not in reg.trace_status()


def test_span_opens_no_annotation_when_given_no_factory():
    reg = tracing.Registry()
    assert reg.annotate is None
    with reg.span("unit.op"), reg.annotation("unit.bare"):
        pass
    assert reg.trace_status()["trace.unit.op.count"] == 1


def test_record_files_the_span_under_the_context_it_is_given():
    """A span measured on one thread for a request of another (a
    ticket's queue wait, measured by the flusher) carries the request's
    trace id; ``ctx=None`` files it under no trace at all."""
    reg = tracing.Registry()
    mine, theirs = tracing.new_root(), tracing.new_root()
    with tracing.use_trace(mine):
        reg.record("unit.own", 0.001)
        reg.record("unit.for_another", 0.002, ctx=theirs)
        reg.record("unit.for_nobody", 0.003, ctx=None)
    assert [r["name"] for r in reg.get_spans(mine.trace_id)] == ["unit.own"]
    (rec,) = reg.get_spans(theirs.trace_id)
    assert rec["name"] == "unit.for_another" and rec["duration_ms"] == 2.0
    assert rec["span_id"] == theirs.span_id
    assert len(reg.recent_spans()) == 2
    assert reg.trace_status()["trace.unit.for_nobody.count"] == 1


def test_importing_tracing_imports_no_jax():
    """Clients import utils.tracing; only the server that owns the chip
    hands the registry jax's annotation factory."""
    import subprocess
    import sys

    code = ("import sys; import jubatus_tpu.utils.tracing; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=120).returncode == 0


def test_slow_log_keeps_the_phases_of_the_request():
    """The span ring turns over in under a second of traffic; the slow
    record of an ``rpc.<m>`` keeps the ``name: ms`` of the spans already
    stored under its trace id."""
    reg = tracing.Registry()
    reg.slowlog.configure(capacity=8, quantile=0.5, min_count=2)
    for _ in range(4):
        with tracing.use_trace(tracing.new_root()):
            reg.record("rpc.classify", 0.001)
    ctx = tracing.new_root()
    with tracing.use_trace(ctx):
        reg.record("fv.convert", 0.002)
        reg.record("fv.convert", 0.001)
        reg.record("microbatch.classify_raw.queue_wait", 2.0, ctx=ctx)
        reg.record("rpc.classify", 2.5)
    slow = [r for r in reg.slowlog.snapshot()
            if r["method"] == "rpc.classify" and r["trace_id"] == ctx.trace_id]
    assert slow and slow[-1]["phases"] == {
        "fv.convert": 3.0, "microbatch.classify_raw.queue_wait": 2000.0}
    # a span that is no request carries none
    assert all("phases" not in r for r in reg.slowlog.snapshot()
               if not r["method"].startswith("rpc."))


def test_rpc_dispatch_records_spans():
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.rpc.server import RpcServer

    srv = RpcServer()
    srv.register("ping", lambda: "pong", arity=0)
    port = srv.serve_background(0, host="127.0.0.1")
    try:
        with RpcClient("127.0.0.1", port) as c:
            assert c.call("ping") == "pong"
        st = srv.trace.trace_status()
        assert st["trace.rpc.ping.count"] == 1
    finally:
        srv.stop()


def test_per_server_span_isolation():
    """Two servers in one process must not merge each other's counters."""
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.rpc.server import RpcServer

    a, b = RpcServer(), RpcServer()
    a.register("hit", lambda: 1, arity=0)
    b.register("hit", lambda: 2, arity=0)
    pa = a.serve_background(0, host="127.0.0.1")
    b.serve_background(0, host="127.0.0.1")
    try:
        with RpcClient("127.0.0.1", pa) as c:
            c.call("hit")
        assert a.trace.trace_status()["trace.rpc.hit.count"] == 1
        assert "trace.rpc.hit.count" not in b.trace.trace_status()
    finally:
        a.stop(), b.stop()


def test_server_status_includes_traces():
    from jubatus_tpu.server import EngineServer

    conf = {"method": "PA", "parameter": {},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    srv = EngineServer("classifier", conf)
    from jubatus_tpu.client import ClassifierClient, Datum

    port = srv.start(0)
    try:
        c = ClassifierClient("127.0.0.1", port, "")
        c.train([["a", Datum({"x": 1.0})]])
        (node_st,) = c.get_status().values()
        assert node_st["trace.rpc.train.count"] >= 1
        c.close()
        # the server owns the chip: its spans open jax's annotations, and
        # its driver records into the same registry
        import jax.profiler

        assert srv.rpc.trace.annotate is jax.profiler.TraceAnnotation
        assert srv.driver.trace is srv.rpc.trace
    finally:
        srv.stop()
