"""Fault-injection tests (utils/faults.py) — deterministic failures in
the RPC/mix planes, exercising the tolerance paths SURVEY.md §5 lists
(mix skips failed hosts, aborts only when all fail, demotes on put_diff
failure) that the reference could only probe by killing processes."""

from __future__ import annotations

import time

import pytest

from jubatus_tpu.client import ClassifierClient, Datum
from jubatus_tpu.coord import membership
from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
from jubatus_tpu.rpc.errors import RpcError
from jubatus_tpu.server import EngineServer
from jubatus_tpu.server.args import ServerArgs
from jubatus_tpu.utils import faults

CONF = {
    "method": "PA",
    "parameter": {"regularization_weight": 1.0},
    "converter": {"num_rules": [{"key": "*", "type": "num"}]},
}
NAME = "chaos"


# ---------------------------------------------------------------- registry --
def test_rule_parsing_and_matching():
    r = faults.parse_rule("rpc.call.mix_get_diff.*:error@2")
    assert r.pattern == "rpc.call.mix_get_diff.*"
    assert r.action == "error" and r.remaining == 2
    r = faults.parse_rule("coord.*:delay:0.25")
    assert r.action == "delay" and r.arg == 0.25
    with pytest.raises(ValueError):
        faults.parse_rule("no-action")
    with pytest.raises(ValueError):
        faults.parse_rule("site:explode")


def test_fire_noop_when_disarmed():
    faults.fire("anything.at.all")  # must not raise


def test_armed_scope_and_count_limit():
    # the fired counts are the process's: another file's test may have
    # fired this site in the same worker before
    before = faults.stats().get("x.y", 0)
    with faults.armed("x.y:error@2"):
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.y")
        with pytest.raises(faults.FaultInjected):
            faults.fire("x.y")
        faults.fire("x.y")  # budget exhausted
        assert faults.stats()["x.y"] - before == 2
    faults.fire("x.y")  # disarmed on exit


def test_delay_rule():
    with faults.armed("slow.*:delay:0.05"):
        t0 = time.monotonic()
        faults.fire("slow.op")
        assert time.monotonic() - t0 >= 0.05


# ------------------------------------------------------------- mix chaos ---
def _cluster(n, store, mixer="linear_mixer"):
    servers = []
    for _ in range(n):
        args = ServerArgs(
            engine="classifier", coordinator="(shared)", name=NAME,
            mixer=mixer, listen_addr="127.0.0.1", interval_sec=1e9,
            interval_count=1 << 30,
        )
        srv = EngineServer("classifier", CONF, args,
                           coord=MemoryCoordinator(store))
        srv.start(0)
        servers.append(srv)
    return servers


@pytest.fixture()
def cluster():
    store = _Store()
    servers = _cluster(3, store)
    clients = [ClassifierClient("127.0.0.1", s.args.rpc_port, NAME)
               for s in servers]
    yield servers, clients, store
    faults.disarm_all()
    for c in clients:
        c.close()
    for s in servers:
        s.stop()


def _train_disjoint(clients):
    for _ in range(10):
        clients[0].train([["pos", Datum({"x": 1.0})]])
        clients[1].train([["neg", Datum({"x": -1.0})]])


@pytest.mark.slow
def test_mix_survives_one_get_diff_failure(cluster):
    """One member's diff pull fails: the round proceeds with the rest
    (linear_mixer.cpp:470-504 — abort only if ALL fail)."""
    servers, clients, _ = cluster
    _train_disjoint(clients)
    port1 = servers[1].args.rpc_port
    with faults.armed(f"rpc.call.mix_get_diff.*:{port1}:error@1"):
        assert clients[2].do_mix() is True
    # node 1's contribution was skipped this round, node 0's landed
    labels2 = clients[2].get_labels()
    assert "pos" in labels2
    # the next, fault-free round folds node 1 back in
    assert clients[2].do_mix() is True
    assert set(clients[2].get_labels()) == {"pos", "neg"}


@pytest.mark.slow
def test_mix_aborts_when_all_get_diffs_fail(cluster):
    servers, clients, _ = cluster
    _train_disjoint(clients)
    with faults.armed("rpc.call.mix_get_diff.*:error"):
        assert clients[2].do_mix() is False
    # phase-1 schema sync precedes get_diff, so label NAMES may have
    # propagated — but no diff was applied: all counts are zero
    assert all(v == 0 for v in clients[2].get_labels().values())
    assert clients[2].do_mix() is True    # recovers once faults clear
    labels = clients[2].get_labels()
    assert set(labels) == {"pos", "neg"}
    assert sum(labels.values()) > 0


@pytest.mark.slow
def test_put_diff_failure_demotes_then_recovers(cluster):
    """A member that misses the broadcast is demoted from actives by the
    master (linear_mixer.cpp:658-681) and promotes itself after the next
    successful round."""
    servers, clients, store = cluster
    _train_disjoint(clients)
    view = MemoryCoordinator(store)
    port1 = servers[1].args.rpc_port

    def active_ports():
        return {n.port for n in membership.get_all_actives(
            view, "classifier", NAME)}

    # a successful round first, so everyone is active
    assert clients[2].do_mix() is True
    assert port1 in active_ports()

    with faults.armed(f"rpc.call.mix_put_diff.*:{port1}:error@1"):
        assert clients[2].do_mix() is True
    assert port1 not in active_ports()

    # node 1 missed the broadcast but doesn't KNOW yet — the next round's
    # put_diff (base ahead of its version) marks it obsolete and starts
    # async full-model recovery (linear_mixer.cpp:404-424,644-652)
    assert clients[2].do_mix() is True
    assert port1 not in active_ports()  # still stale this round
    deadline = time.time() + 10
    while time.time() < deadline and \
            servers[1].mixer.model_version < servers[2].mixer.model_version:
        time.sleep(0.1)
    assert servers[1].mixer.model_version == servers[2].mixer.model_version

    # recovered: the round after promotes it back into actives
    assert clients[2].do_mix() is True
    assert port1 in active_ports()


@pytest.mark.slow
def test_mix_completes_under_injected_latency(cluster):
    servers, clients, _ = cluster
    _train_disjoint(clients)
    with faults.armed("rpc.call.mix_get_diff.*:delay:0.1"):
        t0 = time.monotonic()
        assert clients[2].do_mix() is True
        assert time.monotonic() - t0 >= 0.1
    assert set(clients[2].get_labels()) == {"pos", "neg"}


@pytest.mark.slow
def test_client_sees_connect_fault_as_io_error(cluster):
    """Injected connect faults surface through the SAME taxonomy a real
    refused connection would (RpcIoError), so callers' error handling is
    exercised faithfully."""
    servers, _, _ = cluster
    port = servers[0].args.rpc_port
    with faults.armed(f"rpc.connect.*:{port}:error"):
        c = ClassifierClient("127.0.0.1", port, NAME)
        try:
            with pytest.raises(RpcError):
                c.get_status()
        finally:
            c.close()


@pytest.mark.slow
def test_proxy_broadcast_tolerates_injected_backend_failure(cluster):
    """Broadcast-with-reducer through the proxy folds the surviving
    hosts when one backend's calls fail (proxy.hpp:325-392), and the
    forward-error counter records the loss."""
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs

    servers, clients, store = cluster
    _train_disjoint(clients)
    assert clients[2].do_mix() is True
    proxy = Proxy(ProxyArgs(engine="classifier", listen_addr="127.0.0.1"),
                  coord=MemoryCoordinator(store))
    pport = proxy.start(0)
    pc = ClassifierClient("127.0.0.1", pport, NAME)
    try:
        port0 = servers[0].args.rpc_port
        # baseline broadcast across all 3
        assert len(pc.get_status()) == 3
        with faults.armed(f"rpc.call.get_status.*:{port0}:error"):
            st = pc.get_status()  # merged map from the 2 survivors
            assert len(st) == 2
            # specifically the faulted backend's entry is the missing one
            assert f"127.0.0.1_{port0}" not in st
        stats = pc.get_proxy_status()
        (pstat,) = stats.values()
        assert int(pstat["forward_errors"]) >= 1
        # faults cleared: full fan-in returns
        assert len(pc.get_status()) == 3
    finally:
        pc.close()
        proxy.stop()


@pytest.mark.slow
def test_push_gossip_shrugs_off_failed_peer():
    """Gossip (broadcast push mixer) skips a peer whose exchange fails —
    the round still succeeds against the reachable peer, and the dead one
    catches up once its faults clear (push_mixer.cpp's per-candidate
    tolerance, tested deterministically)."""
    store = _Store()
    servers = _cluster(3, store, mixer="broadcast_mixer")
    clients = [ClassifierClient("127.0.0.1", s.args.rpc_port, NAME)
               for s in servers]
    try:
        for _ in range(5):
            clients[0].train([["pos", Datum({"x": 1.0})]])
            clients[1].train([["neg", Datum({"x": -1.0})]])
        port1 = servers[1].args.rpc_port
        with faults.armed(f"rpc.call.mix_get_schema.*:{port1}:error",
                          f"rpc.call.mix_get_diff.*:{port1}:error"):
            assert clients[0].do_mix() is True  # node1 unreachable, node2 ok
        # the reachable pair exchanged: node2 got node0's class — but
        # "neg" lives only on the skipped peer, so it went nowhere
        assert set(clients[2].get_labels()) == {"pos"}
        assert "pos" not in clients[1].get_labels()  # skipped peer untouched
        # faults cleared: node 1's own round spreads its class and pulls
        # in what it missed
        assert clients[1].do_mix() is True
        assert set(clients[1].get_labels()) == {"pos", "neg"}
        assert set(clients[2].get_labels()) == {"pos", "neg"}
    finally:
        faults.disarm_all()
        for c in clients:
            c.close()
        for s in servers:
            s.stop()


# --------------------------------------------------------- coord chaos ----
def test_heartbeat_loss_fires_suicide_watchers():
    """Heartbeat failure AND failed session resumption = ZK session loss:
    the client fires its delete watchers (the suicide path,
    server_helper.cpp:91-94) and the server side expires the session's
    ephemerals. coord_open must be faulted too — with a reachable
    coordinator the client now legitimately RESUMES instead of dying
    (coord/remote.py _try_resume; test_coord_service covers that path)."""
    from jubatus_tpu.coord.remote import RemoteCoordinator
    from jubatus_tpu.coord.server import CoordServer

    srv = CoordServer(lease_sec=1.0)
    port = srv.start(0)
    b = None
    try:
        a = RemoteCoordinator("127.0.0.1", port, resume_window_sec=2.0)
        a.create("/chaos/me", ephemeral=True)
        died = []
        a.watch_delete("/chaos/me", lambda p: died.append(p))
        # the pattern hits EVERY session's heartbeats on this port, so the
        # observer client is created only after the fault window closes
        with faults.armed(f"rpc.call.coord_heartbeat.*:{port}:error",
                          f"rpc.call.coord_open.*:{port}:error"):
            deadline = time.time() + 20
            while time.time() < deadline and not died:
                time.sleep(0.1)
        assert died == ["/chaos/me"], "suicide watcher never fired"
        b = RemoteCoordinator("127.0.0.1", port)
        deadline = time.time() + 10
        while time.time() < deadline and b.exists("/chaos/me"):
            time.sleep(0.1)
        assert not b.exists("/chaos/me"), "ephemeral outlived its session"
    finally:
        if b is not None:
            b.close()
        srv.stop()


def test_heartbeat_delay_below_lease_is_harmless():
    """Latency under the lease doesn't expire anything."""
    from jubatus_tpu.coord.remote import RemoteCoordinator
    from jubatus_tpu.coord.server import CoordServer

    srv = CoordServer(lease_sec=1.5)
    port = srv.start(0)
    a = b = None
    try:
        a = RemoteCoordinator("127.0.0.1", port)
        b = RemoteCoordinator("127.0.0.1", port)
        a.create("/slow/me", ephemeral=True)
        with faults.armed("rpc.call.coord_heartbeat.*:delay:0.2"):
            time.sleep(3.0)  # two lease periods of delayed heartbeats
        assert b.exists("/slow/me")
    finally:
        for c in (a, b):
            if c is not None:
                c.close()
        srv.stop()


# ----------------------------------------------- self-healing plane -------
def test_breaker_trips_after_n_failures_and_half_open_readmits():
    """CircuitBreaker state machine, deterministically: N failures in the
    window open it, the cooldown admits exactly one half-open probe, a
    probe success closes it (window cleared), a probe failure re-opens."""
    from jubatus_tpu.rpc.breaker import CircuitBreaker

    b = CircuitBreaker(failure_threshold=3, cooldown_sec=0.15,
                       window_sec=30.0)
    assert b.allow() and b.state == "closed"
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"          # under threshold
    assert b.record_failure() is True   # trips
    assert b.state == "open" and not b.allow()
    time.sleep(0.16)
    assert b.state == "half_open"
    assert b.allow() is True            # the one probe
    assert b.allow() is False           # serialized: second probe refused
    assert b.record_failure() is True   # probe failed: re-open
    assert not b.allow()
    time.sleep(0.16)
    assert b.allow() is True
    assert b.record_success() is True   # probe succeeded: closed
    assert b.state == "closed" and b.allow()
    assert b.opened_total == 2


def test_retry_budget_exhausts_under_sustained_faults():
    """The token bucket caps retry amplification: with every call
    failing, withdrawals stop once the budget is dry and the client
    counts rpc.retry_budget_exhausted instead of hammering the backend."""
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.rpc.retry import RetryBudget
    from jubatus_tpu.utils.tracing import Registry

    reg = Registry()
    budget = RetryBudget(ratio=0.01, max_tokens=2.0)
    c = RpcClient("127.0.0.1", 1, retry_budget=budget, registry=reg)
    with faults.armed("rpc.connect.127.0.0.1:1:error"):
        for _ in range(10):
            with pytest.raises(RpcError):
                c.call("get_status", "x")
    c.close()
    counters = reg.counters()
    # 2 initial tokens + 10 * 0.01 deposits < 3: at most 2-3 retries ever
    # happen, the rest are denied
    assert counters.get("rpc.retries", 0) <= 3
    assert counters.get("rpc.retry_budget_exhausted", 0) >= 7
    assert budget.status()["denials"] >= 7


def test_expired_deadline_rejected_at_dispatch(cluster):
    """A call whose propagated budget dies in the server's queue (here: a
    200 ms injected dispatch delay vs a 50 ms deadline) is rejected at
    dispatch — DeadlineExceeded to the caller in bounded time, counted by
    the server, handler never invoked."""
    from jubatus_tpu.rpc import deadline
    from jubatus_tpu.rpc.errors import DeadlineExceeded

    servers, clients, _ = cluster
    before = servers[0].driver.update_count
    with faults.armed("rpc.dispatch.train:delay:0.2"):
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            with deadline.deadline_after(0.05):
                clients[0].train([["pos", Datum({"x": 1.0})]])
        assert time.monotonic() - t0 < 1.0  # bounded, not the 10 s timeout
    # the server finishes its delayed dispatch, then REJECTS (no apply)
    deadline_counter = None
    deadline_end = time.time() + 5
    while time.time() < deadline_end:
        counters = servers[0].rpc.trace.counters()
        if counters.get("rpc.deadline_rejected"):
            deadline_counter = counters["rpc.deadline_rejected"]
            break
        time.sleep(0.05)
    assert deadline_counter == 1
    assert servers[0].driver.update_count == before  # never applied


def test_quorum_degraded_round_recorded(cluster):
    """One member's diffs unreachable: the round proceeds above quorum
    but is stamped DEGRADED in the flight recorder and counted."""
    servers, clients, _ = cluster
    _train_disjoint(clients)
    port1 = servers[1].args.rpc_port
    with faults.armed(f"rpc.call.mix_get_diff.*:{port1}:error"):
        assert clients[2].do_mix() is True
    recs = servers[2].mixer.flight.snapshot()
    degraded = [r for r in recs if r.get("degraded")]
    assert degraded and degraded[-1]["contributors"] == 2
    assert servers[2].rpc.trace.counters().get("mix.quorum_degraded") == 1


def test_quorum_abort_below_fraction(cluster):
    """Two of three members unreachable: 1/3 < the 0.5 quorum — the
    round aborts instead of broadcasting a one-node fold as everyone's
    new base."""
    servers, clients, _ = cluster
    _train_disjoint(clients)
    p0, p1 = servers[0].args.rpc_port, servers[1].args.rpc_port
    with faults.armed(f"rpc.call.mix_get_diff.*:{p0}:error",
                      f"rpc.call.mix_get_diff.*:{p1}:error"):
        assert clients[2].do_mix() is False
    recs = servers[2].mixer.flight.snapshot()
    assert any("quorum_not_met" in r.get("reason", "") for r in recs)
    assert clients[2].do_mix() is True  # recovers once faults clear


@pytest.mark.slow
def test_chaos_idempotent_failover_and_breaker_lifecycle(monkeypatch):
    """The ISSUE 3 acceptance chaos matrix: with IO errors injected on
    one of three backends, (a) idempotent calls through the proxy
    succeed >= 99% via breaker skip + failover, (b) the failing backend's
    breaker OPENS during the fault window and RE-CLOSES after faults are
    disarmed (half-open probe), and (c) effectful train calls are never
    silently re-forwarded — the failed call surfaces and its examples
    are applied zero times, not two."""
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs

    # python transport end to end: the C++ relay plane would bypass the
    # (python-level) fault injection sites after its first refresh tick
    monkeypatch.setenv("JUBATUS_TPU_NATIVE_RPC", "0")
    store = _Store()
    servers = _cluster(3, store)
    clients = [ClassifierClient("127.0.0.1", s.args.rpc_port, NAME)
               for s in servers]
    proxy = Proxy(ProxyArgs(engine="classifier", listen_addr="127.0.0.1",
                            breaker_failures=3, breaker_cooldown=1.0),
                  coord=MemoryCoordinator(store))
    pport = proxy.start(0)
    pc = ClassifierClient("127.0.0.1", pport, NAME)
    try:
        _train_disjoint(clients)
        bad_port = servers[0].args.rpc_port
        bad_key = f"('127.0.0.1', {bad_port})"
        # (a)+(b) idempotent plane under faults
        ok = 0
        with faults.armed(f"rpc.call.get_labels.*:{bad_port}:error"):
            for _ in range(100):
                try:
                    pc.get_labels()
                    ok += 1
                except RpcError:
                    pass
            snap_during = proxy.breakers.snapshot()
        assert ok >= 99, f"only {ok}/100 idempotent calls survived"
        assert snap_during.get(bad_key, {}).get("state") == "open"
        assert proxy.rpc.trace.counters().get("proxy.breaker_open", 0) >= 1
        # (b) faults disarmed: cooldown passes, a half-open probe
        # re-admits the backend and its breaker closes again
        deadline_end = time.time() + 10
        while time.time() < deadline_end:
            pc.get_labels()
            if proxy.breakers.snapshot()[bad_key]["state"] == "closed":
                break
            time.sleep(0.2)
        assert proxy.breakers.snapshot()[bad_key]["state"] == "closed"
        # (c) effectful plane: a train forward that dies in transport
        # SURFACES (no silent re-forward) and applies nothing anywhere.
        # Faults target the proxy->backend hops only (one rule per
        # backend port) — whichever replica the proxy picks fails once.
        labels_before = pc.get_labels()
        rules = [f"rpc.call.train.*:{s.args.rpc_port}:error@1"
                 for s in servers]
        with faults.armed(*rules):
            with pytest.raises(RpcError):
                pc.train([["pos", Datum({"x": 1.0})],
                          ["pos", Datum({"x": 2.0})]])
        labels_after = pc.get_labels()
        assert labels_after == labels_before, "train was re-forwarded"
    finally:
        faults.disarm_all()
        pc.close()
        for c in clients:
            c.close()
        for s in servers:
            s.stop()
        proxy.stop()


def _envelope_roundtrip(port: int) -> None:
    """Drive one server through every envelope generation a peer might
    send: legacy 4-element, traced 5-element, deadlined 6-element (with
    real and nil trace) — all must round-trip."""
    import socket as _socket

    import msgpack
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.rpc import deadline

    sock = _socket.create_connection(("127.0.0.1", port), timeout=10.0)
    unp = msgpack.Unpacker(raw=False)

    def send_frame(env):
        sock.sendall(msgpack.packb(env, use_bin_type=True))
        while True:
            try:
                return unp.unpack()
            except msgpack.OutOfData:
                data = sock.recv(65536)
                assert data, "server closed on an envelope variant"
                unp.feed(data)

    # legacy 4-element (what every deployed msgpack-rpc client sends)
    msg = send_frame([0, 7, "get_status", ["x"]])
    assert msg[0] == 1 and msg[1] == 7 and msg[2] is None
    # traced 5-element
    msg = send_frame([0, 8, "get_status", ["x"], {"t": "abc", "s": "def"}])
    assert msg[1] == 8 and msg[2] is None
    # deadlined 6-element, nil trace
    msg = send_frame([0, 9, "get_status", ["x"], None, 5.0])
    assert msg[1] == 9 and msg[2] is None
    # deadlined 6-element, real trace
    msg = send_frame([0, 10, "get_status", ["x"], {"t": "abc", "s": "d"},
                      2.5])
    assert msg[1] == 10 and msg[2] is None
    sock.close()
    # the typed client across generations: plain, then deadline-bearing
    c = RpcClient("127.0.0.1", port)
    assert c.call("get_status", "x")
    with deadline.deadline_after(5.0):
        assert c.call("get_status", "x")
    c.close()


def _deadline_bound_check(srv, port: int) -> None:
    """ISSUE 3 acceptance: a 50 ms deadline against a dispatch delayed
    200 ms fails with DeadlineExceeded in bounded time (not the 10 s flat
    timeout), and the server counts the dispatch-side rejection once its
    delayed worker reaches the gate."""
    from jubatus_tpu.rpc import deadline
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.rpc.errors import DeadlineExceeded

    c = RpcClient("127.0.0.1", port)
    with faults.armed("rpc.dispatch.get_status:delay:0.2"):
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            with deadline.deadline_after(0.05):
                c.call("get_status", "x")
        assert time.monotonic() - t0 < 1.0
    c.close()
    deadline_end = time.time() + 5
    while time.time() < deadline_end:
        if srv.trace.counters().get("rpc.deadline_rejected"):
            break
        time.sleep(0.05)
    assert srv.trace.counters().get("rpc.deadline_rejected", 0) >= 1


def test_envelope_compat_python_transport():
    from jubatus_tpu.rpc.server import RpcServer

    srv = RpcServer()
    srv.register("get_status", lambda name: {"node": {"ok": 1}}, arity=1)
    port = srv.serve_background(0, host="127.0.0.1")
    try:
        _envelope_roundtrip(port)
        assert not srv.trace.counters().get("rpc.deadline_rejected")
        _deadline_bound_check(srv, port)
    finally:
        srv.stop()


def test_envelope_compat_native_transport():
    from jubatus_tpu.rpc import native_server

    if not native_server.available():
        pytest.skip("native transport unavailable")
    srv = native_server.NativeRpcServer()
    srv.register("get_status", lambda name: {"node": {"ok": 1}}, arity=1)
    port = srv.serve_background(0, host="127.0.0.1")
    try:
        _envelope_roundtrip(port)
        assert not srv.trace.counters().get("rpc.deadline_rejected")
        _deadline_bound_check(srv, port)
    finally:
        srv.stop()


def test_armed_scopes_compose():
    """Nested/outer rules survive an inner scope's exit; empty arming
    never flips the hot-path flag."""
    assert not faults.is_armed()
    faults.arm()  # zero rules: stays disarmed
    assert not faults.is_armed()
    with faults.armed("outer.site:error"):
        with faults.armed("inner.site:error"):
            with pytest.raises(faults.FaultInjected):
                faults.fire("inner.site")
        # inner scope closed: outer rule still live
        with pytest.raises(faults.FaultInjected):
            faults.fire("outer.site")
        faults.fire("inner.site")  # inner rule gone
    assert not faults.is_armed()


def test_drop_mode_parse_and_fire():
    """ISSUE 11: the ``drop`` mode — fire() reports True and drop-aware
    sites silently lose the operation; error/delay behavior unchanged."""
    r = faults.parse_rule("mix.comm.put_diff:drop")
    assert r.action == "drop" and r.prob == 1.0
    r = faults.parse_rule("mix.comm.*:drop:0.5")
    assert r.action == "drop" and r.prob == 0.5
    r = faults.parse_rule("mix.put_diff:drop@2")
    assert r.remaining == 2
    with faults.armed("some.site:drop@1"):
        assert faults.fire("some.site") is True   # dropped once
        assert faults.fire("some.site") is False  # budget spent
    with faults.armed("err.site:error"):
        with pytest.raises(faults.FaultInjected):
            faults.fire("err.site")
    assert faults.fire("anything") is False  # disarmed: plain False


def test_drop_mode_loses_mix_broadcast():
    """A dropped put_diff broadcast = no member acks; the sync master
    demotes nobody it can blame and the next round retries."""
    from jubatus_tpu.framework.linear_mixer import RpcLinearCommunication

    class _NoMc(RpcLinearCommunication):
        def __init__(self):  # no coordinator: only the drop path runs
            self.name = NAME

    comm = _NoMc()
    with faults.armed("mix.comm.put_diff:drop"):
        assert comm.put_diff(b"payload") == {}
    with faults.armed("mix.comm.get_diff:drop"):
        assert comm.get_diff() == []


def test_fault_flag_arms_at_server_boot(tmp_path):
    """--fault SITE:MODE:ARG rules arm when the server constructs —
    the operator's chaos-drill lever (same registry as the env var)."""
    faults.disarm_all()
    srv = EngineServer(
        "classifier", CONF,
        args=ServerArgs(engine="classifier",
                        fault=["mix.put_diff:error@1"],
                        telemetry_interval=0))
    try:
        assert faults.is_armed()
        with pytest.raises(faults.FaultInjected):
            faults.fire("mix.put_diff")
        faults.fire("mix.put_diff")  # @1 budget spent
    finally:
        srv.stop()
        faults.disarm_all()


def test_ann_rebuild_fault_degrades_to_exact_scan():
    """ISSUE 16 fault site ``ann.rebuild``: an injected index-build
    failure degrades the ANN tier to the exact scan — counted, evented,
    and NEVER wrong-answering (the degraded tier's results match a
    backend that never armed ANN at all)."""
    import numpy as np

    from jubatus_tpu.models._nn_backend import NNBackend
    from jubatus_tpu.utils import events

    rng = np.random.default_rng(7)

    def vec():
        idx = rng.integers(1, 64, size=6)
        val = rng.normal(size=6)
        return [(int(i), float(v)) for i, v in zip(idx, val)]

    rows = {f"r{i}": vec() for i in range(160)}
    plain = NNBackend("lsh", dim=64, hash_num=64)
    ann = NNBackend("lsh", dim=64, hash_num=64)
    ann.configure_ann("ivf", cells=4, nprobe=2)
    for rid, v in rows.items():
        plain.set_row(rid, v)
        ann.set_row(rid, v)

    j = events.default_journal()
    cursor = max([r["hlc"] for r in j.snapshot()] or [0])
    q = vec()
    with faults.armed("ann.rebuild:error"):
        got = ann.neighbors(q, 5)          # build attempt fires the fault
    want = plain.neighbors(q, 5)
    assert got == want                      # degraded == exact, not wrong
    st = ann.ann_stats()
    assert st["degraded"] is True and st["built"] is False
    assert st["rebuild_failed"] == 1
    evs = j.snapshot(since=cursor, grep="ann")
    assert any(e["type"] == "degraded" and e["subsystem"] == "ann"
               for e in evs)
    # the latch is sticky: later queries stay exact with no retry storm
    q2 = vec()
    assert ann.neighbors(q2, 5) == plain.neighbors(q2, 5)
    assert ann.ann_stats()["rebuild_failed"] == 1
    # and explicit re-configure re-arms the tier cleanly
    ann.configure_ann("ivf", cells=4, nprobe=4)
    assert ann.ann_stats()["degraded"] is False
    res = ann.neighbors(q2, 5)
    assert ann.ann_stats()["built"] is True
    assert [r for r, _ in res]              # non-empty approximate answer
