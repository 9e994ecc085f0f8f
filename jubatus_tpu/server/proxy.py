"""Query-routing proxy tier (≙ framework/proxy.{hpp,cpp} + proxy_common.{hpp,cpp}).

The reference's ``juba<engine>_proxy`` binaries are async RPC servers whose
methods are registered by routing class — random (1 active node), broadcast
(all actives + reducer fold), cht (N ring successors of the key + reducer)
(proxy.hpp:64-186,229-286) — with built-ins save/load/get_status/
get_proxy_status (proxy.cpp:43-66). Member lookup reads the coordination
store's ``actives`` list through a watch-invalidated cache (proxy_common.cpp:
73-114, cached_zk). Sessions to backend servers live in a pool with expiry
(proxy.hpp:502-593).

Here one ``Proxy`` class serves any engine: the routing/aggregator table
comes from ``framework.idl.SERVICES`` (what the reference bakes into the
generated ``*_proxy.cpp``). Wire behavior matches: same method names, same
leading cluster-name param, same reducer semantics, per-host failures
tolerated as long as one backend answers (proxy.hpp:325-392).

Beyond the reference — the self-healing request plane:

- **per-backend circuit breakers** (rpc/breaker.py): transport failures
  land in a rolling window per member; an OPEN backend is skipped by
  random/cht routing and re-admitted via half-open probes, replacing the
  old blunt ``members.invalidate(cluster)`` (which nuked the whole
  cluster's cache because ONE node failed);
- **idempotent failover**: random-routed reads that hit a transport
  failure fail over to the next active replica (retry-budget-gated, so a
  degraded cluster sees bounded amplification); effectful calls keep
  propagate-don't-double-apply semantics;
- **deadline-aware fan-out**: the broadcast collects against ONE shared
  deadline (``concurrent.futures.wait``) derived from the caller's
  remaining budget, abandoning stragglers (counted as
  ``proxy.fanout_timeouts``) instead of serially paying ``timeout+1`` per
  hung backend; per-attempt backend timeouts derive from the remaining
  budget because the forwarded call re-ships it on the envelope.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait as futures_wait
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from jubatus_tpu.coord import create_coordinator, membership
from jubatus_tpu.coord.base import Coordinator, NodeInfo
from jubatus_tpu.coord.cht import CHT, ring_key
from jubatus_tpu.framework.idl import INTERNAL, get_service, idempotent_methods
from jubatus_tpu.rpc import aggregators
from jubatus_tpu.rpc import deadline as deadlines
from jubatus_tpu.rpc import principal as principals
from jubatus_tpu.rpc.breaker import BreakerBoard
from jubatus_tpu.rpc.client import RpcClient
from jubatus_tpu.rpc.errors import (
    DeadlineExceeded,
    EpochMismatch,
    HostError,
    MultiRpcError,
    NodeDraining,
    RpcIoError,
    RpcNoClient,
    RpcNoResult,
    RpcTimeoutError,
)
from jubatus_tpu.rpc.retry import RetryBudget
from jubatus_tpu.utils import faults, tracing
from jubatus_tpu.version import __version__

log = logging.getLogger(__name__)

#: transport-level failures (a breaker's evidence; failover triggers)
_TRANSPORT_ERRORS = (RpcIoError, RpcTimeoutError, faults.FaultInjected)

#: membership-protocol rejections (elastic membership, ISSUE 10): the
#: backend refused BEFORE applying anything (draining, or a ring-epoch
#: disagreement). Safe to re-route even for EFFECTFUL calls — the fix is
#: a membership refresh, not a backoff
_MEMBERSHIP_ERRORS = (NodeDraining, EpochMismatch)


def _membership_rejection(exc: BaseException) -> bool:
    """True when ``exc`` is (or a fan-out whose every failure is) a
    membership-protocol rejection — the caller should refresh its ring
    and re-route."""
    if isinstance(exc, _MEMBERSHIP_ERRORS):
        return True
    if isinstance(exc, MultiRpcError) and exc.errors:
        return all(isinstance(e.cause, _MEMBERSHIP_ERRORS)
                   for e in exc.errors)
    return False


class _RingCache:
    """CHT snapshots per cluster, rebuilt ONLY when the member list
    changes (the satellite fix for the per-request ``CHT(actives)``
    rebuild: 8 MD5 hashes per member per call, pure hot-path tax).

    Each entry remembers the PREVIOUS ring and when the swap happened:
    for ``handoff_window`` seconds after a membership change the proxy
    double-dispatches CHT-routed effectful calls to the union of old and
    new owners, so no key ever has zero owners while rows migrate."""

    def __init__(self, handoff_window: float = 15.0) -> None:
        self.handoff_window = float(handoff_window)
        self._lock = threading.Lock()
        #: name -> (ring_key, ring, prev_ring_or_None, swap_monotonic)
        self._entries: Dict[str, Tuple[Tuple[str, ...], CHT,
                                       Optional[CHT], float]] = {}
        self.builds = 0
        self.hits = 0

    def get(self, name: str, actives: Sequence[NodeInfo]
            ) -> Tuple[CHT, Optional[CHT]]:
        """(current ring, previous ring while inside the handoff
        window — else None)."""
        key = ring_key(actives)
        now = time.monotonic()
        with self._lock:
            e = self._entries.get(name)
            if e is not None and e[0] == key:
                self.hits += 1
                ring, prev, swapped = e[1], e[2], e[3]
                if prev is not None and now - swapped > self.handoff_window:
                    # window over: forget the old ring
                    self._entries[name] = (key, ring, None, swapped)
                    prev = None
                return ring, prev
        ring = CHT(actives)
        with self._lock:
            e = self._entries.get(name)
            prev = e[1] if (e is not None and e[0] != key
                            and e[1].members) else None
            self._entries[name] = (key, ring, prev, now)
            self.builds += 1
        return ring, prev

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"builds": self.builds, "hits": self.hits,
                    "clusters": len(self._entries),
                    "in_handoff": sum(1 for e in self._entries.values()
                                      if e[2] is not None)}


@dataclasses.dataclass
class ProxyArgs:
    """≙ proxy_argv (server_util.cpp:440-557). Same defaults: 4 worker
    threads vs the server's 2, 10 s timeouts, session-pool knobs."""

    engine: str = ""
    rpc_port: int = 9199
    listen_addr: str = ""
    thread: int = 4
    timeout: float = 10.0
    coordinator: str = ""
    coordinator_timeout: float = 10.0
    interconnect_timeout: float = 10.0
    session_pool_expire: float = 60.0   # --pool_expire
    session_pool_size: int = 0          # --pool_size, 0 = unbounded
    daemon: bool = False
    legacy_wire: bool = False           # --legacy-wire (see rpc/legacy.py)
    modern_wire: bool = False           # --modern-wire: no autodetection
    #: Prometheus /metrics + /healthz HTTP port: -1 = off, 0 = ephemeral
    metrics_port: int = -1
    #: circuit breaker tuning (rpc/breaker.py): this many transport
    #: failures to one backend inside the window open its breaker for
    #: the cooldown; half-open probes re-admit it
    breaker_failures: int = 5
    breaker_window: float = 30.0
    breaker_cooldown: float = 5.0
    #: retry budget: failover retries per first-attempt forward (10% =
    #: the gRPC/Finagle convention; see rpc/retry.py)
    retry_budget_ratio: float = 0.1
    #: --slowlog-*: tail-based slow-request capture at the PROXY hop
    #: (utils/slowlog.py) — same semantics as the engine servers
    slowlog_capacity: int = 256
    slowlog_quantile: float = 0.99
    slowlog_min_count: int = 64
    #: runtime telemetry sampler period (0 disables the thread)
    telemetry_interval: float = 10.0
    #: --slo et al.: the model-health plane at the PROXY hop (ISSUE 7) —
    #: same grammar/semantics as the engine servers (utils/slo.py);
    #: proxy-side SLOs watch the forwarded-request spans
    slo: List[str] = dataclasses.field(default_factory=list)
    slo_fast_window: float = 300.0
    slo_slow_window: float = 3600.0
    slo_burn_threshold: float = 2.0
    #: metric time-series ring depth (0 disables ring + SLO evaluation)
    timeseries_capacity: int = 360
    #: --profile-hz: always-on stack sampler at the PROXY hop
    #: (utils/profiler.py) — the proxy's own routing/fan-out stacks fold
    #: into the cluster profile next to the backends'; 0 = off
    profile_hz: float = 67.0
    #: --profile-trigger-*: slow-log breach trigger for the proxy's own
    #: spans (same semantics as the engine servers)
    profile_trigger_breaches: int = 3
    profile_trigger_window: float = 10.0
    #: --handoff-window: seconds after a membership change during which
    #: CHT-routed EFFECTFUL calls double-dispatch to the union of old
    #: and new ring owners (elastic membership: no key has zero owners
    #: while rows migrate); idempotent reads fail over old->new instead
    handoff_window: float = 15.0
    #: --event-capacity: cluster event journal depth at the PROXY hop
    #: (utils/events.py, ISSUE 14) — breaker transitions and proxy SLO
    #: edges land here; 0 disables emission
    event_capacity: int = 2048
    #: --incident-window: debounce window (seconds) for automatic
    #: incident bundles at the proxy hop (0 disables auto-capture)
    incident_window: float = 300.0
    #: --incident-dir: capped bundle artifacts dir; empty = under /tmp
    #: keyed by the bound port
    incident_dir: str = ""
    #: --usage-top: exact per-principal ledger rows at the PROXY hop
    #: (utils/usage.py, ISSUE 19) — the proxy is in the request path,
    #: so it attributes its own dispatch cost per tenant; 0 disables
    usage_top: int = 64
    #: --usage-gauge-principals: top-N principals published as
    #: usage.<principal>.* gauges each telemetry tick
    usage_gauge_principals: int = 8

    @property
    def bind_host(self) -> str:
        return self.listen_addr or "0.0.0.0"

    def flags_status(self) -> Dict[str, Any]:
        return {f"argv.{f.name}": getattr(self, f.name)
                for f in dataclasses.fields(self)}


class MemberCache:
    """Watch-invalidated actives cache (≙ cached_zk, common/cached_zk.hpp:
    31-59): one entry per cluster name, cleared when the coordinator signals
    a child change, with a TTL backstop for coordinators whose watches are
    best-effort."""

    def __init__(self, coord: Coordinator, engine: str, ttl: float = 2.0) -> None:
        self._coord = coord
        self._engine = engine
        self._ttl = ttl
        self._lock = threading.Lock()
        self._cache: Dict[str, Tuple[float, List[NodeInfo]]] = {}
        self._watched: set = set()

    def actives(self, name: str) -> List[NodeInfo]:
        now = time.monotonic()
        with self._lock:
            hit = self._cache.get(name)
            if hit is not None and now - hit[0] < self._ttl:
                return hit[1]
        nodes = membership.get_all_actives(self._coord, self._engine, name)
        with self._lock:
            self._cache[name] = (now, nodes)
            need_watch = name not in self._watched
            if need_watch:
                self._watched.add(name)
        if need_watch:  # outside the lock: watchers may fire synchronously
            path = f"{membership.actor_path(self._engine, name)}/actives"
            try:
                self._coord.watch_children(path, lambda _p, n=name: self.invalidate(n))
            except NotImplementedError:
                pass
        return nodes

    def invalidate(self, name: str) -> None:
        with self._lock:
            self._cache.pop(name, None)


def _peek_cluster_name(raw_params: bytes) -> Optional[str]:
    """First element of the params array when it is a string — WITHOUT
    feeding the (possibly multi-megabyte) span through an unpacker copy.
    None for any other wire shape."""
    try:
        t = raw_params[0]
        if 0x90 <= t <= 0x9F:
            if t == 0x90:
                return None
            i = 1
        elif t == 0xDC:
            if int.from_bytes(raw_params[1:3], "big") < 1:
                return None
            i = 3
        elif t == 0xDD:
            if int.from_bytes(raw_params[1:5], "big") < 1:
                return None
            i = 5
        else:
            return None
        t = raw_params[i]
        if 0xA0 <= t <= 0xBF:
            n, i = t & 0x1F, i + 1
        elif t == 0xD9:
            n, i = raw_params[i + 1], i + 2
        elif t == 0xDA:
            n, i = int.from_bytes(raw_params[i + 1:i + 3], "big"), i + 3
        else:
            return None
        return raw_params[i:i + n].decode("utf-8", "surrogateescape")
    except (IndexError, ValueError):
        return None


class _Session:
    __slots__ = ("client", "last_used")

    def __init__(self, client: RpcClient) -> None:
        self.client = client
        self.last_used = time.monotonic()


class Proxy:
    """One engine's routing proxy. listen → start → join, like the servers."""

    def __init__(self, args: ProxyArgs, coord: Optional[Coordinator] = None) -> None:
        if not args.engine:
            raise ValueError("ProxyArgs.engine required")
        self.args = args
        self.engine = args.engine
        self.coord = coord or create_coordinator(args.coordinator)
        self.members = MemberCache(self.coord, self.engine)
        # same transport selection as the engine servers: the C++
        # front-end when JUBATUS_TPU_NATIVE_RPC=1 (rpc/native_server.py)
        from jubatus_tpu.rpc.native_server import create_rpc_server

        self.rpc = create_rpc_server(
            timeout=args.timeout,
            legacy_wire=getattr(args, "legacy_wire", False),
            wire_detect=not getattr(args, "modern_wire", False))
        self.start_time = time.time()  # wall-clock
        self._pool: Dict[Tuple[str, int], List[_Session]] = {}
        self._pool_lock = threading.Lock()
        self._last_expiry = 0.0
        self._executor = ThreadPoolExecutor(
            max_workers=max(8, args.thread * 4), thread_name_prefix="proxy-fanout"
        )
        self._stop_event = threading.Event()
        # counters (proxy_common.cpp:126-182)
        self._counters_lock = threading.Lock()
        self.request_counts: Dict[str, int] = {}
        self.forward_count = 0
        self.forward_errors = 0
        #: self-healing plane: per-backend breakers + the failover retry
        #: budget; transitions count into the proxy's own registry
        #: (proxy.breaker_open / proxy.breaker_close on /metrics)
        self.breakers = BreakerBoard(
            window_sec=args.breaker_window,
            failure_threshold=args.breaker_failures,
            cooldown_sec=args.breaker_cooldown,
            registry=self.rpc.trace, counter_prefix="proxy.breaker")
        self.retry_budget = RetryBudget(ratio=args.retry_budget_ratio)
        self._idempotent = idempotent_methods(self.engine)
        #: elastic membership (ISSUE 10): member-list-keyed ring cache
        #: (no per-request CHT rebuild) + the double-dispatch window
        self.rings = _RingCache(
            handoff_window=getattr(args, "handoff_window", 15.0))
        #: C++ relay plane (native transport only): random-routed raw
        #: methods forward in rpc_frontend.cpp without entering Python;
        #: this side only keeps the routing table fresh (clusters seen ->
        #: current actives) and serves whatever the C++ declines
        self._relay_methods: List[str] = []
        self._relay_seen: Dict[str, float] = {}  # cluster -> last-live ts
        self._relay_lock = threading.Lock()
        #: Prometheus /metrics + /healthz endpoint (--metrics-port >= 0)
        self.metrics = None
        # forensics plane (ISSUE 4): slow-request ring at the proxy hop +
        # the runtime telemetry sampler (started with the listener)
        self.rpc.trace.slowlog.configure(
            capacity=getattr(args, "slowlog_capacity", 256),
            quantile=getattr(args, "slowlog_quantile", 0.99),
            min_count=getattr(args, "slowlog_min_count", 64))
        from jubatus_tpu.utils.runtime_telemetry import RuntimeTelemetry

        self.telemetry = RuntimeTelemetry(
            self.rpc.trace,
            interval_sec=getattr(args, "telemetry_interval", 10.0))
        # continuous profiling plane (ISSUE 8) at the proxy hop: the
        # same always-on sampler + slowlog tail trigger as the servers
        # (no device capture — proxies have no accelerator work)
        from jubatus_tpu.utils.profiler import SamplingProfiler

        self.profiler = SamplingProfiler(
            self.rpc.trace, hz=getattr(args, "profile_hz", 67.0))
        trig = getattr(args, "profile_trigger_breaches", 3)
        if trig > 0 and self.profiler.enabled:
            self.rpc.trace.slowlog.set_trigger(
                self.profiler.tail_snapshot, breaches=trig,
                window_s=getattr(args, "profile_trigger_window", 10.0))
        # model-health plane (ISSUE 7) at the proxy hop: time-series
        # ring + SLO burn-rate engine, ticked by the telemetry sampler
        from jubatus_tpu.utils.slo import SloEngine, parse_slo
        from jubatus_tpu.utils.timeseries import TimeSeriesRing

        ts_cap = getattr(args, "timeseries_capacity", 360)
        interval = self.telemetry.interval_sec
        self.timeseries: Optional[TimeSeriesRing] = None
        self.slo: Optional[SloEngine] = None
        if ts_cap > 0:
            self.timeseries = TimeSeriesRing(
                capacity=ts_cap,
                min_spacing_s=min(1.0, interval / 2) if interval > 0
                else 0.0)
            self.slo = SloEngine(
                [parse_slo(s) for s in getattr(args, "slo", []) or []],
                self.timeseries, self.rpc.trace,
                fast_window_s=getattr(args, "slo_fast_window", 300.0),
                slow_window_s=getattr(args, "slo_slow_window", 3600.0),
                burn_threshold=getattr(args, "slo_burn_threshold", 2.0))
            self.telemetry.hooks.append(self._model_health_tick)
        # cluster event plane + incident bundles (ISSUE 14) at the
        # proxy hop: breaker transitions and proxy-side SLO edges land
        # in this journal; the same two triggers capture bundles
        from jubatus_tpu.utils.incidents import IncidentManager

        self.rpc.trace.events.set_capacity(
            getattr(args, "event_capacity", 2048))
        self.incidents = IncidentManager(
            self.rpc.trace, self._incident_state, self._incident_dir,
            window_s=getattr(args, "incident_window", 300.0),
            journal=self.rpc.trace.events)
        if self.slo is not None:
            self.slo.on_fire = self._on_slo_fire
        # usage-attribution plane (ISSUE 19) at the proxy hop: the proxy
        # is in the request path, so it keeps its OWN per-tenant ledger
        # (dispatch spans + request/response bytes); jubactl -c usage
        # folds it with the backends' via usage.merge_usage
        from jubatus_tpu.utils import usage as usage_mod

        self.usage: Optional[usage_mod.UsageLedger] = None
        ut = getattr(args, "usage_top", 64)
        if ut > 0:
            self.usage = usage_mod.UsageLedger(
                top=ut,
                gauge_principals=getattr(args, "usage_gauge_principals", 8),
                registry=self.rpc.trace)
            self.rpc.usage_recorder = self.usage
            self.rpc.trace.usage_sink = self.usage.span_sink
            usage_mod.attach(self.usage)
        self._was_degraded = False
        #: re-entrancy guard (see EngineServer): the incident
        #: collector's _health() re-runs the telemetry hooks
        self._in_health_tick = False
        self._register_methods()
        if hasattr(self.rpc, "relay_config"):
            t = threading.Thread(target=self._relay_refresher, daemon=True,
                                 name="proxy-relay-refresh")
            t.start()

    # -- session pool (proxy.hpp:502-593) ------------------------------------
    # Borrow/return, like the reference's get/return session pool: each
    # in-flight forward owns a connection, so N concurrent client calls
    # ride N parallel backend connections instead of serializing their
    # round trips through one socket. ``self._pool`` holds IDLE sessions.
    def _checkout(self, node: NodeInfo) -> _Session:
        key = (node.host, node.port)
        with self._pool_lock:
            lst = self._pool.get(key)
            if lst:
                return lst.pop()
        # the proxy's backend clients do NOT retry at the client layer:
        # the proxy owns failover ACROSS replicas (same budget, better
        # spread) — stacked same-host retries under the fan-out would
        # multiply tail latency
        return _Session(RpcClient(node.host, node.port,
                                  timeout=self.args.interconnect_timeout,
                                  retry_methods=frozenset(),
                                  registry=self.rpc.trace))

    def _checkin(self, node: NodeInfo, sess: _Session) -> None:
        sess.last_used = time.monotonic()
        with self._pool_lock:
            self._pool.setdefault((node.host, node.port), []).append(sess)

    def _expire_sessions(self) -> None:
        # throttled: expiry precision is seconds (pool_expire defaults to
        # 60 s); walking the pool under its lock on EVERY forward is pure
        # hot-path tax
        now = time.monotonic()
        if now - self._last_expiry < 1.0:
            return
        self._last_expiry = now
        horizon = time.monotonic() - self.args.session_pool_expire
        dead: List[_Session] = []
        with self._pool_lock:
            for key, lst in list(self._pool.items()):
                keep = [s for s in lst if s.last_used >= horizon]
                dead.extend(s for s in lst if s.last_used < horizon)
                if keep:
                    self._pool[key] = keep
                else:
                    del self._pool[key]
            if self.args.session_pool_size > 0:
                flat = sorted(
                    ((s.last_used, key, s)
                     for key, lst in self._pool.items() for s in lst),
                    key=lambda e: e[0])
                excess = len(flat) - self.args.session_pool_size
                for _, key, s in flat[:max(0, excess)]:
                    self._pool[key].remove(s)
                    dead.append(s)
                for key in [k for k, v in self._pool.items() if not v]:
                    del self._pool[key]
        for s in dead:
            s.client.close()

    def _drop_sessions(self, node: NodeInfo) -> None:
        """A backend failed: close its idle sessions (in-flight ones die
        with their own errors)."""
        with self._pool_lock:
            lst = self._pool.pop((node.host, node.port), [])
        for s in lst:
            s.client.close()

    # -- fan-out core (async_task, proxy.hpp:296-495) ------------------------
    def _fan(
        self,
        nodes: Sequence[NodeInfo],
        method: str,
        args: Sequence[Any],
        reducer: Callable[[Any, Any], Any],
    ) -> Any:
        """Call all nodes in parallel; fold successes left-to-right through
        the reducer; per-host errors are tolerated unless every host fails
        (proxy.hpp:325-392). The whole collection runs against ONE shared
        deadline — a single hung backend costs the broadcast one budget,
        not N serial budgets; stragglers are abandoned and counted."""
        if not nodes:
            raise RpcNoClient(f"no active {self.engine} servers")
        with self._counters_lock:
            self.forward_count += len(nodes)
        if len(nodes) == 1:
            return self._one(nodes[0], method, args)
        # the fan-out hops threads: carry this request's trace context,
        # deadline AND principal into the executor so each backend call
        # ships the same trace_id, derives its timeout from the remaining
        # budget, and bills the same tenant (ISSUE 19)
        ctx = tracing.current_trace()
        dl = deadlines.current()
        pr = principals.current()

        def call(n: NodeInfo) -> Any:
            with tracing.use_trace(ctx), deadlines.use(dl), \
                    principals.use(pr):
                return self._one(n, method, args)

        futs: Dict[Any, NodeInfo] = {
            self._executor.submit(call, n): n for n in nodes}
        budget = self.args.timeout + 1.0
        rem = deadlines.remaining()
        if rem is not None:
            budget = min(budget, max(rem, 0.0))
        done, pending = futures_wait(futs, timeout=budget)
        results: List[Any] = []
        errors: List[HostError] = []
        # iterate in submission order (dict preserves it): the reducer
        # fold stays deterministic even though completion order isn't
        for fut, n in futs.items():
            if fut in pending:
                fut.cancel()  # abandon: result (if any) is discarded
                errors.append(HostError(
                    n.host, n.port,
                    RpcTimeoutError(f"{method} @ {n.host}:{n.port}: "
                                    "fanout deadline")))
                continue
            try:
                results.append(fut.result())
            except Exception as e:  # broad-ok — per-host failure is data
                errors.append(HostError(n.host, n.port, e))
        if pending:
            self.rpc.trace.count("proxy.fanout_timeouts", len(pending))
        if errors:
            with self._counters_lock:
                self.forward_errors += len(errors)
        if not results:
            raise MultiRpcError(errors) if errors else RpcNoResult(method)
        acc = results[0]
        for r in results[1:]:
            acc = reducer(acc, r)
        return acc

    def _one(self, node: NodeInfo, method: str, args: Sequence[Any]) -> Any:
        """One forwarded call, feeding the backend's breaker: transport
        failures tear the node's sessions down and count against it;
        application errors prove the backend alive. The old
        ``members.invalidate(cluster)`` on any failure is gone — one sick
        node no longer blinds the cache for the whole cluster."""
        key = (node.host, node.port)
        sess = self._checkout(node)
        try:
            result = sess.client.call(method, *args)
        except _TRANSPORT_ERRORS:
            # dead/unreachable backend: close this session, drop its idle
            # siblings, feed the breaker, and let the caller decide
            sess.client.close()
            self._drop_sessions(node)
            self.breakers.record(key, False)
            raise
        except DeadlineExceeded:
            # the CALLER's budget ran out — no evidence about the backend
            sess.client.close()
            raise
        except Exception:  # broad-ok — app error from a healthy backend
            self._checkin(node, sess)
            self.breakers.record(key, True)
            raise
        self._checkin(node, sess)
        self.breakers.record(key, True)
        return result

    # -- routing handlers (register_async_{random,broadcast,cht}) -------------
    def _count(self, method: str) -> None:
        with self._counters_lock:
            self.request_counts[method] = self.request_counts.get(method, 0) + 1

    def _route_candidates(self, nodes: Sequence[NodeInfo]) -> List[NodeInfo]:
        """Breaker-aware filter (peek only — the probe slot is claimed by
        ``allow`` on the node actually called): open backends drop out of
        routing; if EVERY candidate is open, fail static (route anyway —
        refusing all traffic would turn a breaker bug into an outage)."""
        allowed = [n for n in nodes
                   if self.breakers.available((n.host, n.port))]
        if allowed:
            return allowed
        if nodes:
            self.rpc.trace.count("proxy.breaker_fail_static")
        return list(nodes)

    def _call_random(self, name: str, actives: Sequence[NodeInfo],
                     params: Sequence[Any]) -> Any:
        """Random routing with breaker-aware selection and idempotent
        failover: a read that hits a transport failure moves to the next
        active replica (budget-gated); an effectful call propagates its
        first failure — re-forwarding could double-apply."""
        if not actives:
            raise RpcNoClient(f"no active {self.engine} servers")
        candidates = self._route_candidates(actives)
        random.shuffle(candidates)
        idem = name in self._idempotent
        last: Optional[BaseException] = None
        tried = 0
        for node in candidates:
            if not self.breakers.allow((node.host, node.port)):
                continue  # half-open probe slot already taken
            tried += 1
            with self._counters_lock:
                self.forward_count += 1
            try:
                return self._one(node, name, params)
            except _MEMBERSHIP_ERRORS as e:
                # pre-apply rejection (draining backend): move to the
                # next replica regardless of idempotency — nothing was
                # applied. Refresh so the NEXT request routes clean.
                self._refresh_members(str(params[0]))
                last = e
                continue
            except _TRANSPORT_ERRORS as e:
                with self._counters_lock:
                    self.forward_errors += 1
                last = e
                if not idem:
                    raise
                rem = deadlines.remaining()
                if rem is not None and rem <= 0:
                    raise
                if not self.retry_budget.try_withdraw():
                    self.rpc.trace.count("rpc.retry_budget_exhausted")
                    raise
                self.rpc.trace.count("rpc.retries")
                continue
        if last is not None:
            raise last
        if not tried:
            # every candidate refused (all half-open with a probe in
            # flight): force one attempt rather than failing closed
            node = random.choice(list(candidates))
            with self._counters_lock:
                self.forward_count += 1
            try:
                return self._one(node, name, params)
            except _TRANSPORT_ERRORS:
                with self._counters_lock:
                    self.forward_errors += 1
                raise
        raise RpcNoClient(f"no active {self.engine} servers")

    #: clusters with no actives for this long fall out of the relay
    #: table and the seen-set (client-supplied names must not grow state
    #: unboundedly — a typo'd cluster should cost one window, not forever)
    _RELAY_SEEN_TTL = 60.0
    _RELAY_SEEN_CAP = 1024

    def _note_cluster(self, cluster: str) -> None:
        """A cluster first seen on the Python path enters the relay table
        at the next refresher tick — after that, its random-routed raw
        traffic never comes back here (C++ relay plane)."""
        with self._relay_lock:
            if cluster not in self._relay_seen and \
                    len(self._relay_seen) >= self._RELAY_SEEN_CAP:
                return  # cap: a flood of bogus names relays nothing anyway
            self._relay_seen.setdefault(cluster, time.monotonic())

    def _relay_refresher(self) -> None:
        """Keep the C++ relay's routing table fresh: every tick, push the
        current actives of every cluster this proxy has served. Replaced
        wholesale — a de-registered backend retires its pipes via the
        config generation (rpc_frontend.cpp relay_try). A cluster whose
        actives lookup FAILS transiently keeps its previous routing (a
        coordinator hiccup must not bounce traffic to the Python path);
        one that stays EMPTY past the TTL is dropped entirely. Backends
        with an OPEN breaker are withheld from the relay table — the C++
        plane routes around them exactly like the Python plane."""
        last_table: Dict[str, list] = {}
        while not self._stop_event.wait(1.0):
            with self._relay_lock:
                seen = dict(self._relay_seen)
            if not seen:
                continue
            now = time.monotonic()
            table = {}
            expired = []
            for cluster, last_live in seen.items():
                try:
                    nodes = [(n.host, n.port)
                             for n in self.members.actives(cluster)]
                except Exception:  # broad-ok — carry last known
                    log.debug("relay refresh failed for %s", cluster,
                              exc_info=True)
                    nodes = last_table.get(cluster, [])
                if nodes:
                    open_set = {k for k in nodes
                                if not self.breakers.available(k)}
                    healthy = [k for k in nodes if k not in open_set]
                    table[cluster] = healthy or nodes  # fail static
                    with self._relay_lock:
                        if cluster in self._relay_seen:
                            self._relay_seen[cluster] = now
                elif now - last_live > self._RELAY_SEEN_TTL:
                    expired.append(cluster)
            if expired:
                with self._relay_lock:
                    for cluster in expired:
                        self._relay_seen.pop(cluster, None)
            last_table = table
            try:
                self.rpc.relay_config(
                    self._relay_methods, table,
                    timeout=self.args.interconnect_timeout,
                    idle_expire=self.args.session_pool_expire)
            except Exception:  # broad-ok — next tick retries
                log.debug("relay config push failed", exc_info=True)

    def _route_cht(self, name: str, cht_n: int,
                   reducer: Callable[[Any, Any], Any],
                   cluster: str, actives: Sequence[NodeInfo],
                   params: Sequence[Any]) -> Any:
        """CHT routing over the CACHED ring (rebuilt only on membership
        change). Inside the handoff window after a change:

        - EFFECTFUL calls double-dispatch to the UNION of old and new
          owners — no key has zero owners while rows migrate (the
          per-host-failure tolerance of ``_fan`` means one dead old
          owner cannot fail the call);
        - IDEMPOTENT reads try new owners first and fail over to the
          old ones — whichever actually holds the row answers (a row
          not yet migrated raises an app error on the new owner)."""
        key = str(params[1])
        ring, prev = self.rings.get(cluster, actives)
        nodes = ring.find(key, cht_n)
        if prev is None:
            return self._fan(self._route_candidates(nodes), name, params,
                             reducer)
        old_nodes = prev.find(key, cht_n)
        seen = {n.name for n in nodes}
        extra = [n for n in old_nodes if n.name not in seen]
        if name in self._idempotent:
            # reads: first owner (new ring first, then old) that answers
            last: Optional[BaseException] = None
            for node in list(nodes) + extra:
                with self._counters_lock:
                    self.forward_count += 1
                try:
                    return self._one(node, name, params)
                except Exception as e:  # broad-ok — try the next owner
                    last = e
            if last is not None:
                raise last
            raise RpcNoClient(f"no active {self.engine} servers")
        if extra:
            self.rpc.trace.count("proxy.double_dispatch")
        return self._fan(self._route_candidates(list(nodes) + extra),
                         name, params, reducer)

    def _handler(self, name: str, routing: str, cht_n: int,
                 reducer: Callable[[Any, Any], Any]) -> Callable[..., Any]:
        def handle_once(*params: Any) -> Any:
            actives = self.members.actives(str(params[0]))
            if routing == "broadcast":
                # writes must reach every member: breakers observe but
                # never skip a broadcast (a success even self-heals an
                # open breaker — proof of life)
                return self._fan(actives, name, params, reducer)
            if routing == "cht":
                if len(params) < 2:
                    raise TypeError(f"{name}: cht routing needs a key param")
                return self._route_cht(name, cht_n, reducer,
                                       str(params[0]), actives, params)
            # random (proxy.hpp:229-247) + breaker skip + idempotent
            # failover
            return self._call_random(name, actives, params)

        def handle(*params: Any) -> Any:
            if params and isinstance(params[0], (str, bytes)):
                c = params[0]
                self._note_cluster(c.decode("utf-8", "surrogateescape")
                                   if isinstance(c, bytes) else c)
            self._count(name)
            self._expire_sessions()
            try:
                return handle_once(*params)
            except Exception as e:  # broad-ok — refined below, re-raised
                if not _membership_rejection(e):
                    raise
                # the backend(s) rejected BEFORE applying (draining /
                # stale ring): refresh the membership view and re-route
                # once — safe for effectful calls too
                self._refresh_members(str(params[0]))
                return handle_once(*params)

        return handle

    def _refresh_members(self, cluster: str) -> None:
        """A membership-protocol rejection means this proxy's ring view
        is stale: drop the actives cache (the ring cache revalidates by
        member-list key on the next lookup) and count the event."""
        self.members.invalidate(cluster)
        self.rpc.trace.count("proxy.ring_refresh")

    def _raw_handler(self, name: str) -> Callable[[bytes], Any]:
        """Zero-decode relay for RANDOM-routed methods: forward the raw
        params span to one backend and splice its raw result span into the
        response (rpc/server.py RawResult) — the multi-megabyte train/
        classify payloads never materialize as Python objects at the
        proxy, matching the reference proxy's C++ forwarding cost shape
        (proxy.hpp:64-186). Anything irregular (no actives, backend
        error/IO, undecodable name) declines to the generic path, which
        owns retry and error taxonomy. Breaker-aware like the generic
        path: open backends are skipped, and IDEMPOTENT methods fail over
        to the next replica on a transport failure."""
        from jubatus_tpu.rpc.server import RAW_FALLBACK, RawResult

        idem = name in self._idempotent

        def handle(raw_params: bytes) -> Any:
            cluster = _peek_cluster_name(raw_params)
            if cluster is None:
                return RAW_FALLBACK  # odd wire: generic path decides
            self._note_cluster(cluster)
            self._expire_sessions()
            actives = self.members.actives(cluster)
            if not actives:
                return RAW_FALLBACK  # generic path raises RpcNoClient
            # counted only once we own the request: every RAW_FALLBACK
            # re-enters the generic handler, which counts it there
            self._count(name)
            candidates = self._route_candidates(actives)
            random.shuffle(candidates)
            last: Optional[BaseException] = None
            tried = 0
            for node in candidates:
                key = (node.host, node.port)
                if not self.breakers.allow(key):
                    continue
                tried += 1
                with self._counters_lock:
                    self.forward_count += 1
                sess = self._checkout(node)
                try:
                    span = sess.client.call_raw(name, raw_params)
                except _TRANSPORT_ERRORS as e:
                    # transport failure AFTER the request may have reached
                    # the backend: for an EFFECTFUL method a silent
                    # re-forward would double-apply a train batch, so
                    # propagate — reads fail over to the next replica.
                    # Tear the node's sessions down either way.
                    sess.client.close()
                    self._drop_sessions(node)
                    self.breakers.record(key, False)
                    with self._counters_lock:
                        self.forward_errors += 1
                    if not idem:
                        raise
                    rem = deadlines.remaining()
                    if rem is not None and rem <= 0:
                        raise
                    if not self.retry_budget.try_withdraw():
                        self.rpc.trace.count("rpc.retry_budget_exhausted")
                        raise
                    self.rpc.trace.count("rpc.retries")
                    last = e
                    continue
                except DeadlineExceeded:
                    sess.client.close()
                    raise
                except _MEMBERSHIP_ERRORS as e:
                    # the backend refused BEFORE applying (draining):
                    # healthy connection, so pool it — then move to the
                    # next replica regardless of idempotency
                    self._checkin(node, sess)
                    self.breakers.record(key, True)
                    self._refresh_members(cluster)
                    last = e
                    continue
                except Exception:  # broad-ok — app error: backend alive
                    # application error from a HEALTHY backend (non-nil
                    # error span): the connection read the full response —
                    # return it to the pool and relay the error as-is
                    self._checkin(node, sess)
                    self.breakers.record(key, True)
                    raise
                self._checkin(node, sess)
                self.breakers.record(key, True)
                return RawResult(span)
            if last is not None:
                raise last
            if not tried:
                return RAW_FALLBACK  # all probes busy: generic path decides
            raise RpcNoClient(f"no active {self.engine} servers")

        # era-safe for every client: call_raw pins pooled backend
        # connections MODERN via its str8 method encoding, so a legacy
        # client's relayed span can never latch a shared connection
        # legacy; legacy clients get their response re-encoded old-raw by
        # build_response's RawResult materialization
        return handle

    def _register(self, name: str, arity: int, routing: str,
                  reducer: Callable[[Any, Any], Any], cht_n: int = 2) -> None:
        self.rpc.register(name, self._handler(name, routing, cht_n, reducer),
                          arity=arity)
        if routing == "random" and hasattr(self.rpc, "register_raw"):
            self.rpc.register_raw(name, self._raw_handler(name))
            self._relay_methods.append(name)

    def _register_methods(self) -> None:
        for m in get_service(self.engine):
            if m.routing == INTERNAL:
                continue  # create_node_here etc. are server↔server only
            self._register(m.name, len(m.args) + 1, m.routing,
                           aggregators.BY_NAME.get(m.aggregator, aggregators.pass_),
                           m.cht_n)
        # built-ins (proxy.cpp:43-66; get_config routes like any analysis call)
        self._register("get_config", 1, "random", aggregators.pass_)
        self._register("save", 2, "broadcast", aggregators.merge)
        self._register("load", 2, "broadcast", aggregators.all_and)
        self._register("get_status", 1, "broadcast", aggregators.merge)
        self._register("get_metrics", 1, "broadcast", aggregators.merge)
        self._register("get_mix_history", 1, "broadcast", aggregators.concat)
        # trace forensics (ISSUE 4): broadcast + fold the proxy's OWN
        # records into the reply, so one call against the proxy returns
        # the full cross-node view (the proxy hop is part of the trace)
        self.rpc.register("get_spans",
                          self._forensics_handler(
                              "get_spans", self.get_proxy_spans),
                          arity=2)
        self.rpc.register("get_slow_log",
                          self._forensics_handler(
                              "get_slow_log", self.get_proxy_slow_log),
                          arity=1)
        # model-health plane (ISSUE 7): one call against the proxy
        # returns the whole cluster's time-series/alert state (backends
        # broadcast + the proxy's own hop folded in)
        self.rpc.register("get_timeseries",
                          self._forensics_handler(
                              "get_timeseries", self.get_proxy_timeseries),
                          arity=1)
        self.rpc.register("get_alerts",
                          self._forensics_handler(
                              "get_alerts", self.get_proxy_alerts),
                          arity=1)
        # data-quality plane (ISSUE 17): one call against the proxy
        # returns every backend's mergeable sketch doc keyed by node —
        # jubactl folds them with quality.merge_quality, so fleet drift
        # is recomputed exactly from the merged sketches
        self.rpc.register("get_quality",
                          self._forensics_handler(
                              "get_quality", self.get_proxy_quality),
                          arity=1)
        # usage-attribution plane (ISSUE 19): one call against the
        # proxy returns every node's mergeable ledger doc keyed by node
        # (proxy hop included) — jubactl folds them with
        # usage.merge_usage (sketch merge, never gauge averaging)
        self.rpc.register("get_usage",
                          self._forensics_handler(
                              "get_usage", self.get_proxy_usage),
                          arity=1)
        # continuous profiling plane (ISSUE 8): one get_profile against
        # the proxy returns the whole cluster's folded stacks (backends
        # broadcast + the proxy's own samples); device captures
        # broadcast so `jubactl -c profile --device` hits every backend
        self.rpc.register("get_profile",
                          self._forensics_handler(
                              "get_profile", self.get_proxy_profile),
                          arity=2)
        self._register("profile_device", 2, "broadcast", aggregators.merge)
        # event plane + incident bundles (ISSUE 14): one call against
        # the proxy returns the whole cluster's causally merged events /
        # bundle index (backends broadcast + the proxy's own folded in)
        self.rpc.register("get_events",
                          self._forensics_handler(
                              "get_events", self.get_proxy_events),
                          arity=3)
        self.rpc.register("get_incidents",
                          self._forensics_handler(
                              "get_incidents", self.get_proxy_incidents),
                          arity=2)
        self._register("do_mix", 1, "random", aggregators.pass_)
        # elastic membership (ISSUE 10): ring-version probe routes like
        # any read (all backends agree modulo watch latency)
        self._register("get_epoch", 1, "random", aggregators.pass_)
        self.rpc.register("get_proxy_status", self.get_proxy_status, arity=1)
        self.rpc.register("get_proxy_metrics", self.get_metrics, arity=1)
        self.rpc.register("get_proxy_spans", self.get_proxy_spans, arity=2)
        self.rpc.register("get_proxy_slow_log", self.get_proxy_slow_log,
                          arity=1)
        self.rpc.register("get_proxy_timeseries", self.get_proxy_timeseries,
                          arity=1)
        self.rpc.register("get_proxy_alerts", self.get_proxy_alerts,
                          arity=1)
        self.rpc.register("get_proxy_quality", self.get_proxy_quality,
                          arity=1)
        self.rpc.register("get_proxy_usage", self.get_proxy_usage,
                          arity=1)
        self.rpc.register("get_proxy_profile", self.get_proxy_profile,
                          arity=2)
        self.rpc.register("get_proxy_events", self.get_proxy_events,
                          arity=3)
        self.rpc.register("get_proxy_incidents", self.get_proxy_incidents,
                          arity=2)
        self.rpc.register("get_breakers", self.get_breakers, arity=1)

    def _forensics_handler(self, name: str,
                           own_fn: Callable[..., Dict[str, Any]]
                           ) -> Callable[..., Dict[str, Any]]:
        """Broadcast ``name`` to the backends and fold the proxy's OWN
        records in — a proxied trace/slow-log query returns every hop of
        the story in one call. Backend failures (no actives, partial
        cluster) degrade to whatever answered plus the proxy's view: a
        forensics query against a sick cluster is exactly when partial
        data matters most."""
        fan = self._handler(name, "broadcast", 2, aggregators.merge)

        def handle(*params: Any) -> Dict[str, Any]:
            out: Dict[str, Any] = {}
            try:
                folded = fan(*params)
                if isinstance(folded, dict):
                    out.update(folded)
            except Exception:  # broad-ok — partial forensics beat none
                log.debug("%s backend broadcast failed", name,
                          exc_info=True)
            out.update(own_fn(*params))
            return out

        return handle

    # -- own status (proxy_common::get_status) --------------------------------
    def get_proxy_spans(self, _name: str = "",
                        trace_id: str = "") -> Dict[str, Any]:
        """This proxy's OWN span records for one trace (its dispatch and
        per-backend client-call spans), keyed by proxy node name."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.get_spans(str(trace_id))}

    def get_proxy_slow_log(self, _name: str = "") -> Dict[str, Any]:
        """This proxy's slow-request ring (tail-based capture of the
        proxy hop itself)."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.slowlog.snapshot()}

    def _model_health_tick(self) -> None:
        """Telemetry tick: ring sample + SLO evaluation (ISSUE 7) +
        the degraded-healthz incident trigger (ISSUE 14)."""
        if self.timeseries is None or self._in_health_tick:
            return
        self._in_health_tick = True
        try:
            # proxies have no device plane: capacity 0 keeps the
            # capacity.* gauges quiet while per-tenant demand publishes
            if self.usage is not None:
                self.usage.tick(0.0)
            self.timeseries.sample(self.rpc.trace.snapshot())
            if self.slo is not None:
                self.slo.evaluate()
            degraded = bool(self._health().get("degraded_reasons"))
            if degraded and not self._was_degraded:
                self.incidents.trigger("healthz_degraded")
            self._was_degraded = degraded
        finally:
            self._in_health_tick = False

    # -- event plane + incident bundles (ISSUE 14) ----------------------------
    def get_proxy_events(self, _name: str = "", since: int = 0,
                         grep: str = "") -> Dict[str, Any]:
        """This proxy's OWN event journal (breaker transitions, SLO
        edges at the proxy hop) merged with the process default journal;
        the RPC-routed ``get_events`` additionally broadcasts."""
        from jubatus_tpu.utils import events as ev

        node = NodeInfo(self.args.bind_host,
                        self.rpc.port or self.args.rpc_port)
        grep = grep.decode() if isinstance(grep, bytes) else str(grep or "")
        recs = ev.merge_events([
            self.rpc.trace.events.snapshot(since=int(since or 0), grep=grep),
            ev.default_journal().snapshot(since=int(since or 0), grep=grep),
        ])
        return {node.name: {"events": recs, "hlc_now": ev.hlc_now(),
                            "stats": self.rpc.trace.events.stats()}}

    def get_proxy_incidents(self, _name: str = "",
                            incident_id: str = "") -> Dict[str, Any]:
        """This proxy's incident bundles: empty id lists, a concrete id
        returns the full forensic doc."""
        node = NodeInfo(self.args.bind_host,
                        self.rpc.port or self.args.rpc_port)
        incident_id = incident_id.decode() \
            if isinstance(incident_id, bytes) else str(incident_id or "")
        if incident_id:
            return {node.name: self.incidents.get(incident_id)}
        return {node.name: self.incidents.list()}

    def _incident_dir(self) -> str:
        return getattr(self.args, "incident_dir", "") or os.path.join(
            "/tmp", f"jubatus_incidents_{self.engine}_proxy_"
            f"{self.rpc.port or self.args.rpc_port}")

    def _on_slo_fire(self, name: str, _state: Dict[str, Any]) -> None:
        ids = [r.get("trace_id", "")
               for r in self.rpc.trace.slowlog.snapshot(last=16)]
        self.incidents.trigger(f"slo_firing:{name}",
                               trace_ids=[t for t in ids if t][-8:])

    def _incident_state(self) -> Dict[str, Any]:
        """Proxy-flavored forensic snapshot: events, timeseries, slow
        log, per-backend breaker state, profiler tail, health."""
        from jubatus_tpu.utils import events as ev

        doc: Dict[str, Any] = {
            "node": NodeInfo(self.args.bind_host,
                             self.rpc.port or self.args.rpc_port).name,
            "events": ev.merge_events([
                self.rpc.trace.events.snapshot(limit=256),
                ev.default_journal().snapshot(limit=64)]),
            "slow_log": self.rpc.trace.slowlog.snapshot(last=64),
            "breakers": self.breakers.snapshot(),
            "health": self._health(),
        }
        if self.usage is not None:
            doc["usage"] = self.usage.incident_doc()
        if self.timeseries is not None:
            doc["timeseries"] = self.timeseries.points(last=60)
        try:
            prof = self.profiler.profile(30.0)
            folded = prof.get("folded") or {}
            top = dict(sorted(folded.items(), key=lambda kv: -kv[1])[:50])
            doc["profile"] = {"folded_top": top,
                              "snapshots": prof.get("snapshots") or [],
                              "stats": prof.get("stats") or {}}
        except Exception:  # broad-ok — forensics must not block capture
            log.debug("incident profile fold failed", exc_info=True)
        return doc

    def get_proxy_timeseries(self, _name: str = "") -> Dict[str, Any]:
        """This proxy's OWN metric time-series ring (the RPC-routed
        ``get_timeseries`` additionally broadcasts to the backends)."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        if self.timeseries is None:
            return {node.name: {"stats": {}, "points": []}}
        return {node.name: {"stats": self.timeseries.stats(),
                            "points": self.timeseries.points()}}

    def get_proxy_alerts(self, _name: str = "") -> Dict[str, Any]:
        """This proxy's OWN SLO state (firing alerts + burn rates)."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        if self.slo is None:
            return {node.name: {"alerts": [], "slos": []}}
        return {node.name: {"alerts": self.slo.alerts(),
                            "slos": self.slo.status()}}

    def get_proxy_quality(self, _name: str = "") -> Dict[str, Any]:
        """The proxy hop has no train path, so it contributes no
        quality doc of its own — the RPC-routed ``get_quality`` is the
        backend broadcast folded over this empty dict."""
        return {}

    def get_proxy_usage(self, _name: str = "") -> Dict[str, Any]:
        """This proxy's OWN per-tenant ledger doc, keyed by proxy node
        name — unlike quality, the proxy hop has real cost to report
        (every forward dispatches here). The RPC-routed ``get_usage``
        is the backend broadcast folded over this."""
        node = NodeInfo(self.args.bind_host,
                        self.rpc.port or self.args.rpc_port)
        if self.usage is None:
            return {node.name: {}}
        return {node.name: self.usage.snapshot()}

    def get_proxy_profile(self, _name: str = "",
                          seconds: float = 0.0) -> Dict[str, Any]:
        """This proxy's OWN folded stack profile (the RPC-routed
        ``get_profile`` additionally broadcasts to the backends)."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        return {node.name: self.profiler.profile(float(seconds or 0.0))}

    def get_breakers(self, _name: str = "") -> Dict[str, Dict[str, Any]]:
        """Breaker + retry-budget state, keyed by proxy node name — the
        ``jubactl -c breakers`` view and the ops answer to 'why is this
        backend getting no traffic?'."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        return {node.name: {
            "breakers": self.breakers.snapshot(),
            "retry_budget": self.retry_budget.status(),
        }}

    def get_proxy_status(self, _name: str = "") -> Dict[str, Dict[str, Any]]:
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        # requests the C++ relay served never reach Python — fold its
        # per-method counts into the same counters the reference reports
        relayed: Dict[str, int] = {}
        if hasattr(self.rpc, "relay_stats"):
            try:
                relayed = self.rpc.relay_stats()
            except Exception:  # broad-ok — status must never fail
                log.debug("relay stats fetch failed", exc_info=True)
        relay_errors = relayed.pop("__errors__", 0)
        breakers = self.breakers.snapshot()
        with self._counters_lock:
            st: Dict[str, Any] = {
                "timestamp": int(time.time()),  # wall-clock
                "uptime": int(time.time() - self.start_time),  # wall-clock
                "type": f"{self.engine}_proxy",
                "version": __version__,
                "forward_count": self.forward_count + sum(relayed.values()),
                "forward_errors": self.forward_errors + relay_errors,
                "session_pool_size": sum(
                    len(v) for v in self._pool.values()),
                "relay_count": sum(relayed.values()),
            }
            counts = dict(self.request_counts)
            for m, c in relayed.items():
                counts[m] = counts.get(m, 0) + c
            st.update({f"request.{k}": v for k, v in counts.items()})
        st["breaker_backends"] = len(breakers)
        st["breaker_open"] = sum(
            1 for b in breakers.values() if b["state"] == "open")
        st["breaker_opened_total"] = sum(
            b["opened_total"] for b in breakers.values())
        # elastic membership (ISSUE 10): ring-cache engagement + how
        # many clusters are inside a double-dispatch handoff window
        for k, v in self.rings.stats().items():
            st[f"ring.{k}"] = v
        for k, v in self.retry_budget.status().items():
            st[f"retry_budget.{k}"] = v
        st.update(self.args.flags_status())
        st["rpc.transport"] = self.rpc.transport
        # span histograms + counters (same registry /metrics exposes) —
        # the proxy hop's rpc.* quantiles and trace ids sit next to the
        # backends' in a merged get_status view
        st.update(self.rpc.trace.trace_status())
        st.update({f"runtime.{k}": v
                   for k, v in self.telemetry.status().items()})
        st.update({f"slowlog.{k}": v
                   for k, v in self.rpc.trace.slowlog.stats().items()})
        st.update({f"profiler.{k}": v
                   for k, v in self.profiler.stats().items()})
        st.update({f"events.{k}": v
                   for k, v in self.rpc.trace.events.stats().items()})
        st.update({f"incident.{k}": v
                   for k, v in self.incidents.stats().items()})
        # usage-attribution plane (ISSUE 19): the per-tenant summary
        if self.usage is not None:
            st.update({f"usage.{k}": v
                       for k, v in self.usage.stats().items()})
        return {node.name: st}

    def get_metrics(self, _name: str = "") -> Dict[str, Dict[str, Any]]:
        """This proxy's own mergeable metrics snapshot (the RPC-routed
        ``get_metrics`` fans out to the backends instead)."""
        node = NodeInfo(self.args.bind_host, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.snapshot()}

    def _health(self) -> Dict[str, Any]:
        with self._counters_lock:
            fwd, errs = self.forward_count, self.forward_errors
        breakers = self.breakers.snapshot()
        # structured degraded reasons (ISSUE 7): open backend breakers
        # + firing proxy-side SLOs, same shape as the servers' /healthz
        reasons: List[Dict[str, Any]] = []
        open_backends = sorted(
            str(k) for k, b in breakers.items() if b["state"] == "open")
        if open_backends:
            reasons.append({"kind": "breaker_open",
                            "count": len(open_backends),
                            "backends": open_backends})
        if self.slo is not None:
            for a in self.slo.alerts():
                reasons.append({"kind": "slo_firing", "name": a["name"],
                                "burn_fast": a.get("burn_fast"),
                                "burn_slow": a.get("burn_slow")})
        doc = {"engine": f"{self.engine}_proxy",
               "status": "degraded" if reasons else "ok",
               "degraded_reasons": reasons,
               "uptime_s": int(time.time() - self.start_time),  # wall-clock
               "rpc_port": self.rpc.port or self.args.rpc_port,
               "forward_count": fwd, "forward_errors": errs,
               "breaker_open": len(open_backends)}
        pstats = self.profiler.stats()
        doc["profiler_hz"] = pstats["hz"]
        doc["profiler_samples"] = pstats["samples"]
        rt = self.telemetry.status()
        for k in ("rss_bytes", "open_fds", "threads", "slowlog_depth"):
            if k in rt:
                doc[k] = rt[k]
        return doc

    # -- lifecycle ------------------------------------------------------------
    def start(self, port: Optional[int] = None) -> int:
        actual = self.rpc.serve_background(
            port if port is not None else self.args.rpc_port,
            nthreads=self.args.thread,
            host=self.args.bind_host,
        )
        self.args.rpc_port = actual
        # event plane (ISSUE 14): attribute this proxy's events by its
        # bound node name
        self.rpc.trace.events.node = NodeInfo(self.args.bind_host,
                                              actual).name
        self.telemetry.start()
        self.profiler.start()
        if getattr(self.args, "metrics_port", -1) >= 0:
            from jubatus_tpu.utils.metrics_http import MetricsServer

            self.metrics = MetricsServer(
                self.rpc.trace,
                labels={"engine": f"{self.engine}_proxy",
                        "node": f"{self.args.bind_host}_{actual}"},
                health_fn=self._health,
                host=self.args.bind_host, port=self.args.metrics_port)
            self.args.metrics_port = self.metrics.start()
            log.info("proxy metrics endpoint on %s:%d", self.args.bind_host,
                     self.args.metrics_port)
        try:
            membership.register_proxy(self.coord, self.args.bind_host, actual)
        except Exception:  # broad-ok — registry is informational for proxies
            log.debug("proxy registration failed", exc_info=True)
        log.info("%s proxy listening on %s:%d", self.engine, self.args.bind_host, actual)
        return actual

    def join(self) -> None:
        self._stop_event.wait()

    def stop(self) -> None:
        if self.usage is not None:
            from jubatus_tpu.utils import usage as usage_mod

            usage_mod.detach(self.usage)
        self.rpc.stop()
        self.telemetry.stop()
        self.profiler.stop()
        if self.metrics is not None:
            try:
                self.metrics.stop()
            except Exception:  # broad-ok — teardown must finish
                log.debug("metrics endpoint stop failed", exc_info=True)
        with self._pool_lock:
            for lst in self._pool.values():
                for sess in lst:
                    sess.client.close()
            self._pool.clear()
        self._executor.shutdown(wait=False)
        self.coord.close()
        self._stop_event.set()


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m jubatus_tpu.server.proxy <engine> -z <coord> [-p PORT]``
    (≙ juba<engine>_proxy binaries)."""
    import argparse
    import signal
    import sys

    p = argparse.ArgumentParser(prog="jubatus_tpu.server.proxy")
    p.add_argument("engine")
    p.add_argument("-p", "--rpc-port", type=int, default=9199)
    p.add_argument("-b", "--listen-addr", default="")
    p.add_argument("-c", "--thread", type=int, default=4)
    p.add_argument("-t", "--timeout", type=float, default=10.0)
    p.add_argument("-z", "--coordinator", required=True)
    p.add_argument("--interconnect-timeout", type=float, default=10.0)
    p.add_argument("--pool-expire", dest="session_pool_expire", type=float, default=60.0)
    p.add_argument("--pool-size", dest="session_pool_size", type=int, default=0)
    p.add_argument("--legacy-wire", action="store_true",
                   help="FORCE responses into the pre-str8/bin msgpack "
                        "format for unmodified legacy jubatus clients "
                        "(otherwise autodetected per connection)")
    p.add_argument("--modern-wire", action="store_true",
                   help="disable per-connection legacy-wire autodetection")
    p.add_argument("--metrics-port", type=int, default=-1,
                   help="serve Prometheus /metrics + /healthz on this "
                        "HTTP port (0 = ephemeral; default off)")
    p.add_argument("--breaker-failures", type=int, default=5,
                   help="transport failures within --breaker-window that "
                        "open a backend's circuit breaker")
    p.add_argument("--breaker-window", type=float, default=30.0)
    p.add_argument("--breaker-cooldown", type=float, default=5.0,
                   help="seconds an open breaker refuses traffic before "
                        "admitting a half-open probe")
    p.add_argument("--retry-budget-ratio", type=float, default=0.1,
                   help="failover retries allowed per first-attempt "
                        "forward (token bucket; 0 disables failover)")
    p.add_argument("--slowlog-capacity", type=int, default=256,
                   help="slow-request ring size at the proxy hop "
                        "(0 disables tail-based capture)")
    p.add_argument("--slowlog-quantile", type=float, default=0.99,
                   help="per-span histogram quantile at/above which a "
                        "forwarded request is captured in the slow log")
    p.add_argument("--slowlog-min-count", type=int, default=64,
                   help="samples a span needs before slow-log "
                        "thresholding starts")
    p.add_argument("--telemetry-interval", type=float, default=10.0,
                   help="runtime telemetry sampling period in seconds "
                        "(0 disables the sampler thread)")
    p.add_argument("--slo", action="append", default=None, metavar="SPEC",
                   help="declarative SLO at the proxy hop, evaluated as "
                        "a multi-window burn rate (repeatable; same "
                        "grammar as the servers: latency:<span>:p<QQ>:"
                        "<threshold_ms>[:<objective>], error_rate:"
                        "<span|*>:<objective>, gauge:<key>:<ceiling>)")
    p.add_argument("--slo-fast-window", type=float, default=300.0,
                   help="fast burn-rate window in seconds")
    p.add_argument("--slo-slow-window", type=float, default=3600.0,
                   help="slow burn-rate window in seconds")
    p.add_argument("--slo-burn-threshold", type=float, default=2.0,
                   help="fire when BOTH windows burn at/above this "
                        "multiple of the sustainable budget spend")
    p.add_argument("--timeseries-capacity", type=int, default=360,
                   help="metric time-series ring depth (points; 0 "
                        "disables the ring and SLO evaluation)")
    p.add_argument("--profile-hz", type=float, default=67.0,
                   help="always-on stack sampling rate at the proxy hop "
                        "(Hz); the proxy's samples fold into jubactl -c "
                        "profile next to the backends'; 0 disables")
    p.add_argument("--profile-trigger-breaches", type=int, default=3,
                   help="slow-log captures of the SAME span inside "
                        "--profile-trigger-window that auto-capture a "
                        "profile snapshot (once per window; 0 disables)")
    p.add_argument("--profile-trigger-window", type=float, default=10.0,
                   help="breach-counting window (seconds) for the "
                        "tail-triggered profile snapshot")
    p.add_argument("--handoff-window", type=float, default=15.0,
                   help="seconds after a membership change during which "
                        "CHT-routed effectful calls double-dispatch to "
                        "the union of old and new ring owners (no key "
                        "has zero owners while rows migrate); idempotent "
                        "reads fail over new->old instead")
    p.add_argument("--event-capacity", type=int, default=2048,
                   help="cluster event journal depth at the proxy hop "
                        "(breaker transitions, proxy SLO edges; served "
                        "by get_events / jubactl -c timeline); 0 "
                        "disables emission")
    p.add_argument("--incident-window", type=float, default=300.0,
                   help="debounce window (seconds) for automatic "
                        "incident bundles at the proxy hop: a firing "
                        "proxy SLO or degraded /healthz captures ONE "
                        "correlated snapshot per window; 0 disables")
    p.add_argument("--incident-dir", default="",
                   help="capped incident-bundle artifacts dir (oldest "
                        "pruned); empty = under /tmp keyed by the "
                        "bound port")
    p.add_argument("--usage-top", type=int, default=64,
                   help="exact per-principal usage-ledger rows at the "
                        "proxy hop (overflow folds into the '(other)' "
                        "row backed by a heavy-hitter sketch; 0 "
                        "disables the ledger)")
    p.add_argument("--usage-gauge-principals", type=int, default=8,
                   help="top-N principals published as "
                        "usage.<principal>.* gauges each telemetry tick")
    ns = p.parse_args(argv)
    ns.slo = ns.slo or []
    args = ProxyArgs(**{f.name: getattr(ns, f.name)
                        for f in dataclasses.fields(ProxyArgs)
                        if hasattr(ns, f.name)})
    for spec in args.slo:
        from jubatus_tpu.utils.slo import parse_slo

        try:  # reject bad grammar at argv time
            parse_slo(spec)
        except ValueError as e:
            raise SystemExit(str(e))
    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s %(levelname)s [{args.engine}_proxy:{args.rpc_port}] %(message)s",
    )
    proxy = Proxy(args)
    signal.signal(signal.SIGTERM, lambda *_: proxy.stop())
    signal.signal(signal.SIGINT, lambda *_: proxy.stop())
    proxy.start()
    proxy.join()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
