"""EngineServer — lifecycle + built-ins (≙ framework/server_base.{hpp,cpp} +
server_helper.{hpp,cpp} collapsed into one class).

Owns: driver, mixer, RPC server, optional coordinator session. Serves the
engine's IDL methods (bound by server/service.py) plus the reference's
built-ins — get_config / save / load / get_status / do_mix — and, when
distributed, the mixer's internal API and membership registration with the
suicide watcher (server_helper.cpp:96-112).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from jubatus_tpu.coord import create_coordinator, membership
from jubatus_tpu.coord.base import Coordinator, NodeInfo
from jubatus_tpu.coord.idgen import IdGenerator
from jubatus_tpu.framework.linear_mixer import RpcLinearMixer
from jubatus_tpu.framework.push_mixer import PushCommunication, create_mixer
from jubatus_tpu.framework.save_load import load_model, save_model
from jubatus_tpu.server.args import ServerArgs
from jubatus_tpu.server.factory import create_driver
from jubatus_tpu.version import __version__

log = logging.getLogger(__name__)


class EngineServer:
    def __init__(
        self,
        engine: str,
        config: Any,
        args: Optional[ServerArgs] = None,
        coord: Optional[Coordinator] = None,
    ) -> None:
        self.engine = engine
        self.args = args or ServerArgs(engine=engine)
        if isinstance(config, dict):
            config = json.dumps(config)
        self.config_json: str = config
        mesh = None
        if getattr(self.args, "shard_devices", 0) > 1:
            import jax
            from jax.sharding import Mesh

            # local_devices: on a multi-host runtime jax.devices() spans
            # every process, and device_put on non-addressable devices fails
            devs = jax.local_devices()[: self.args.shard_devices]
            if len(devs) < self.args.shard_devices:
                raise ValueError(
                    f"--shard-devices {self.args.shard_devices} but only "
                    f"{len(devs)} local devices present")
            mesh = Mesh(devs, axis_names=("shard",))
        # --fault: arm boot-time fault-injection rules (utils/faults.py;
        # process-global by design — the chaos plane models the process,
        # not one server object, exactly like the env-var path)
        fault_rules = getattr(self.args, "fault", None) or []
        if fault_rules:
            from jubatus_tpu.utils import faults

            faults.arm(*fault_rules)
            log.warning("fault injection armed from --fault: %s",
                        ", ".join(fault_rules))
        self.driver = create_driver(
            engine, json.loads(config), mesh=mesh,
            shard_features=getattr(self.args, "shard_features", 0),
            ann=getattr(self.args, "ann", "off"),
            ann_cells=getattr(self.args, "ann_cells", 0),
            ann_nprobe=getattr(self.args, "ann_nprobe", 8))
        # --fv-cache-size: rebound the converter's tokenization/name memo
        # caches (core/fv/converter.py; default matches the flag default)
        conv = getattr(self.driver, "converter", None)
        if conv is not None and hasattr(conv, "set_cache_size"):
            conv.set_cache_size(getattr(self.args, "fv_cache_size", 65536))
        self.start_time = time.time()  # wall-clock
        self.last_saved = 0.0
        self.last_loaded = 0.0
        #: train-path microbatch coalescers by method name (service.py
        #: populates; stats surface in get_status)
        self.coalescers: Dict[str, Any] = {}
        # transport: python sockets, or the C++ front-end when
        # JUBATUS_TPU_NATIVE_RPC=1 (rpc/native_server.py)
        from jubatus_tpu.rpc.native_server import create_rpc_server

        self.rpc = create_rpc_server(
            timeout=self.args.timeout,
            legacy_wire=getattr(self.args, "legacy_wire", False),
            wire_detect=not getattr(self.args, "modern_wire", False))
        # one timeline (ISSUE 24): this process owns the chip, so its
        # registry's spans also open profiler annotations (they land in a
        # profile_device capture beside the device's operations), and the
        # driver records its step phases into the same registry
        import jax.profiler

        self.rpc.trace.annotate = jax.profiler.TraceAnnotation
        self.driver.trace = self.rpc.trace
        # forensics plane (ISSUE 4): slow-request ring tuning off the
        # --slowlog-* flags, and the runtime telemetry sampler thread
        self.rpc.trace.slowlog.configure(
            capacity=getattr(self.args, "slowlog_capacity", 256),
            quantile=getattr(self.args, "slowlog_quantile", 0.99),
            min_count=getattr(self.args, "slowlog_min_count", 64))
        from jubatus_tpu.utils.runtime_telemetry import RuntimeTelemetry

        self.telemetry = RuntimeTelemetry(
            self.rpc.trace,
            interval_sec=getattr(self.args, "telemetry_interval", 10.0))
        # continuous profiling plane (ISSUE 8): always-on stack sampler
        # + capped device-capture dir + the slowlog tail trigger that
        # snapshots the sampler when one span breaches repeatedly
        from jubatus_tpu.utils.profiler import SamplingProfiler

        self.profiler = SamplingProfiler(
            self.rpc.trace, hz=getattr(self.args, "profile_hz", 67.0))
        #: created lazily (_device_capture()): the default artifacts dir
        #: carries the BOUND rpc port, which an ephemeral-port start
        #: only resolves at serve time
        self.device_capture = None
        trig = getattr(self.args, "profile_trigger_breaches", 3)
        if trig > 0 and self.profiler.enabled:
            self.rpc.trace.slowlog.set_trigger(
                self.profiler.tail_snapshot, breaches=trig,
                window_s=getattr(self.args, "profile_trigger_window", 10.0))
        # model-health plane (ISSUE 7): the metric time-series ring +
        # the SLO burn-rate engine, both ticked by the telemetry
        # sampler (one thread owns all periodic observability work)
        from jubatus_tpu.utils.slo import SloEngine, parse_slo
        from jubatus_tpu.utils.timeseries import TimeSeriesRing

        ts_cap = getattr(self.args, "timeseries_capacity", 360)
        interval = self.telemetry.interval_sec
        self.timeseries: Optional[TimeSeriesRing] = None
        self.slo: Optional[SloEngine] = None
        if ts_cap > 0:
            self.timeseries = TimeSeriesRing(
                capacity=ts_cap,
                min_spacing_s=min(1.0, interval / 2) if interval > 0
                else 0.0)
            self.slo = SloEngine(
                [parse_slo(s) for s in getattr(self.args, "slo", []) or []],
                self.timeseries, self.rpc.trace,
                fast_window_s=getattr(self.args, "slo_fast_window", 300.0),
                slow_window_s=getattr(self.args, "slo_slow_window", 3600.0),
                burn_threshold=getattr(
                    self.args, "slo_burn_threshold", 2.0))
            self.telemetry.hooks.append(self._model_health_tick)
        # cluster event plane + incident bundles (ISSUE 14): bound the
        # journal from the flag, and arm the two incident triggers —
        # SLO transitioning to firing, /healthz transitioning degraded
        from jubatus_tpu.utils.incidents import IncidentManager

        self.rpc.trace.events.set_capacity(
            getattr(self.args, "event_capacity", 2048))
        self.incidents = IncidentManager(
            self.rpc.trace, self._incident_state, self._incident_dir,
            window_s=getattr(self.args, "incident_window", 300.0),
            journal=self.rpc.trace.events)
        if self.slo is not None:
            self.slo.on_fire = self._on_slo_fire
        self._was_degraded = False
        # data-quality plane (ISSUE 17): mergeable drift sketches +
        # prequential accuracy, sampled by --quality-sample and ticked
        # by the same telemetry thread (gauges land BEFORE the ring
        # samples, so quality.drift.* is SLO-able with zero new grammar)
        from jubatus_tpu.utils.quality import QualityPlane

        self.quality: Optional[QualityPlane] = None
        qs = getattr(self.args, "quality_sample", 0.05)
        if qs > 0:
            self.quality = QualityPlane(
                sample=qs,
                window_s=getattr(self.args, "quality_window", 60.0),
                ref_windows=getattr(self.args, "quality_ref_windows", 2),
                registry=self.rpc.trace)
            conv = getattr(self.driver, "converter", None)
            if conv is not None and hasattr(conv, "quality_hook"):
                conv.quality_hook = self.quality.record_named
        # usage-attribution plane (ISSUE 19): per-principal resource
        # ledger. Wired three ways: the registry's usage_sink feeds it
        # every rpc.<method> span's CPU-seconds while the dispatch
        # thread still holds the request's principal; the transport's
        # usage_recorder notes errors + bytes; service.py binds the
        # coalescer usage_hook for queue/device attribution. Ticked by
        # the telemetry thread (gauges land BEFORE the ring samples, so
        # capacity.saturation is SLO-able with zero new grammar).
        from jubatus_tpu.utils import usage as usage_mod

        self.usage: Optional[usage_mod.UsageLedger] = None
        ut = getattr(self.args, "usage_top", 64)
        if ut > 0:
            self.usage = usage_mod.UsageLedger(
                top=ut,
                gauge_principals=getattr(
                    self.args, "usage_gauge_principals", 8),
                registry=self.rpc.trace)
            self.rpc.usage_recorder = self.usage
            self.rpc.trace.usage_sink = self.usage.span_sink
            usage_mod.attach(self.usage)
        #: re-entrancy guard: the incident collector reads _health(),
        #: whose telemetry.status() re-runs the sampler hooks — the
        #: tick must not recurse into itself mid-capture
        self._in_health_tick = False
        self._stop_event = threading.Event()
        self._stop_once = threading.Lock()  # first stop() wins; rest no-op
        # elastic membership (ISSUE 10): migration counters + the drain
        # state machine + a cached membership-epoch view (refreshed by
        # the same actives watch that invalidates the CHT snapshot)
        from jubatus_tpu.framework.migration import (DrainController,
                                                     MigrationStats)

        self.migration = MigrationStats(registry=self.rpc.trace)
        self.drain_ctl = DrainController(
            self, grace_sec=getattr(self.args, "drain_grace", 1.0))
        self._epoch_cache: Optional[int] = None
        # model-integrity plane (ISSUE 15): bounded ring of periodic
        # in-process model snapshots (save_load envelope + CRC32) —
        # the "last good" that jubactl -c rollback and the guard's
        # non-finite-total auto-rollback restore. Ticked by the same
        # telemetry thread that owns all periodic observability work.
        from jubatus_tpu.framework.model_guard import ModelSnapshotRing

        self.snapshots = ModelSnapshotRing()
        self._snapshot_interval = getattr(
            self.args, "model_snapshot_interval", 0.0)
        self._last_snapshot = 0.0
        self.rollbacks = 0
        self._last_rollback_ts = 0.0
        if self._snapshot_interval > 0:
            self.telemetry.hooks.append(self._model_snapshot_tick)
        # durable model plane (ISSUE 18): the shared snapshot store +
        # the background diff-chain uploader (created at start(), once
        # the bound port names this node) + warm-boot bookkeeping
        self.store = None
        self.store_uploader = None
        self.warmboot: Dict[str, Any] = {}
        self._store_interval = getattr(self.args, "store_interval", 0.0)
        self._last_store_upload = 0.0
        store_dir = getattr(self.args, "store_dir", "")
        if store_dir:
            from jubatus_tpu.framework.model_store import (LocalDirBackend,
                                                           ModelStore)

            self.store = ModelStore(
                LocalDirBackend(store_dir),
                cluster=self.args.name or "standalone", engine=engine,
                counter=self.rpc.trace.count)
            if self._store_interval > 0:
                self.telemetry.hooks.append(self._store_upload_tick)
        #: Prometheus /metrics + /healthz endpoint (--metrics-port >= 0)
        self.metrics = None
        #: pooled peer clients for server-side replicated writes
        self._peers: Dict[str, Any] = {}
        self._peer_lock = threading.Lock()
        #: watch-invalidated CHT snapshot (cluster_cht)
        self._cht_cache = None
        self._cht_expiry = 0.0
        self._cht_watched = False
        self._cht_lock = threading.Lock()

        # distributed wiring (server_helper ctor path, server_helper.cpp:48-78)
        self.coord = coord
        self.mixer: Optional[RpcLinearMixer] = None
        if not self.args.is_standalone or coord is not None:
            if self.coord is None:
                self.coord = create_coordinator(self.args.coordinator)
            comm = PushCommunication(
                self.coord, engine, self.args.name,
                timeout=self.args.interconnect_timeout,
            )
            # mixer strategy by --mixer flag (≙ mixer_factory)
            self.mixer = create_mixer(
                self.args.mixer, self.driver, comm,
                self_node=NodeInfo(self.args.eth, self.args.rpc_port),
                interval_sec=self.args.interval_sec,
                interval_count=self.args.interval_count,
                mix_compress=getattr(self.args, "mix_compress", "off"),
                mix_bf16=getattr(self.args, "mix_bf16", False),
                mix_topology=getattr(self.args, "mix_topology", ""),
                quorum_fraction=getattr(self.args, "mix_quorum", 0.5),
                mix_async=getattr(self.args, "mix_async", False),
                mix_staleness_bound=getattr(
                    self.args, "mix_staleness_bound", 8),
                mix_guard=getattr(self.args, "mix_guard", "warn"),
                mix_norm_bound=getattr(
                    self.args, "mix_norm_bound", 10.0),
            )
            self.mixer.set_trace_registry(self.rpc.trace)
            # model-integrity plane (ISSUE 15): a put_diff refusing a
            # non-finite folded total auto-rolls back to last-good
            if hasattr(self.mixer, "on_poisoned_total"):
                self.mixer.on_poisoned_total = self._auto_rollback
            # cluster-unique id minting for the engines that mint ids
            # (≙ global_id_generator_zk: anomaly add, graph create_node/edge)
            if hasattr(self.driver, "set_id_generator"):
                self.driver.set_id_generator(IdGenerator(
                    self.coord,
                    f"{membership.actor_path(engine, self.args.name)}/id_generator",
                ))
            # count updates into the mixer (server_base.cpp:214-219)
            driver_event = self.driver.event_model_updated

            def chained(n: int = 1) -> None:
                driver_event(n)
                self.mixer.updated(n)

            self.driver.event_model_updated = chained  # type: ignore[assignment]

        # self-tuning performance plane (ISSUE 20): the telemetry-to-
        # knobs loop (coord/perf_tuner.py) rides the same telemetry tick
        # as every other periodic plane. Created AFTER the mixer block —
        # its adapter reads self.mixer/self.coalescers as they exist now.
        from jubatus_tpu.coord.perf_tuner import (PerfTuner,
                                                  ServerTuneAdapter,
                                                  TunerConfig)

        self.tuner: Optional[PerfTuner] = None
        tune_mode = getattr(self.args, "auto_tune", "off")
        if tune_mode != "off":
            self.tuner = PerfTuner(
                TunerConfig(
                    mode=tune_mode,
                    interval_floor_s=getattr(
                        self.args, "tune_interval_floor", 1.0),
                    interval_ceiling_s=getattr(
                        self.args, "tune_interval_ceiling", 120.0)),
                ServerTuneAdapter(self), registry=self.rpc.trace)
            self.telemetry.hooks.append(self._tune_tick)

    def _tune_tick(self) -> None:
        """One perf-tuner pass per telemetry tick (PerfTuner.tick never
        raises — a sick adapter must not kill the telemetry thread)."""
        if self.tuner is not None:
            self.tuner.tick()

    def get_tune(self, _name: str = "") -> Dict[str, Any]:
        """This node's self-tuning state (coord/perf_tuner.py): mode,
        per-plane core state, backoff, and the decision journal — the
        per-node half of ``jubactl -c tune``."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.tuner is None:
            return {node.name: {}}
        return {node.name: self.tuner.status()}

    # -- construction from files/argv (run_server, server_util.hpp:139-176) --
    @classmethod
    def from_args(cls, args: ServerArgs,
                  coord: Optional[Coordinator] = None) -> "EngineServer":
        if args.configpath:
            with open(args.configpath) as f:
                config = f.read()
        elif not args.is_standalone:
            if coord is None:
                coord = create_coordinator(args.coordinator)
            raw = coord.read(membership.config_path(args.engine, args.name))
            if raw is None:
                raise RuntimeError(
                    f"no config registered for {args.engine}/{args.name} "
                    "(use jubaconfig to write one)"
                )
            return cls(args.engine, raw.decode(), args, coord=coord)
        else:
            raise RuntimeError("standalone mode requires -f/--configpath")
        srv = cls(args.engine, config, args, coord=coord)
        if args.model_file:
            srv.load_file(args.model_file)
        return srv

    # -- peer RPC (server-side replicated writes, anomaly_serv.cpp:275-297) --
    def self_nodeinfo(self) -> NodeInfo:
        return NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)

    def peer_client(self, node: NodeInfo):
        """Pooled RPC client to a cluster peer (≙ the reference's
        client-to-peer sessions in selective_update)."""
        from jubatus_tpu.rpc.client import RpcClient

        with self._peer_lock:
            cli = self._peers.get(node.name)
            if cli is None:
                cli = RpcClient(node.host, node.port,
                                self.args.interconnect_timeout)
                self._peers[node.name] = cli
            return cli

    def _close_peers(self) -> None:
        with self._peer_lock:
            peers = list(self._peers.values())
            self._peers.clear()
        for cli in peers:
            try:
                cli.close()
            except Exception:  # broad-ok — teardown
                pass

    def drop_peer_client(self, node: NodeInfo) -> None:
        with self._peer_lock:
            cli = self._peers.pop(node.name, None)
        if cli is not None:
            try:
                cli.close()
            except Exception:  # broad-ok
                pass

    def cluster_cht(self):
        """CHT over the current actives (cht.cpp:107-143); None in
        standalone mode. Cached: the ring is a pure function of
        membership, so it rebuilds only when the membership watcher fires
        (or on TTL expiry for coordinators with best-effort watches) —
        never per write (replicated add/create_node is the ingest hot
        path)."""
        if self.coord is None:
            return None
        from jubatus_tpu.coord.cht import CHT

        now = time.monotonic()
        with self._cht_lock:
            if self._cht_cache is not None and now < self._cht_expiry:
                return self._cht_cache
        cht = CHT.from_coordinator(self.coord, self.engine, self.args.name)
        with self._cht_lock:
            self._cht_cache = cht
            self._cht_expiry = now + 2.0
            if not self._cht_watched:
                self._cht_watched = True
                path = membership.actor_path(
                    self.engine, self.args.name) + "/actives"
                try:
                    self.coord.watch_children(
                        path, lambda _p: self._invalidate_cht())
                except NotImplementedError:
                    pass
        return cht

    def _invalidate_cht(self) -> None:
        with self._cht_lock:
            self._cht_cache = None
            self._epoch_cache = None

    # -- elastic membership (ISSUE 10) ---------------------------------------
    def membership_epoch(self) -> int:
        """Current membership epoch, cached alongside the CHT snapshot
        (both invalidate on the same actives watch). Standalone: 0."""
        if self.coord is None:
            return 0
        with self._cht_lock:
            cached = self._epoch_cache
        if cached is not None:
            return cached
        epoch = membership.get_epoch(self.coord, self.engine, self.args.name)
        with self._cht_lock:
            self._epoch_cache = epoch
        self.rpc.trace.gauge("cluster.epoch", float(epoch))
        return epoch

    def get_epoch(self, _name: str = "") -> int:
        # the CHT cache TTL (2 s) bounds staleness; a watch-driven
        # invalidation makes it immediate
        self.cluster_cht()
        return self.membership_epoch()

    def migrate_range(self, _name: str, epoch: int, target: str,
                      cursor: str = "", limit: int = 0) -> Dict[str, Any]:
        """SOURCE side of the state-migration plane: rows after
        ``cursor`` that ``target`` owns under the current ring. The
        caller's epoch must match mine — a mismatch is the retryable
        ``EpochMismatch`` that forces a ring refresh on the puller
        (framework/migration.py)."""
        from jubatus_tpu.framework.migration import (DEFAULT_CHUNK_BYTES,
                                                     serve_range)
        from jubatus_tpu.rpc.errors import EpochMismatch

        mine = self.get_epoch()
        if int(epoch) != mine:
            raise EpochMismatch(expected=mine, got=int(epoch))
        target = target.decode() if isinstance(target, bytes) else str(target)
        cursor = cursor.decode() if isinstance(cursor, bytes) else str(cursor)
        ring = self.cluster_cht()
        if ring is None:
            return {"rows": [], "cursor": "", "done": True, "epoch": mine}
        if target not in {m.name for m in ring.members}:
            # the joiner may register between my watch ticks: extend the
            # ring view rather than reject (same members → same ring)
            from jubatus_tpu.coord.cht import CHT

            try:
                node = NodeInfo.from_name(target)
            except (ValueError, IndexError):
                return {"rows": [], "cursor": "", "done": True,
                        "epoch": mine}
            ring = CHT(list(ring.members) + [node], epoch=ring.epoch)
        with self.driver.lock:
            doc = serve_range(self.driver, ring, target, cursor,
                              int(limit) or DEFAULT_CHUNK_BYTES)
        doc["epoch"] = mine
        return doc

    def put_rows(self, _name: str, rows: Any) -> int:
        """Apply migrated rows (already-hashed vectors — no reconvert).
        Drivers without row hooks accept nothing (0)."""
        if not hasattr(self.driver, "put_rows"):
            return 0
        with self.driver.lock:
            n = int(self.driver.put_rows(rows or []))
        return n

    def get_row_count(self, _name: str = "") -> int:
        if hasattr(self.driver, "row_ids"):
            with self.driver.lock:
                return len(self.driver.row_ids())
        return 0

    def drain(self, _name: str = "", stop_after: bool = False) -> Dict[str, Any]:
        """Begin the drain state machine (framework/migration.py):
        reject new effectful work (retryable ``NodeDraining`` — proxies
        re-route), finish in-flight, hand rows to their new owners,
        unregister. Idempotent; returns the current state doc."""
        if self.coord is None:
            return {"state": "active", "error": "standalone: nothing to drain"}
        self.drain_ctl.start(stop_after=bool(stop_after))
        return self.drain_status()

    def drain_status(self, _name: str = "") -> Dict[str, Any]:
        doc = self.drain_ctl.status()
        doc["epoch"] = self.membership_epoch()
        return doc

    def rebalance(self, _name: str = "") -> Dict[str, Any]:
        """Pull every row this member owns under the CURRENT ring from
        the other actives — the joining member's half of the migration
        plane (also the ``jubactl -c rebalance`` repair action). Safe to
        re-run: rows apply as overwrites."""
        if self.coord is None or not hasattr(self.driver, "put_rows"):
            return {"rows": 0, "bytes": 0, "seconds": 0.0,
                    "mb_per_sec": 0.0, "sources_failed": []}
        from jubatus_tpu.framework.migration import RangePuller

        me = self.self_nodeinfo()
        sources = [m for m in membership.get_all_actives(
            self.coord, self.engine, self.args.name) if m.name != me.name]
        if not sources:
            return {"rows": 0, "bytes": 0, "seconds": 0.0,
                    "mb_per_sec": 0.0, "sources_failed": []}

        def apply_rows(rows) -> int:
            with self.driver.lock:
                return int(self.driver.put_rows(rows))

        puller = RangePuller(
            self.args.name, me.name, apply_rows,
            client_factory=self.peer_client, stats=self.migration,
            epoch_of=lambda: self.get_epoch())
        return puller.pull(sources)

    def _join_migration(self) -> None:
        """Background join-time pull: a freshly-registered replica
        streams its owned ranges from the current owners. Best-effort —
        a failed pull leaves the replica serving what the mix plane
        replicates; ``jubactl -c rebalance`` repairs."""
        try:
            out = self.rebalance(self.args.name)
            if out.get("rows"):
                log.info("join migration: pulled %d rows (%.2f MB) in %.2fs",
                         out["rows"], out["bytes"] / 2 ** 20, out["seconds"])
        except Exception:  # broad-ok — join must not die on migration
            log.warning("join migration failed", exc_info=True)

    # -- model-integrity plane: snapshots + rollback (ISSUE 15) --------------
    def _model_snapshot_tick(self) -> None:
        """One telemetry tick: take a model snapshot into the rollback
        ring when the interval elapsed (the first tick seeds the
        baseline — a poisoning incident in the first minutes of a
        process's life still has a last-good to return to)."""
        now = time.monotonic()
        if self._last_snapshot and \
                now - self._last_snapshot < self._snapshot_interval:
            return
        try:
            self.take_snapshot()
        except Exception:  # broad-ok — a failed snapshot must not kill
            log.warning("model snapshot failed", exc_info=True)  # the tick

    def take_snapshot(self) -> Dict[str, Any]:
        """Capture one in-process model snapshot (CRC-stamped save_load
        envelope) into the bounded rollback ring."""
        version = getattr(self.mixer, "model_version", 0) \
            if self.mixer is not None else 0
        with self.driver.lock:
            entry = self.snapshots.snapshot(self.driver, version)
        self._last_snapshot = time.monotonic()
        self.rpc.trace.gauge("mix.snapshots",
                             float(self.snapshots.stats()["count"]))
        return {k: v for k, v in entry.items() if k != "blob"}

    def rollback(self, _name: str = "", reason: str = "") -> Dict[str, Any]:
        """Restore the newest last-good snapshot into the live model
        (``jubactl -c rollback --target`` / the guard's auto-rollback).
        The restore revalidates the envelope CRC; the mixer's model
        version rebases to the snapshot's — in a healthy cluster the
        next round's version gate then pulls this node forward again,
        while in a poisoning incident (every guarded member refused the
        same total) the fleet stays consistently on last-good."""
        reason = reason.decode() if isinstance(reason, bytes) \
            else str(reason or "operator")
        entry = self.snapshots.latest()
        if entry is None:
            return {"rolled_back": False,
                    "error": "no model snapshot retained "
                             "(--model-snapshot-interval off?)"}
        with self.driver.lock:
            version = self.snapshots.restore(self.driver)
        if self.mixer is not None and \
                hasattr(self.mixer, "model_version"):
            self.mixer.model_version = version
        self.rollbacks += 1
        self._last_rollback_ts = time.monotonic()
        self.rpc.trace.count("mix.rollbacks")
        self.rpc.trace.events.emit(
            "mix", "rollback", severity="error", reason=reason,
            model_version=version)
        # a rollback is a forensics moment: bundle the window around it
        self.incidents.trigger(f"rollback:{reason}")
        log.error("model rolled back to snapshot v%d (%s)", version,
                  reason)
        return {"rolled_back": True, "model_version": version,
                "snapshot_ts": entry["ts"], "reason": reason,
                "snapshots": self.snapshots.stats()}

    def _auto_rollback(self) -> None:
        """Wired as the mixer's on_poisoned_total callback: put_diff
        refused a non-finite folded total — return to last-good."""
        out = self.rollback(self.args.name, reason="nonfinite_total")
        if not out.get("rolled_back"):
            log.error("auto-rollback unavailable: %s", out.get("error"))

    # -- durable model plane: store uploads + warm-boot + restore (ISSUE 18) --
    def _store_node_name(self) -> str:
        return NodeInfo(self.args.eth,
                        self.rpc.port or self.args.rpc_port).name

    def _store_upload_tick(self) -> None:
        """One telemetry tick of the background uploader: snapshot →
        diff vs the chain's belief → upload (full every
        --store-compact-every diffs, with store-side compaction).
        Upload failures are counted by the store and must never touch
        the serving path."""
        if self.store_uploader is None:
            return
        now = time.monotonic()
        if self._last_store_upload and \
                now - self._last_store_upload < self._store_interval:
            return
        self._last_store_upload = now
        # upload clock: local training progress + mix progress — either
        # one advancing means the model changed (a mix-only replica has
        # update_count 0; a mix-never fleet has model_version 0)
        version = int(self.driver.update_count)
        if self.mixer is not None:
            version += int(getattr(self.mixer, "model_version", 0) or 0)
        if version == 0 and not self.last_loaded:
            return  # pristine model: nothing worth a store record yet
        try:
            self.store_uploader.tick(self.driver, version)
        except Exception:  # broad-ok — a flaky store must not kill the tick
            log.warning("store upload failed", exc_info=True)

    def _warm_boot(self) -> None:
        """The warm-boot ladder (boot-time, BEFORE the ring sees this
        node): load the freshest store snapshot + diff chain into the
        driver, rebase the mixer's model version to the chain head, and
        let the normal mix plane (put_diff version gate → obsolete
        recovery) catch the tail up. ANY failure — no snapshot, CRC
        refusal, config mismatch, flaky store — degrades to cold boot +
        join migration, never a partial model (counted + evented)."""
        from jubatus_tpu.framework.save_load import (SaveLoadError,
                                                     load_model_bytes)

        t0 = time.monotonic()
        self.rpc.trace.count("warmboot.attempts")
        outcome = "cold"
        meta: Dict[str, Any] = {}
        try:
            got = self.store.latest()
            if got is None:
                if self.store.records(kind="full"):
                    # records exist but NONE materialized (corrupt/flaky
                    # store): that is a degrade, not a clean cold boot
                    raise SaveLoadError(
                        "store records present but none materializable")
                self.rpc.trace.count("warmboot.no_snapshot")
            else:
                blob, meta = got
                with self.driver.lock:
                    load_model_bytes(blob, self.driver,
                                     where=f"store:{meta['key']}",
                                     expected_config=self.config_json)
                if self.mixer is not None and \
                        hasattr(self.mixer, "model_version"):
                    self.mixer.model_version = int(meta["model_version"])
                self.last_loaded = time.time()  # wall-clock
                outcome = "warm"
                self.rpc.trace.count("warmboot.warm")
        except Exception as e:  # broad-ok — ANY failure degrades to cold
            outcome = "degraded_to_cold"
            self.rpc.trace.count("warmboot.degraded_to_cold")
            self.rpc.trace.events.emit(
                "warmboot", "degraded_to_cold", severity="warning",
                error=str(e)[:200])
            log.warning("warm boot degraded to cold: %s", e)
        seconds = round(time.monotonic() - t0, 3)
        self.rpc.trace.gauge("warmboot.seconds", seconds)
        self.warmboot = {
            "outcome": outcome, "seconds": seconds,
            "model_version": int(meta.get("model_version", 0)),
            "chain_len": int(meta.get("chain_len", 0)),
            "hlc": int(meta.get("hlc", 0)),
        }
        if outcome == "warm":
            self.rpc.trace.events.emit(
                "warmboot", "loaded", model_version=meta["model_version"],
                chain_len=meta["chain_len"], seconds=seconds)
            log.info("warm boot: model v%d (+%d diffs) in %.3fs",
                     meta["model_version"], meta["chain_len"], seconds)

    def store_restore(self, _name: str = "", at: int = 0) -> Dict[str, Any]:
        """Point-in-time restore from the store (``jubactl -c restore
        --at HLC|latest`` fans this out fleet-wide). Loads the freshest
        snapshot at/before ``at`` (0 = latest) as this node's model,
        then — for row-holding drivers — unions in the rows THIS node
        owns under the CURRENT ring from every other uploading node's
        snapshot: an N-shard fleet snapshot restores onto an M-shard
        fleet (reshard-on-restore through the store)."""
        from jubatus_tpu.framework.save_load import (SaveLoadError,
                                                     load_model_bytes)

        if self.store is None:
            return {"restored": False, "error": "no --store-dir configured"}
        hlc_at = int(at or 0) or None
        t0 = time.monotonic()
        got = self.store.latest(at=hlc_at)
        if got is None:
            return {"restored": False,
                    "error": "no store snapshot"
                             + (f" at hlc<={hlc_at}" if hlc_at else "")}
        blob, meta = got
        try:
            with self.driver.lock:
                load_model_bytes(blob, self.driver,
                                 where=f"store:{meta['key']}",
                                 expected_config=self.config_json)
        except SaveLoadError as e:
            return {"restored": False, "error": str(e)[:300]}
        if self.mixer is not None and hasattr(self.mixer, "model_version"):
            self.mixer.model_version = int(meta["model_version"])
        rows = self._restore_rows(hlc_at, skip_node=meta["node"])
        self.last_loaded = time.time()  # wall-clock
        self.rpc.trace.count("store.restores")
        doc = {"restored": True, "model_version": int(meta["model_version"]),
               "hlc": int(meta["hlc"]), "chain_len": int(meta["chain_len"]),
               "primary_node": meta["node"], "rows_imported": rows,
               "seconds": round(time.monotonic() - t0, 3)}
        self.rpc.trace.events.emit("store", "restored", **doc)
        return doc

    def _restore_rows(self, hlc_at: Optional[int], skip_node: str) -> int:
        """Reshard-on-restore: walk every OTHER uploading node's
        materialized snapshot through a scratch driver and put_rows the
        rows this member owns under the current ring (standalone: all
        of them). Row-less drivers import nothing — the primary
        envelope already carried the whole model."""
        if not hasattr(self.driver, "put_rows"):
            return 0
        from jubatus_tpu.framework.migration import serve_range
        from jubatus_tpu.server.factory import create_driver
        from jubatus_tpu.utils.serialization import unpack_obj

        ring = self.cluster_cht()
        me = self._store_node_name()
        imported = 0
        for node, (blob, _meta) in sorted(
                self.store.materialize_all(at=hlc_at).items()):
            if node == skip_node:
                continue
            try:
                from jubatus_tpu.framework.save_load import read_envelope

                _sys, user_bytes = read_envelope(blob, f"store:{node}")
                _uv, state = unpack_obj(user_bytes)
                scratch = create_driver(self.engine,
                                        json.loads(self.config_json))
                scratch.unpack(state)
            except Exception:  # broad-ok — a sick snapshot skips, never aborts
                log.warning("restore: skipping node %s snapshot", node,
                            exc_info=True)
                continue
            if not hasattr(scratch, "row_ids"):
                continue
            if ring is None:
                ids = sorted(scratch.row_ids())
                rows = scratch.get_rows(ids)
                with self.driver.lock:
                    imported += int(self.driver.put_rows(rows))
                continue
            cursor = ""
            while True:
                doc = serve_range(scratch, ring, me, cursor)
                if doc["rows"]:
                    with self.driver.lock:
                        imported += int(self.driver.put_rows(doc["rows"]))
                if doc["done"]:
                    break
                cursor = doc["cursor"]
        return imported

    def get_store_status(self, _name: str = "") -> Dict[str, Any]:
        """The durable plane's view, keyed like get_status: record
        counts, head HLC, per-node chains, this node's warm-boot
        outcome — what ``jubactl -c restore`` consults for --at."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.store is None:
            return {node.name: {}}
        doc: Dict[str, Any] = dict(self.store.stats())
        doc["warmboot"] = dict(self.warmboot)
        doc["store_dir"] = getattr(self.args, "store_dir", "")
        doc["records"] = [
            {"kind": r.kind, "hlc": r.hlc, "version": r.version,
             "node": r.node} for r in self.store.records()[-64:]]
        return {node.name: doc}

    # -- built-in RPCs (server_base.hpp:41-109, client.hpp:30-87) ------------
    def get_config(self, _name: str = "") -> str:
        return self.config_json

    def model_path(self, model_id: str) -> str:
        """<datadir>/<ip>_<port>_<type>_<id>.jubatus (server_base.cpp:41-49)."""
        node = NodeInfo(self.args.eth, self.args.rpc_port)
        return os.path.join(
            self.args.datadir, f"{node.name}_{self.engine}_{model_id}.jubatus"
        )

    def save(self, _name: str, model_id: str) -> Dict[str, str]:
        """Write the node-local envelope AND (durable model plane,
        ISSUE 18) upload the same bytes to the shared store, so the
        snapshot survives the node that took it. The reply carries the
        per-node path plus the store id under ``store:<node>`` — a
        later ``load`` on ANY member accepts ``store:<key>``."""
        model_id = model_id.decode() if isinstance(model_id, bytes) \
            else str(model_id)
        path = self.model_path(model_id)
        with self.driver.lock:
            save_model(path, self.driver, model_id=model_id,
                       config=self.config_json)
        self.last_saved = time.time()  # wall-clock
        node = NodeInfo(self.args.eth, self.args.rpc_port)
        out = {node.name: path}
        if self.store is not None:
            version = getattr(self.mixer, "model_version", 0) \
                if self.mixer is not None else int(self.driver.update_count)
            try:
                with open(path, "rb") as f:
                    blob = f.read()
                out[f"store:{node.name}"] = self.store.put_blob(
                    blob, kind="full", node=node.name,
                    model_version=version)
            except Exception:  # broad-ok — local save stands on its own
                log.warning("save: store upload failed", exc_info=True)
        return out

    def load(self, _name: str, model_id: str) -> bool:
        """Load by model id. Accepts a store id from a save reply
        (``store:<key>`` — fetched + CRC-validated from the shared
        store), and falls back to the store when the node-local file is
        missing (a replacement node loading a snapshot its predecessor
        took): the newest full record whose system container carries
        this model id."""
        from jubatus_tpu.framework.save_load import load_model_bytes

        model_id = model_id.decode() if isinstance(model_id, bytes) \
            else str(model_id)
        if model_id.startswith("store:") and self.store is not None:
            key = model_id[len("store:"):]
            blob = self.store.fetch(key)
            with self.driver.lock:
                load_model_bytes(blob, self.driver, where=f"store:{key}",
                                 expected_config=self.config_json)
            self.last_loaded = time.time()  # wall-clock
            return True
        try:
            self.load_file(self.model_path(model_id))
        except FileNotFoundError:
            if self.store is None or not self._load_from_store(model_id):
                raise
        return True

    def _load_from_store(self, model_id: str) -> bool:
        """Store fallback for ``load``: scan the newest full records for
        one saved under ``model_id`` (bounded scan — save-uploaded
        records, not the background chain, carry ids)."""
        from jubatus_tpu.framework.save_load import (SaveLoadError,
                                                     load_model_bytes,
                                                     read_envelope)
        from jubatus_tpu.utils.serialization import unpack_obj

        for rec in reversed(self.store.records(kind="full")[-32:]):
            try:
                blob = self.store.fetch(rec.key)
                system = unpack_obj(read_envelope(blob, rec.key)[0])
                if system.get("id") != model_id:
                    continue
                with self.driver.lock:
                    load_model_bytes(blob, self.driver,
                                     where=f"store:{rec.key}",
                                     expected_config=self.config_json)
            except (SaveLoadError, OSError):
                continue  # corrupt/missing record: keep scanning
            self.last_loaded = time.time()  # wall-clock
            log.info("load: %s restored from store record %s",
                     model_id, rec.key)
            return True
        return False

    def load_file(self, path: str) -> None:
        with self.driver.lock:
            load_model(path, self.driver, expected_config=self.config_json)
        self.last_loaded = time.time()  # wall-clock

    def do_mix(self, _name: str = "") -> bool:
        if self.mixer is None:
            return False
        return self.mixer.mix_now() is not None

    def get_metrics(self, _name: str = "") -> Dict[str, Dict[str, Any]]:
        """Raw mergeable metrics state keyed like get_status: one map per
        node name, holding span histogram buckets + counters. ``jubactl
        metrics`` folds these across the cluster for exact merged
        quantiles (bucket-wise sums — see utils/tracing.py)."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.snapshot()}

    def get_spans(self, _name: str, trace_id: str) -> Dict[str, Any]:
        """Span records of one trace from THIS node's span store, keyed
        like get_status — the per-node half of ``jubactl -c trace``
        (the proxy broadcasts this and merges the maps)."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.get_spans(str(trace_id))}

    def get_slow_log(self, _name: str = "") -> Dict[str, Any]:
        """This node's slow-request ring (tail-based capture; see
        utils/slowlog.py), keyed like get_status."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        return {node.name: self.rpc.trace.slowlog.snapshot()}

    # -- continuous profiling plane (ISSUE 8) --------------------------------
    def get_profile(self, _name: str = "", seconds: float = 0.0
                    ) -> Dict[str, Any]:
        """This node's folded stack profile over the last ``seconds``
        (0 = every retained bucket), keyed like get_status: collapsed
        stacks + sampler stats + the tail-triggered snapshot ring. The
        proxy broadcasts this and folds its own samples in (``jubactl
        -c profile``)."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        return {node.name: self.profiler.profile(float(seconds or 0.0))}

    def _device_capture(self):
        """The capped device-capture dir, created on first use so the
        default path carries the ACTUAL bound rpc port (multiple
        ephemeral-port servers on one host must not share a dir)."""
        if self.device_capture is None:
            from jubatus_tpu.utils.profiler import DeviceCapture

            prof_dir = getattr(self.args, "profile_dir", "") or os.path.join(
                self.args.datadir,
                f"jubatus_profile_{self.engine}_"
                f"{self.rpc.port or self.args.rpc_port}")
            self.device_capture = DeviceCapture(prof_dir)
        return self.device_capture

    def profile_device(self, _name: str = "", seconds: float = 0.0
                       ) -> Dict[str, Any]:
        """On-demand device capture: ``seconds > 0`` runs one bounded
        ``jax.profiler.trace()`` into the capped ``--profile-dir``
        (blocking this RPC worker for the duration); ``seconds == 0``
        lists existing artifacts. Failures return a structured
        ``error`` — a CPU-only box degrades, it doesn't 500."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        s = float(seconds or 0.0)
        if s <= 0:
            return {node.name: self._device_capture().list()}
        doc = self._device_capture().capture(s)
        if "artifact" in doc:
            self.rpc.trace.count("profiler.device_captures")
        return {node.name: doc}

    # -- event plane + incident bundles (ISSUE 14) ---------------------------
    def get_events(self, _name: str = "", since: int = 0,
                   grep: str = "") -> Dict[str, Any]:
        """This node's cluster-event view, keyed like get_status: the
        server registry's journal MERGED with the process default
        journal (membership/fault/checkpoint emissions), causally
        ordered by HLC. ``since`` is an HLC cursor (return events
        strictly after it — the ``--follow`` contract); ``grep`` is a
        substring filter applied server-side."""
        from jubatus_tpu.utils import events as ev

        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        grep = grep.decode() if isinstance(grep, bytes) else str(grep or "")
        recs = ev.merge_events([
            self.rpc.trace.events.snapshot(since=int(since or 0), grep=grep),
            ev.default_journal().snapshot(since=int(since or 0), grep=grep),
        ])
        return {node.name: {"events": recs, "hlc_now": ev.hlc_now(),
                            "stats": self.rpc.trace.events.stats()}}

    def get_incidents(self, _name: str = "",
                      incident_id: str = "") -> Dict[str, Any]:
        """Incident-bundle surface (utils/incidents.py): an empty id
        lists the capped artifacts dir, a concrete id returns that
        bundle's full forensic doc."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        incident_id = incident_id.decode() \
            if isinstance(incident_id, bytes) else str(incident_id or "")
        if incident_id:
            return {node.name: self.incidents.get(incident_id)}
        return {node.name: self.incidents.list()}

    def _incident_dir(self) -> str:
        return getattr(self.args, "incident_dir", "") or os.path.join(
            self.args.datadir,
            f"jubatus_incidents_{self.engine}_"
            f"{self.rpc.port or self.args.rpc_port}")

    def _on_slo_fire(self, name: str, _state: Dict[str, Any]) -> None:
        """SLO transitioned to firing: capture one incident bundle,
        seeded with the breaching trace_ids from the slow log (the
        requests that spent the error budget)."""
        ids = [r.get("trace_id", "")
               for r in self.rpc.trace.slowlog.snapshot(last=16)]
        self.incidents.trigger(f"slo_firing:{name}",
                               trace_ids=[t for t in ids if t][-8:])

    def _incident_state(self) -> Dict[str, Any]:
        """The correlated forensic snapshot one bundle holds: event
        window, timeseries window, slow log, mix flight records,
        profiler tail snapshots, breaker state, health verdict."""
        from jubatus_tpu.utils import events as ev

        doc: Dict[str, Any] = {
            "node": NodeInfo(self.args.eth,
                             self.rpc.port or self.args.rpc_port).name,
            "events": ev.merge_events([
                self.rpc.trace.events.snapshot(limit=256),
                ev.default_journal().snapshot(limit=64)]),
            "slow_log": self.rpc.trace.slowlog.snapshot(last=64),
            "health": self._health(),
        }
        if self.timeseries is not None:
            doc["timeseries"] = self.timeseries.points(last=60)
        if self.quality is not None:
            # names the top drifting group and carries its reference /
            # live sketch pair — the drift-SLO forensic payload
            doc["quality"] = self.quality.incident_doc()
        if self.usage is not None:
            # who was spending the replica when it breached: top
            # principals by CPU with full rows + the capacity picture
            doc["usage"] = self.usage.incident_doc()
        if self.mixer is not None and \
                getattr(self.mixer, "flight", None) is not None:
            doc["mix_history"] = self.mixer.flight.snapshot(last=32)
            breakers = getattr(getattr(self.mixer, "comm", None),
                               "breakers", None)
            if breakers is not None:
                doc["breakers"] = breakers.snapshot()
        try:
            prof = self.profiler.profile(30.0)
            folded = prof.get("folded") or {}
            top = dict(sorted(folded.items(), key=lambda kv: -kv[1])[:50])
            doc["profile"] = {"folded_top": top,
                              "snapshots": prof.get("snapshots") or [],
                              "stats": prof.get("stats") or {}}
        except Exception:  # broad-ok — a sick profiler must not block capture
            log.debug("incident profile fold failed", exc_info=True)
        return doc

    # -- model-health plane (ISSUE 7) ----------------------------------------
    def _model_health_tick(self) -> None:
        """One telemetry tick: gauge the coalescer load signals, then
        snapshot the registry into the time-series ring and re-evaluate
        every SLO's burn rates against the updated ring."""
        if self.timeseries is None or self._in_health_tick:
            return
        self._in_health_tick = True
        try:
            self._model_health_tick_inner()
        finally:
            self._in_health_tick = False

    def _model_health_tick_inner(self) -> None:
        # ingest backpressure gauges (ISSUE 12): queued examples behind
        # the current flush + trailing arrival rate, summed over every
        # train-plane coalescer — the autoscaler's primary signal, so
        # they must ride /metrics and the time-series ring, not just
        # the microbatch.<name>.* stats lines in get_status
        if self.coalescers:
            depth = arrival = 0.0
            for name, co in self.coalescers.items():
                if hasattr(co, "queue_depth"):
                    depth += co.queue_depth()
                    arrival += co.arrival_per_sec()
                # trailing flush-duration EWMA per queue (ISSUE 20): the
                # one drain-rate estimate the coalescer tuner's Little's-
                # law target and the capacity model both read
                st = co.stats() if hasattr(co, "stats") else {}
                fm = st.get("flush_ms_ewma")
                if isinstance(fm, (int, float)) and fm > 0:
                    self.rpc.trace.gauge(
                        f"microbatch.{name}.flush_ms_ewma", float(fm))
            self.rpc.trace.gauge("microbatch.queue_depth", depth)
            self.rpc.trace.gauge("microbatch.arrival_per_sec",
                                 round(arrival, 1))
        # shard-layout gauges (ISSUE 13): shard count, live rows, bytes
        # per arena, and the last sharded top-k merge wall — the keys
        # jubactl -c status/watch render the layout from
        shard_stats = getattr(self.driver, "shard_stats", None)
        if shard_stats is not None:
            doc = shard_stats()
            if doc:
                self.rpc.trace.gauge("shard.count", float(doc["count"]))
                self.rpc.trace.gauge("shard.rows", float(doc.get("rows", 0)))
                self.rpc.trace.gauge("shard.bytes_in_use",
                                     float(doc.get("bytes_in_use", 0)))
                if doc.get("topk_merge_ms") is not None:
                    self.rpc.trace.gauge("shard.topk_merge_ms",
                                         float(doc["topk_merge_ms"]))
        # ANN index gauges (ISSUE 16): cell count, probe width, rescore
        # candidate budget, and the shadow-query recall estimate
        ann_stats = getattr(self.driver, "ann_stats", None)
        if ann_stats is not None:
            doc = ann_stats()
            if doc:
                self.rpc.trace.gauge("ann.cells", float(doc.get("cells", 0)))
                self.rpc.trace.gauge("ann.probed_cells",
                                     float(doc.get("probed_cells", 0)))
                self.rpc.trace.gauge("ann.rescore_candidates",
                                     float(doc.get("rescore_candidates", 0)))
                if doc.get("recall_probe") is not None:
                    self.rpc.trace.gauge("ann.recall_probe",
                                         float(doc["recall_probe"]))
                    # the SLO grammar alarms on HIGH gauges, so recall
                    # sag trends as a deficit: gauge:ann.recall_probe_
                    # deficit:0.1 fires when shadow recall dips < 0.9
                    self.rpc.trace.gauge(
                        "ann.recall_probe_deficit",
                        round(1.0 - float(doc["recall_probe"]), 4))
        # data-quality plane (ISSUE 17): roll windows, recompute PSI
        # drift + prequential gauges — BEFORE the ring samples, so
        # quality.drift.* is visible to gauge: SLOs this same tick
        if self.quality is not None:
            self.quality.tick()
        # usage-attribution plane (ISSUE 19): per-principal demand vs
        # this replica's measured flush throughput — BEFORE the ring
        # samples, so usage.* / capacity.saturation are SLO-able via
        # gauge: this same tick
        if self.usage is not None:
            self.usage.tick(self._capacity_rows_per_sec())
        self.timeseries.sample(self.rpc.trace.snapshot())
        if self.slo is not None:
            self.slo.evaluate()
        # incident trigger #2 (ISSUE 14): /healthz transitioning
        # ok -> degraded captures a bundle (the SLO on_fire trigger
        # usually beats it; the debounce window keeps it to ONE)
        reasons = self._degraded_reasons()
        if reasons and not self._was_degraded:
            self.incidents.trigger(
                "healthz_degraded:" + ",".join(
                    sorted({str(r.get("kind", "?")) for r in reasons})))
        self._was_degraded = bool(reasons)

    def get_timeseries(self, _name: str = "") -> Dict[str, Any]:
        """This node's metric time-series ring (utils/timeseries.py),
        keyed like get_status: ring stats + the raw points, so callers
        (jubactl -c watch) compute windowed rates/quantiles per node
        and fold across the cluster."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.timeseries is None:
            return {node.name: {"stats": {}, "points": []}}
        return {node.name: {"stats": self.timeseries.stats(),
                            "points": self.timeseries.points()}}

    def _capacity_rows_per_sec(self) -> float:
        """This replica's capacity estimate: rows the drain plane moves
        per busy second, from each queue's trailing flush EWMA × its
        average batch — the SAME estimate the coalescer tuner's
        Little's-law target reads (one throughput model, two consumers;
        ISSUE 20). 0 until a flush has actually run — a cold replica
        publishes no headroom rather than a fictitious one."""
        total = 0.0
        for co in self.coalescers.values():
            st = co.stats() if hasattr(co, "stats") else {}
            flush_ms = float(st.get("flush_ms_ewma", 0.0))
            avg_batch = float(st.get("avg_batch", 0.0))
            if flush_ms > 0.0 and avg_batch > 0.0:
                total += avg_batch / (flush_ms / 1e3)
        return total

    def get_usage(self, _name: str = "") -> Dict[str, Any]:
        """This node's usage-attribution doc (utils/usage.py): the
        per-principal × method exact table, heavy-hitter sketch state,
        and capacity picture — mergeable, so the proxy folds the fleet
        with merge_usage (sketch merge + table sum, never gauge
        averaging)."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.usage is None:
            return {node.name: {}}
        return {node.name: self.usage.snapshot()}

    def get_quality(self, _name: str = "") -> Dict[str, Any]:
        """This node's data-quality doc (utils/quality.py): reference
        and live sketch states, drift scores, prequential totals, trend
        — mergeable, so the proxy folds the fleet with merge_quality
        and drift is recomputed exactly from the merged sketches."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.quality is None:
            return {node.name: {}}
        return {node.name: self.quality.snapshot()}

    def get_alerts(self, _name: str = "") -> Dict[str, Any]:
        """This node's SLO state (utils/slo.py): currently-firing
        alerts plus every configured SLO's last-evaluated burn rates —
        the per-node half of ``jubactl -c alerts``."""
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        if self.slo is None:
            return {node.name: {"alerts": [], "slos": []}}
        return {node.name: {"alerts": self.slo.alerts(),
                            "slos": self.slo.status()}}

    def _degraded_reasons(self) -> list:
        """Structured degraded-reason list for /healthz and get_status:
        firing SLOs, open mix breakers, a quorum-degraded last round,
        an obsolete (recovering) model, a torn-down collective plane."""
        reasons: list = []
        if self.slo is not None:
            for a in self.slo.alerts():
                reasons.append({"kind": "slo_firing", "name": a["name"],
                                "burn_fast": a.get("burn_fast"),
                                "burn_slow": a.get("burn_slow")})
        m = self.mixer
        if m is not None:
            breakers = getattr(getattr(m, "comm", None), "breakers", None)
            if breakers is not None:
                open_backends = [k for k, b in breakers.snapshot().items()
                                 if b["state"] == "open"]
                if open_backends:
                    reasons.append({"kind": "mix_breaker_open",
                                    "count": len(open_backends),
                                    "backends": sorted(open_backends)})
            if getattr(m, "last_round_degraded", False):
                reasons.append({"kind": "mix_quorum_degraded"})
            if getattr(m, "_obsolete", False):
                reasons.append({"kind": "model_obsolete",
                                "staleness": getattr(m, "self_staleness", 0)})
            if getattr(m, "collective_dead", False):
                reasons.append({"kind": "collective_dead"})
            # async mix (ISSUE 11): a member lagging past the staleness
            # bound is contributing nothing to the fold — surface it
            # before the ladder demotes it to obsolete
            lag = getattr(m, "async_lag_rounds", 0)
            bound = getattr(m, "staleness_bound", 0)
            if bound and lag > bound:
                reasons.append({"kind": "mix_async_lagging",
                                "lag_rounds": lag,
                                "staleness_bound": bound})
            # model-integrity plane (ISSUE 15): peers behind the
            # quarantine breaker mean part of the fleet's training is
            # being excluded from folds — an operator should look
            guard = getattr(m, "guard", None)
            if guard is not None and guard.enabled:
                q = guard.quarantined()
                if q:
                    reasons.append({"kind": "mix_member_quarantined",
                                    "members": sorted(q)})
        if self.rollbacks and \
                time.monotonic() - self._last_rollback_ts < 600.0:
            # recent rollback (10 min window): the model moved backwards
            # — visible on /healthz while the incident is fresh, then
            # clears (the counter stays in get_status forever)
            reasons.append({"kind": "model_rolled_back",
                            "count": self.rollbacks})
        if self.drain_ctl.state != "active":
            reasons.append({"kind": "draining",
                            "state": self.drain_ctl.state})
        return reasons

    def _health(self) -> Dict[str, Any]:
        """Liveness document for /healthz (utils/metrics_http.py).
        ``status`` degrades to "degraded" with a STRUCTURED reason list
        (ISSUE 7) — orchestration keeps getting its 200 (the process
        serves), operators and the watch view get the why."""
        reasons = self._degraded_reasons()
        doc: Dict[str, Any] = {
            "status": "degraded" if reasons else "ok",
            "degraded_reasons": reasons,
            "engine": self.engine,
            "name": self.args.name,
            "uptime_s": int(time.time() - self.start_time),  # wall-clock
            "rpc_port": self.rpc.port or self.args.rpc_port,
            "update_count": self.driver.update_count,
        }
        if self.slo is not None:
            doc["slo_count"] = len(self.slo.specs)
            doc["slo_firing"] = len(self.slo.alerts())
        if self.mixer is not None:
            doc["mix_count"] = getattr(self.mixer, "mix_count", 0)
        # elastic membership (ISSUE 10): one glance says which ring
        # version this node believes in and whether it is on the way out
        doc["cluster_epoch"] = self.membership_epoch()
        doc["drain_state"] = self.drain_ctl.state
        mig = self.migration.snapshot()
        if mig.get("active") or mig.get("rows_moved"):
            doc["migration_rows_moved"] = mig["rows_moved"]
            doc["migration_active"] = mig["active"]
        # profiler state (ISSUE 8): one glance says whether the sampler
        # is on and collecting (full stats live in get_status)
        pstats = self.profiler.stats()
        doc["profiler_hz"] = pstats["hz"]
        doc["profiler_samples"] = pstats["samples"]
        doc["profiler_snapshots"] = pstats["snapshots_taken"]
        # incident bundles (ISSUE 14): how many forensic snapshots this
        # process has auto-captured (the dir is in get_incidents)
        doc["incidents_captured"] = self.incidents.stats()["captured"]
        # model-integrity plane (ISSUE 15): one glance says whether a
        # last-good exists and whether this model ever rolled back
        doc["model_snapshots"] = self.snapshots.stats()["count"]
        doc["model_rollbacks"] = self.rollbacks
        # runtime telemetry summary (full key set lives in get_status)
        rt = self.telemetry.status()
        for k in ("rss_bytes", "open_fds", "threads",
                  "jax_compile_count", "jax_compile_ms", "slowlog_depth"):
            if k in rt:
                doc[k] = rt[k]
        return doc

    def get_status(self, _name: str = "") -> Dict[str, Dict[str, Any]]:
        """≙ server_helper::get_status (server_helper.hpp:119-219): one map
        keyed by <ip>_<port> with uptime/memory/flags/counters."""
        st: Dict[str, Any] = {
            "timestamp": int(time.time()),  # wall-clock
            "uptime": int(time.time() - self.start_time),  # wall-clock
            "type": self.engine,
            "name": self.args.name,
            "version": __version__,
            "update_count": self.driver.update_count,
            "last_saved": self.last_saved,
            "last_loaded": self.last_loaded,
            "rpc_port": self.rpc.port or self.args.rpc_port,
        }
        try:
            with open("/proc/self/statm") as f:
                pages = f.read().split()
            page = os.sysconf("SC_PAGE_SIZE")
            st["VIRT"] = int(pages[0]) * page
            st["RSS"] = int(pages[1]) * page
            st["SHR"] = int(pages[2]) * page
        except (OSError, IndexError, ValueError):
            pass
        try:
            st["loadavg"] = os.getloadavg()[0]
        except OSError:
            pass
        st.update(self.args.flags_status())
        for nm, co in self.coalescers.items():
            st.update({f"microbatch.{nm}.{k}": v
                       for k, v in co.stats().items()})
        # which transport and parser serve: both quietly downgrade on a
        # host without a compiler, and nothing else tells them apart
        st["rpc.transport"] = self.rpc.transport
        ingest = getattr(self, "ingest_stats", None)
        st["ingest.native"] = ingest is not None
        # the native fast path's flush counters (service.py populates
        # them when it is registered)
        for k, v in (ingest or {}).items():
            st[f"ingest.{k}"] = v
        st.update({f"driver.{k}": v for k, v in self.driver.get_status().items()})
        if self.mixer is not None:
            st.update({f"mixer.{k}": v for k, v in self.mixer.get_status().items()})
        # span histograms + counters (SURVEY §5: tracing the reference
        # never had) — this server's own registry, not the process default
        st.update(self.rpc.trace.trace_status())
        # runtime telemetry sample (RSS, FDs, GC, JAX compile/cache/device
        # memory) + slow-log ring health (utils/runtime_telemetry.py)
        st.update({f"runtime.{k}": v
                   for k, v in self.telemetry.status().items()})
        st.update({f"slowlog.{k}": v
                   for k, v in self.rpc.trace.slowlog.stats().items()})
        # continuous profiling plane (ISSUE 8): sampler health — is it
        # on, how many samples/stacks, how often the tail trigger fired
        st.update({f"profiler.{k}": v
                   for k, v in self.profiler.stats().items()})
        # model-health plane (ISSUE 7): health verdict + time-series
        # ring depth + SLO burn states, so `jubactl -c status --all`
        # and the watch view read one map
        reasons = self._degraded_reasons()
        st["health.status"] = "degraded" if reasons else "ok"
        st["health.reasons"] = reasons
        # elastic membership (ISSUE 10): ring version, drain state, and
        # the migration plane's lifetime counters
        st["cluster.epoch"] = self.membership_epoch()
        st["drain.state"] = self.drain_ctl.state
        st.update({f"migration.{k}": v
                   for k, v in self.migration.snapshot().items()})
        if self.timeseries is not None:
            st.update({f"timeseries.{k}": v
                       for k, v in self.timeseries.stats().items()})
        if self.slo is not None:
            st["slo.configured"] = len(self.slo.specs)
            st["slo.firing"] = len(self.slo.alerts())
        # data-quality plane (ISSUE 17)
        if self.quality is not None:
            st.update({f"quality.{k}": v
                       for k, v in self.quality.stats().items()})
        # usage-attribution plane (ISSUE 19): the per-tenant summary
        # jubactl -c watch's tenant column reads
        if self.usage is not None:
            st.update({f"usage.{k}": v
                       for k, v in self.usage.stats().items()})
        # model-integrity plane (ISSUE 15): snapshot ring + rollbacks
        # (guard state rides mixer.guard_* via the mixer's get_status)
        st.update({f"snapshot.{k}": v
                   for k, v in self.snapshots.stats().items()})
        st["rollback.count"] = self.rollbacks
        # durable model plane (ISSUE 18): store record counts + this
        # node's warm-boot outcome (counters ride trace.counter.store.*)
        if self.store is not None:
            st.update(self.store.stats())
            st.update({f"warmboot.{k}": v
                       for k, v in self.warmboot.items()})
        # event plane + incident bundles (ISSUE 14)
        st.update({f"events.{k}": v
                   for k, v in self.rpc.trace.events.stats().items()})
        st.update({f"incident.{k}": v
                   for k, v in self.incidents.stats().items()})
        # process-wide counters (zk session events, ...) live in the
        # default registry; surface them without clobbering our own
        from jubatus_tpu.utils import tracing as _tracing

        for k, v in _tracing.default_registry().counters().items():
            st.setdefault(f"trace.counter.{k}", v)
        if self.metrics is not None:
            st["metrics_port"] = self.metrics.port
        node = NodeInfo(self.args.eth, self.rpc.port or self.args.rpc_port)
        return {node.name: st}

    # -- lifecycle (server_helper::start, server_helper.hpp:221-262) ---------
    def start(self, port: Optional[int] = None, background: bool = True) -> int:
        from jubatus_tpu.server.service import bind_engine  # cycle-free import

        bind_engine(self.rpc, self)
        if self.mixer is not None:
            self.mixer.register_api(self.rpc)
        # durable model plane (ISSUE 18): warm-boot BEFORE the socket
        # serves and BEFORE membership registration — a spawning
        # replica loads the freshest store snapshot + diff chain, then
        # enters the ring already warm and catches the tail up via the
        # normal mix plane (an autoscaler spawn whose argv carries
        # --store-dir takes this path automatically)
        if self.store is not None \
                and getattr(self.args, "store_warmboot", True) \
                and not self.driver.update_count and not self.last_loaded:
            self._warm_boot()
        actual = self.rpc.serve_background(
            port if port is not None else self.args.rpc_port,
            nthreads=self.args.thread,
            host=self.args.bind_host,
        )
        self.args.rpc_port = actual
        # the background uploader needs the BOUND port for its node
        # name (ephemeral-port starts resolve it only now)
        if self.store is not None and self._store_interval > 0:
            from jubatus_tpu.framework.model_store import StoreUploader

            self.store_uploader = StoreUploader(
                self.store, self._store_node_name(),
                model_id="auto", config=self.config_json,
                compress=getattr(self.args, "store_compress", "off"),
                compact_every=getattr(self.args, "store_compact_every", 8))
        # event plane (ISSUE 14): journals attribute events by node name,
        # which an ephemeral-port bind only resolves now; the process
        # default journal keeps the FIRST server's name (one server per
        # process in production)
        from jubatus_tpu.utils import events as _events

        self.rpc.trace.events.node = NodeInfo(self.args.eth, actual).name
        if not _events.default_journal().node:
            _events.default_journal().node = self.rpc.trace.events.node
        self.telemetry.start()
        self.profiler.start()
        if getattr(self.args, "metrics_port", -1) >= 0:
            from jubatus_tpu.utils.metrics_http import MetricsServer

            node = NodeInfo(self.args.eth, actual)
            self.metrics = MetricsServer(
                self.rpc.trace,
                labels={"engine": self.engine, "cluster": self.args.name,
                        "node": node.name},
                health_fn=self._health,
                host=self.args.bind_host, port=self.args.metrics_port)
            self.args.metrics_port = self.metrics.start()
            log.info("metrics endpoint on %s:%d", self.args.bind_host,
                     self.args.metrics_port)
        if self.coord is not None and self.mixer is not None:
            node = NodeInfo(self.args.eth, actual)
            # ephemeral-port binds (start(0)) resolve only now
            self.mixer.self_node = node
            if getattr(self.mixer, "flight", None) is not None:
                self.mixer.flight.node = node.name
            path = membership.register_actor(
                self.coord, self.engine, self.args.name, node.host, node.port
            )
            membership.register_active(
                self.coord, self.engine, self.args.name, node.host, node.port
            )
            # put_diff outcome drives my own actives entry (through MY
            # coordinator session, so it dies with me, not with the master)
            def on_active(ok: bool, _n=node) -> None:
                if ok:
                    membership.register_active(
                        self.coord, self.engine, self.args.name, _n.host, _n.port
                    )
                else:
                    membership.unregister_active(
                        self.coord, self.engine, self.args.name, _n.host, _n.port
                    )

            self.mixer.on_active = on_active
            # suicide watcher (server_helper.cpp:91-94,105-109)
            self.coord.watch_delete(path, lambda _p: self.stop())
            # keyword/key partitioning: drivers exposing set_assignment
            # (burst) process only their CHT(2)-assigned keys, re-hashed
            # on membership change (burst_serv.cpp:225-239, 264-290)
            if hasattr(self.driver, "set_assignment"):
                self._install_assignment(node)
            self.mixer.start()
            # elastic membership (ISSUE 10): a joining replica streams
            # its owned key ranges from the current owners in the
            # background (CHT-routed engines only — drivers exposing the
            # row hooks). The proxy's double-dispatch window covers the
            # in-between.
            if getattr(self.args, "auto_rebalance", True) and \
                    hasattr(self.driver, "put_rows"):
                threading.Thread(target=self._join_migration,
                                 daemon=True, name="join-migrate").start()
        log.info("%s server listening on %s:%d", self.engine,
                 self.args.bind_host, actual)
        return actual

    def _install_assignment(self, me: NodeInfo) -> None:
        """Wire CHT keyword assignment into the driver and keep it fresh
        across membership changes (≙ the reference's child watcher
        re-hash, burst_serv.cpp:264-290). The predicate snapshots the
        ring at (re)build time; each change swaps in a new snapshot."""
        from jubatus_tpu.coord.cht import CHT

        def rebuild(_path: str = "") -> None:
            try:
                cht = CHT.from_coordinator(
                    self.coord, self.engine, self.args.name,
                    actives_only=False)
            except Exception:  # broad-ok — transient coord trouble
                log.warning("assignment rebuild failed; keeping previous",
                            exc_info=True)
                return
            if not cht.members:
                return

            def assigned(kw: str, _cht=cht, _me=me.name) -> bool:
                return any(n.name == _me for n in _cht.find(kw, 2))

            self.driver.set_assignment(assigned)

        rebuild()
        nodes_dir = membership.actor_path(self.engine, self.args.name) + "/nodes"
        try:
            self.coord.watch_children(nodes_dir, rebuild)
        except NotImplementedError:
            pass  # backends without watches: assignment stays static

    def join(self) -> None:
        self._stop_event.wait()

    def stop(self) -> None:
        # reentry-safe: the suicide watcher and a lost coordinator session
        # can both call stop() concurrently from different threads
        if not self._stop_once.acquire(blocking=False):
            return
        if self.usage is not None:
            # drop out of the process-wide retry fan-in: a stopped
            # server's ledger must not keep collecting another server's
            # client retries (multi-server tests/benches)
            from jubatus_tpu.utils import usage as usage_mod

            usage_mod.detach(self.usage)
        try:
            # each step independently: stop() is unretryable (_stop_once),
            # so one failing step must not skip the others
            for step in (
                (self.mixer.stop if self.mixer is not None else None),
                (self.coord.close if self.coord is not None else None),
                self.rpc.stop,
                (self.metrics.stop if self.metrics is not None else None),
                self.telemetry.stop,
                self.profiler.stop,
                self._close_peers,
            ):
                if step is None:
                    continue
                try:
                    step()
                except Exception:  # broad-ok — teardown must finish
                    log.exception("shutdown step %r failed", step)
        finally:
            # set LAST (join() must not return mid-teardown) but ALWAYS
            self._stop_event.set()
