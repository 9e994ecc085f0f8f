"""Wire adapters: bind drivers onto RpcServer (≙ generated *_impl.cpp).

One binder per engine converts between msgpack wire types (datum 3-tuples,
[k,v] pair lists) and driver types (Datum, tuples), registers each IDL method
under its wire name with the leading cluster-name param every jubatus call
carries, calls driver.event_model_updated() after update methods (the
reference's generated impls do this via lock decorators + serv methods,
classifier_impl.cpp:56-59 → classifier_serv.cpp:127-146), and registers the
built-ins (get_config/save/load/get_status/do_mix, client.hpp:30-87).

Update methods run under the driver lock (JWLOCK_); the built-ins take it
where the reference does.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List

import numpy as np

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.rpc.server import RpcServer
from jubatus_tpu.utils.tracing import current_flush

log = logging.getLogger(__name__)

# -- wire ↔ driver conversions ----------------------------------------------


def _datum(obj: Any) -> Datum:
    return Datum.from_msgpack(obj)


def _datums(objs: Any) -> List[Datum]:
    return [Datum.from_msgpack(o) for o in objs]


def _wire_datum(d: Datum):
    return d.to_msgpack()


def _scored(results: List) -> List:
    """[(id, score)] → [[id, score]] (id_with_score wire shape)."""
    return [[i, float(s)] for i, s in results]


# -- binder registry ---------------------------------------------------------

_BINDERS: Dict[str, Callable[[RpcServer, Any], None]] = {}


def _binder(engine: str):
    def deco(fn):
        _BINDERS[engine] = fn
        return fn

    return deco


def bind_engine(rpc: RpcServer, server: Any) -> None:
    """Register built-ins + the engine's IDL surface on the RPC server."""
    rpc.register("get_config", server.get_config, arity=1)
    rpc.register("save", server.save, arity=2)
    rpc.register("load", server.load, arity=2)
    rpc.register("get_status", server.get_status, arity=1)
    rpc.register("get_metrics", server.get_metrics, arity=1)
    # trace forensics (ISSUE 4): per-trace span store + slow-request ring
    rpc.register("get_spans", server.get_spans, arity=2)
    rpc.register("get_slow_log", server.get_slow_log, arity=1)
    # model-health plane (ISSUE 7): metric time-series + SLO alerts
    rpc.register("get_timeseries", server.get_timeseries, arity=1)
    rpc.register("get_alerts", server.get_alerts, arity=1)
    # data-quality plane (ISSUE 17): mergeable drift/prequential doc
    rpc.register("get_quality", server.get_quality, arity=1)
    # usage-attribution plane (ISSUE 19): per-principal cost ledger doc
    rpc.register("get_usage", server.get_usage, arity=1)
    # self-tuning performance plane (ISSUE 20): tuner state + journal
    rpc.register("get_tune", server.get_tune, arity=1)
    # continuous profiling plane (ISSUE 8): folded stack profile +
    # on-demand XLA device capture
    rpc.register("get_profile", server.get_profile, arity=2)
    rpc.register("profile_device", server.profile_device, arity=2)
    # cluster event plane + incident bundles (ISSUE 14): HLC-ordered
    # event journal (cursor-resumable) + the capped forensic bundles
    rpc.register("get_events", server.get_events, arity=3)
    rpc.register("get_incidents", server.get_incidents, arity=2)
    rpc.register("do_mix", server.do_mix, arity=1)
    # elastic membership (ISSUE 10): ring-version + drain control +
    # the state-migration data plane (framework/migration.py). The
    # migration payloads ship packed row vectors between our own
    # servers — binary=True keeps them modern even under --legacy-wire.
    rpc.register("get_epoch", server.get_epoch, arity=1)
    rpc.register("drain", server.drain, arity=2)
    rpc.register("drain_status", server.drain_status, arity=1)
    rpc.register("rebalance", server.rebalance, arity=1)
    rpc.register("migrate_range", server.migrate_range, arity=5,
                 binary=True)
    rpc.register("put_rows", server.put_rows, arity=2, binary=True)
    rpc.register("get_row_count", server.get_row_count, arity=1)
    # model-integrity plane (ISSUE 15): restore the last-good snapshot
    rpc.register("rollback", server.rollback, arity=2)
    # durable model plane (ISSUE 18): point-in-time restore from the
    # shared snapshot store + the store's status read
    rpc.register("store_restore", server.store_restore, arity=2)
    rpc.register("get_store_status", server.get_store_status, arity=1)
    _BINDERS[server.engine](rpc, server)


def _updating(server: Any, fn: Callable, count: Callable[[Any], int] = lambda r: 1,
              lock_span: str = ""):
    """Wrap an update method: driver lock + event_model_updated (the
    reference's JWLOCK_ + serv-side bookkeeping). Most driver methods bump
    the counter themselves; the wrapper only adds the event when the driver
    didn't, so updates are never double-counted. ``lock_span``: the span
    that times the wait for the lock (the train steps name theirs), with
    the id of the flush that waits (a coalescer's device stage)."""

    def wrapped(*args):
        lock = server.driver.lock
        if lock_span:
            with server.rpc.trace.span(lock_span, flush=current_flush()):
                lock.acquire()
        else:
            lock.acquire()
        try:
            before = server.driver.update_count
            result = fn(*args)
            if server.driver.update_count == before:
                n = count(result)
                if n:
                    server.driver.event_model_updated(n)
        finally:
            lock.release()
        return result

    return wrapped


# -- per-engine binders -------------------------------------------------------


def _quality_observe_pairs(server: Any, pairs) -> None:
    """Prequential (test-then-train) hook for the generic train path
    (ISSUE 17): on sampled batches, score a bounded prefix with the
    CURRENT model before the update is submitted, and record the label
    distribution. Reads are snapshot reads (no driver lock), failures
    never reach the ingest path. A sampled call's hook is span
    ``quality.observe``: its scoring's ``step.classify.*`` lie inside."""
    q = getattr(server, "quality", None)
    if q is None or not pairs or not q.admit("train"):
        return
    d = server.driver
    sub = pairs[:q.max_score_rows]
    try:
        with server.rpc.trace.span("quality.observe"):
            q.record_labels(p[0] for p in pairs)
            data = [p[1] for p in sub]
            if isinstance(sub[0][0], str) and hasattr(d, "classify"):
                for (truth, _dat), ranked in zip(sub, d.classify(data)):
                    q.record_classified(truth, ranked)
            elif hasattr(d, "estimate"):
                for (truth, _dat), est in zip(sub, d.estimate(data)):
                    q.record_estimated(float(truth), float(est))
    except Exception:  # broad-ok — quality scoring must not break ingest
        log.debug("prequential hook failed", exc_info=True)


def _quality_observe_raw(server: Any, item, numeric: bool) -> None:
    """Prequential + feature-stat hook for the native raw-ingest path:
    names never materialize here, so values record under the ``hashed``
    group; scoring rides classify_hashed/estimate_hashed on a bounded
    row prefix. A sampled call's hook is span ``quality.observe``."""
    q = getattr(server, "quality", None)
    if q is None or not q.admit("train"):
        return
    d = server.driver
    labels, idx, val = item
    try:
        with server.rpc.trace.span("quality.observe"):
            q.record_hashed(val)
            k = min(q.max_score_rows, idx.shape[0])
            if numeric:
                if hasattr(d, "estimate_hashed"):
                    for t, e in zip(labels[:k],
                                    d.estimate_hashed(idx[:k], val[:k])):
                        q.record_estimated(float(t), float(e))
            else:
                uniq, lidx = labels
                q.record_labels(uniq[int(j)] for j in lidx)
                if hasattr(d, "classify_hashed"):
                    ranked = d.classify_hashed(idx[:k], val[:k])
                    for j, r in enumerate(ranked):
                        q.record_classified(uniq[int(lidx[j])], r)
    except Exception:  # broad-ok — quality scoring must not break ingest
        log.debug("raw prequential hook failed", exc_info=True)


def _usage_batch_hook(server: Any, method: str):
    """Microbatch billing hook (ISSUE 19): the coalescer calls this once
    per ticket per flush with the submitting tenant, its row weight, its
    queue residency and its amortized share of the flush's device time —
    the ledger rolls them into the ``usage.<principal>.*`` gauges. None
    (no hook, zero overhead) when the ledger is disabled."""
    u = getattr(server, "usage", None)
    if u is None:
        return None

    def hook(principal, rows, queue_s, device_s):
        u.record_batch(principal, method, rows, queue_s, device_s)

    return hook


def _register_train(rpc: RpcServer, server: Any, decode_pair,
                    train_fn) -> None:
    """Register "train" with microbatch coalescing (server/microbatch.py):
    concurrent train RPCs merge into one driver/device batch — SURVEY.md
    §7 step 4's ingest queue. ``--microbatch-max 0`` restores the direct
    per-RPC path. Either way each caller's reply is its own item count
    (the reference's per-call return, classifier_impl.cpp:56-59).

    Drivers exposing the featurize/apply split (``featurize_train`` +
    ``train_hashed``) ride the two-stage PipelinedCoalescer: batch N+1
    featurizes on the flusher's host thread (span ``fv.convert``) while
    the device consumes batch N (span ``microbatch.train.device_stage``)
    — the feature pipeline's host/device overlap."""
    max_batch = getattr(server.args, "microbatch_max", 8192)
    flush = _updating(server, train_fn, count=lambda r: r,
                      lock_span="step.train.lock_wait")
    if not max_batch:
        def train_direct(name, data):
            pairs = [decode_pair(p) for p in data]
            _quality_observe_pairs(server, pairs)
            return flush(pairs)

        rpc.register("train", train_direct, arity=2)
        return
    driver = server.driver
    featurize = getattr(driver, "featurize_train", None)
    apply_fn = getattr(driver, "train_hashed", None)
    if featurize is not None and apply_fn is not None:
        from jubatus_tpu.server.microbatch import PipelinedCoalescer

        device_step = _updating(
            server, lambda prepared: apply_fn(*prepared),
            count=lambda r: r, lock_span="step.train.lock_wait")
        co = PipelinedCoalescer(featurize, device_step,
                                max_batch=max_batch, trace=rpc.trace,
                                name="train")
    else:
        from jubatus_tpu.server.microbatch import Coalescer

        co = Coalescer(flush, max_batch=max_batch, trace=rpc.trace,
                       name="train")
    server.coalescers["train"] = co
    co.usage_hook = _usage_batch_hook(server, "train")

    # -t 0 conventionally means "no timeout" — map to an unbounded wait
    wait_s = server.args.timeout * 6 if server.args.timeout > 0 else None
    combines = bool(driver.converter.config.combination_rules)

    def train(name, data):
        pairs = [decode_pair(p) for p in data]
        if not pairs:
            return 0
        if combines:
            # the cross product in the Python converter: what the native
            # parser declined, or a server without it
            rpc.trace.count("fv.combine.generic")
        # test-then-train: prequential scoring sees the pre-update model
        _quality_observe_pairs(server, pairs)
        co.submit(pairs, timeout=wait_s)
        return len(pairs)

    rpc.register("train", train, arity=2)


def _register_train_raw(rpc: RpcServer, server: Any, numeric: bool) -> None:
    """Native ingest fast path for ``train`` (native/fast_ingest.cpp): the
    request's raw msgpack params parse in C++ straight to pre-hashed [B, K]
    arrays — no Datum objects, no Python convert loop. Registered only
    when the transport exposes raw spans, the driver has ``train_hashed``,
    and the converter config is expressible in the native parser
    (jubatus_tpu/native/ingest.py gates); any request the parser declines
    (unexpected wire shape, unrepresentable values) falls back to the
    generic decode + converter path, so behavior is identical either way."""
    import json as _json

    driver = server.driver
    if not hasattr(rpc, "register_raw") or not hasattr(driver, "train_hashed"):
        return
    try:
        from jubatus_tpu.native.ingest import IngestParser

        conv = _json.loads(server.config_json).get("converter")
        parser = IngestParser.from_converter_config(
            conv, driver.converter.hasher.dim_bits)
    except Exception:  # broad-ok — fast path is strictly optional
        return
    if parser is None:
        return
    from jubatus_tpu.rpc.server import RAW_FALLBACK

    def _pad_concat(pairs):
        """Merge per-request (idx, val) pairs into one batch: pad widths
        to the max (each already on a rung of the parser's width ladder,
        or a power of two where its rows are uneven, and the widest of
        rungs is a rung and of powers of two a power of two, so the flush
        lands on a compiled width; pads are rare and small where rows are
        alike) and concatenate at numpy speed. ONE owner for both the
        train and query flush paths."""
        kmax = max(i.shape[1] for i, _ in pairs)
        parts_i, parts_v = [], []
        for ir, vr in pairs:
            if ir.shape[1] != kmax:
                pad = kmax - ir.shape[1]
                ir = np.pad(ir, ((0, 0), (0, pad)))
                vr = np.pad(vr, ((0, 0), (0, pad)))
            parts_i.append(ir)
            parts_v.append(vr)
        return (np.concatenate(parts_i) if len(parts_i) > 1 else parts_i[0],
                np.concatenate(parts_v) if len(parts_v) > 1 else parts_v[0])

    # what get_status shows as "ingest.*" (server/base.py): the train
    # and the query flushes this path prepared
    stats = server.ingest_stats = {"sparse_flushes": 0,
                                   "sparse_query_flushes": 0}

    # deferred-idf (pure-idf specs): parses run lock-free against zero df
    # tables; ONE observe+scale per coalesced flush (the idf
    # batch-collapse fix — see native/ingest.py deferred_idf_scale)
    deferred = parser.deferred_idf
    weights = driver.converter.weights \
        if (parser.needs_weights or deferred) else None

    trace = rpc.trace

    def _crossed(cross, rows: int) -> None:
        """Account for one combination request's cross product, which the
        parser made on its own thread (span ``fv.combine`` is its own
        clock around it)."""
        trace.record("fv.combine", cross.seconds)
        trace.count("fv.combine.rows", rows)
        trace.count("fv.combine.slots", cross.slots)
        trace.count("fv.combine.native")

    def _counted(counts) -> None:
        """What the parser counted of one request as it went: the tokens
        its string rules cut (an ngram rule's windows), the code points
        its ngram rules slid over, and whether its rows were uneven
        enough to be packed at a power of two (core/sparse.py
        _request_width)."""
        trace.count("fv.tokens", counts.tokens)
        if counts.codepoints:
            trace.count("fv.codepoints", counts.codepoints)
        if counts.pow2:
            trace.count("fv.pack.pow2")

    def _merge_labels(label_pairs):
        """Union per-request (uniq_labels, label_idx) pairs into one
        distinct-label list + remapped int32 row index — no per-example
        Python loop (the C++ dedup did the heavy lifting)."""
        label_map: dict = {}
        parts_l = []
        for uniq, lidx in label_pairs:
            lut = np.empty(len(uniq), np.int32)
            for j, u in enumerate(uniq):
                lut[j] = label_map.setdefault(u, len(label_map))
            parts_l.append(lut[lidx])
        lidx = np.concatenate(parts_l) if len(parts_l) > 1 else parts_l[0]
        return list(label_map), lidx

    def prep_requests(reqs):
        """Stage 1 (host) of the pipelined flush: merge the requests'
        (labels, idx, val) into ONE device-ready batch of the same shape
        — label-map union, width pad+concat, deferred-idf observe+scale.
        Runs on the flusher thread while the device consumes the
        previous batch."""
        if not reqs:
            return None
        idx, val = _pad_concat([(ir, vr) for _lb, ir, vr in reqs])
        if numeric:
            labels = np.concatenate([r[0] for r in reqs]) \
                if len(reqs) > 1 else reqs[0][0]
        else:
            labels = _merge_labels([r[0] for r in reqs])
            stats["sparse_flushes"] += 1
        if deferred:
            from jubatus_tpu.native.ingest import deferred_idf_scale

            val = deferred_idf_scale(idx, val, weights, observe=True)
        return labels, idx, val

    def apply_prepared(prepared):
        """Stage 2 (device): hand the prepared batch to the driver, which
        settles the step's plan from the arrays."""
        if prepared is None:
            return 0
        labels, idx, val = prepared
        if numeric:
            return driver.train_hashed(labels, idx, val)
        return driver.train_indexed(*labels, idx, val)

    max_batch = getattr(server.args, "microbatch_max", 8192)
    wait_s = server.args.timeout * 6 if server.args.timeout > 0 else None
    device_step = _updating(server, apply_prepared, count=lambda r: r,
                            lock_span="step.train.lock_wait")
    if max_batch:
        from jubatus_tpu.server.microbatch import (Coalescer,
                                                   PipelinedCoalescer)

        co = PipelinedCoalescer(
            prep_requests, device_step, max_batch=max_batch,
            weigher=lambda item: item[1].shape[0], trace=rpc.trace,
            name="train_raw")
        server.coalescers["train_raw"] = co
        co.usage_hook = _usage_batch_hook(server, "train")

    def train_raw(raw_params: bytes):
        cross = None
        with trace.span("fv.convert"):
            if parser.combines:
                parsed = parser.parse_indexed(raw_params, cross=True,
                                              counts=True)
                if parsed is not None:
                    cross, parsed = parsed[3], parsed[:3] + parsed[4:]
            elif weights is not None and not deferred:
                with weights.lock:
                    parsed = parser.parse_indexed(raw_params,
                                                  weights=weights,
                                                  counts=True)
            else:
                # deferred idf / unweighted: lock-free parallel parse
                parsed = parser.parse_indexed(raw_params, counts=True)
        if parsed is None:
            return RAW_FALLBACK
        labels, idx, val, counts = parsed
        if numeric != isinstance(labels, np.ndarray):
            return RAW_FALLBACK  # label kind mismatch: let the
            # generic path produce the proper type error
        n = idx.shape[0]
        if n == 0:
            return 0
        if cross is not None:
            _crossed(cross, n)
        _counted(counts)
        item = (labels, idx, val)
        # test-then-train: prequential scoring sees the pre-update model
        _quality_observe_raw(server, item, numeric)
        if max_batch:
            co.submit([item], timeout=wait_s)
        else:
            device_step(prep_requests([item]))
        return n

    rpc.register_raw("train", train_raw)

    # the query path rides the same parser: [name, [datum, ...]] -> hashed
    # batch -> snapshot-read scores, no Datum objects
    def _parse_datums(raw_params: bytes):
        """(idx, val, counts), or None."""
        if deferred:
            # lock-free parse, then one vectorized idf gather (queries
            # read idf, never observe)
            parsed = parser.parse_datums(raw_params, counts=True)
            if parsed is None:
                return None
            from jubatus_tpu.native.ingest import deferred_idf_scale

            idx, val, counts = parsed
            return idx, deferred_idf_scale(idx, val, weights,
                                           observe=False), counts
        if weights is not None:
            with weights.lock:  # queries read idf, never observe
                return parser.parse_datums(raw_params, weights=weights,
                                           counts=True)
        return parser.parse_datums(raw_params, counts=True)

    def _query_coalescer(name: str, score_batch):
        """Query-plane microbatching (the mirror of the train coalescer):
        concurrent read requests join ONE device dispatch against the
        same model snapshot — every kernel launch costs ~ms on an
        accelerator regardless of batch size, so per-request dispatch
        caps the query plane at launches/s, not samples/s.
        ``score_batch(idx, val) -> per-row results``; each request gets
        exactly its rows back (Coalescer split_results)."""
        def query_flush(items):
            stats["sparse_query_flushes"] += 1
            if len(items) == 1:
                i, v = items[0]
                return [score_batch(i, v)]
            rows = score_batch(*_pad_concat(items))
            out, off = [], 0
            for i, _ in items:
                out.append(rows[off:off + i.shape[0]])
                off += i.shape[0]
            return out

        qco = Coalescer(query_flush, max_batch=max_batch,
                        weigher=lambda it: it[0].shape[0],
                        split_results=True, trace=trace, name=name)
        server.coalescers[name] = qco
        # bill under the wire method ("classify"), not the coalescer key
        qco.usage_hook = _usage_batch_hook(
            server, name[:-4] if name.endswith("_raw") else name)

        def scored(idx, val):
            (mine,) = qco.submit([(idx, val)], timeout=wait_s)
            return mine

        return scored

    def _raw_query(scored):
        """The raw handler of a read method: parse, then ``scored(idx,
        val)`` (a query coalescer, or the driver where coalescing is
        off)."""
        def raw_handler(raw_params: bytes):
            cross = None
            with trace.span("fv.convert"):
                if parser.combines:
                    parsed = parser.parse_datums(raw_params, cross=True,
                                                 counts=True)
                    if parsed is not None:
                        cross, parsed = parsed[2], parsed[:2] + parsed[3:]
                else:
                    parsed = _parse_datums(raw_params)
            if parsed is None:
                return RAW_FALLBACK
            idx, val, counts = parsed
            if idx.shape[0] == 0:
                return []
            if cross is not None:
                _crossed(cross, idx.shape[0])
            _counted(counts)
            return scored(idx, val)

        return raw_handler

    if numeric and hasattr(driver, "estimate_hashed"):
        rpc.register_raw("estimate", _raw_query(
            _query_coalescer("estimate_raw", driver.estimate_hashed)
            if max_batch else driver.estimate_hashed))
    elif not numeric and hasattr(driver, "classify_hashed"):
        rpc.register_raw("classify", _raw_query(
            _query_coalescer("classify_raw", driver.classify_hashed)
            if max_batch else driver.classify_hashed))


@_binder("classifier")
def _bind_classifier(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    _register_train(rpc, server,
                    lambda p: (p[0], _datum(p[1])), d.train)
    _register_train_raw(rpc, server, numeric=False)
    rpc.register("classify",  # no-usage — uncoalesced path: dispatch-span billing covers it
                 lambda name, data: [_scored(r)
                                     for r in d.classify(_datums(data))],
                 arity=2)
    rpc.register("get_labels", lambda name: {k: int(v) for k, v in d.get_labels().items()}, arity=1)
    rpc.register("set_label", _updating(server, lambda name, lbl: d.set_label(lbl)), arity=2)
    rpc.register("delete_label", _updating(server, lambda name, lbl: d.delete_label(lbl)), arity=2)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("regression")
def _bind_regression(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    _register_train(rpc, server,
                    lambda p: (float(p[0]), _datum(p[1])), d.train)
    _register_train_raw(rpc, server, numeric=True)
    rpc.register(
        "estimate",
        lambda name, data: [float(x) for x in d.estimate(_datums(data))],
        arity=2,
    )
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("recommender")
def _bind_recommender(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register("clear_row", _updating(server, lambda name, rid: d.clear_row(rid)), arity=2)
    rpc.register(
        "update_row",
        _updating(server, lambda name, rid, row: d.update_row(rid, _datum(row))),
        arity=3,
    )
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)
    rpc.register("complete_row_from_id", lambda name, rid: _wire_datum(d.complete_row_from_id(rid)), arity=2)
    rpc.register("complete_row_from_datum",
                 lambda name, row: _wire_datum(d.complete_row_from_datum(_datum(row))), arity=2)
    rpc.register("similar_row_from_id",
                 lambda name, rid, size: _scored(d.similar_row_from_id(rid, int(size))), arity=3)
    rpc.register("similar_row_from_datum",
                 lambda name, row, size: _scored(d.similar_row_from_datum(_datum(row), int(size))), arity=3)
    rpc.register("decode_row", lambda name, rid: _wire_datum(d.decode_row(rid)), arity=2)
    rpc.register("get_all_rows", lambda name: d.get_all_rows(), arity=1)
    rpc.register("calc_similarity", lambda name, lhs, rhs: float(d.calc_similarity(_datum(lhs), _datum(rhs))),
                 arity=3)
    rpc.register("calc_l2norm", lambda name, row: float(d.calc_l2norm(_datum(row))), arity=2)


@_binder("nearest_neighbor")
def _bind_nearest_neighbor(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)
    rpc.register("set_row", _updating(server, lambda name, rid, dat: d.set_row(rid, _datum(dat))), arity=3)
    rpc.register("neighbor_row_from_id",
                 lambda name, rid, size: _scored(d.neighbor_row_from_id(rid, int(size))), arity=3)
    rpc.register("neighbor_row_from_datum",
                 lambda name, q, size: _scored(d.neighbor_row_from_datum(_datum(q), int(size))), arity=3)
    rpc.register("similar_row_from_id", lambda name, rid, n: _scored(d.similar_row_from_id(rid, int(n))),
                 arity=3)
    rpc.register("similar_row_from_datum",
                 lambda name, q, n: _scored(d.similar_row_from_datum(_datum(q), int(n))), arity=3)
    rpc.register("get_all_rows", lambda name: d.get_all_rows(), arity=1)


def _replicated_write(server: Any, key: str, apply_local, apply_remote,
                      replication: int = 2):
    """Server-side CHT-replicated write (≙ anomaly_serv.cpp:178-211,
    graph_serv.cpp:181-228): place ``key`` on its ``replication`` ring
    successors — apply locally when a successor is me, RPC the peer
    otherwise. The primary write must succeed (exceptions propagate);
    replicas are best-effort (warn + continue). Returns the primary's
    result. Falls back to a local-only apply when the ring is empty."""
    cht = server.cluster_cht()
    nodes = cht.find(key, replication) if cht is not None else []
    if not nodes:
        return apply_local()
    me = server.self_nodeinfo()
    result = None
    for i, node in enumerate(nodes):
        try:
            if node.name == me.name:
                out = apply_local()
            else:
                out = apply_remote(server.peer_client(node))
            if i == 0:
                result = out
        except Exception:  # broad-ok — replica writes are best-effort
            if i == 0:
                raise  # primary failure is the caller's failure
            server.drop_peer_client(node)
            log.warning("replica write to %s failed (best-effort)",
                        node.name, exc_info=True)
    return result


@_binder("anomaly")
def _bind_anomaly(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register("clear_row", _updating(server, lambda name, rid: d.clear_row(rid)), arity=2)

    def add(name, row):
        """Distributed add = mint id + CHT(2) placement + primary write +
        best-effort replica, INSIDE the server — a direct-to-server add is
        replicated immediately, not at the next mix (anomaly_serv.cpp:
        155-211). Standalone keeps the driver's local add."""
        if server.coord is None:
            return list(_updating(server, lambda: d.add(_datum(row)))())
        row_id = str(d.idgen.generate()) if getattr(d, "idgen", None) \
            else None
        if row_id is None:
            return list(_updating(server, lambda: d.add(_datum(row)))())
        score = _replicated_write(
            server, row_id,
            apply_local=_updating(
                server, lambda: float(d.overwrite(row_id, _datum(row)))),
            apply_remote=lambda cli: float(
                cli.call("overwrite", name, row_id, row)),
        )
        return [row_id, float(score)]

    rpc.register("add", add, arity=2)
    rpc.register("update", _updating(server, lambda name, rid, row: float(d.update(rid, _datum(row)))),
                 arity=3)
    rpc.register("overwrite", _updating(server, lambda name, rid, row: float(d.overwrite(rid, _datum(row)))),
                 arity=3)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)
    rpc.register("calc_score", lambda name, row: float(d.calc_score(_datum(row))), arity=2)
    rpc.register("get_all_rows", lambda name: d.get_all_rows(), arity=1)


@_binder("graph")
def _bind_graph(rpc: RpcServer, server: Any) -> None:
    d = server.driver

    def edge_parts(e):
        """wire edge [property_map, source, target] → driver arg order
        (source, target, properties)."""
        return e[1], e[2], dict(e[0])

    def create_node(name):
        """Distributed create_node = mint global id + create_node_here on
        the CHT(2) successors via direct peer RPC (graph_serv.cpp:181-228)
        — a direct-to-server create is visible on its replica before any
        mix. Standalone keeps the local driver path."""
        if server.coord is None:
            return _updating(server, lambda: d.create_node())()
        node_id = str(d.idgen.generate()) if getattr(d, "idgen", None) \
            else None
        if node_id is None:
            return _updating(server, lambda: d.create_node())()
        _replicated_write(
            server, node_id,
            apply_local=_updating(
                server, lambda: d.create_node_here(node_id)),
            apply_remote=lambda cli: cli.call(
                "create_node_here", name, node_id),
        )
        return node_id

    rpc.register("create_node", create_node, arity=1)
    rpc.register("remove_node", _updating(server, lambda name, nid: d.remove_node(nid)), arity=2)
    rpc.register("update_node", _updating(server, lambda name, nid, prop: d.update_node(nid, dict(prop))),
                 arity=3)
    rpc.register(
        "create_edge",
        _updating(server, lambda name, nid, e: d.create_edge(nid, *edge_parts(e))),
        arity=3,
    )
    rpc.register(
        "update_edge",
        _updating(server, lambda name, nid, eid, e: d.update_edge(nid, int(eid), *edge_parts(e))),
        arity=4,
    )
    rpc.register("remove_edge", _updating(server, lambda name, nid, eid: d.remove_edge(nid, int(eid))),
                 arity=3)
    rpc.register("get_centrality", lambda name, nid, ct, q: float(d.get_centrality(nid, int(ct), q)), arity=4)
    rpc.register("add_centrality_query", _updating(server, lambda name, q: d.add_centrality_query(q)),
                 arity=2)
    rpc.register("add_shortest_path_query", _updating(server, lambda name, q: d.add_shortest_path_query(q)),
                 arity=2)
    rpc.register("remove_centrality_query", _updating(server, lambda name, q: d.remove_centrality_query(q)),
                 arity=2)
    rpc.register("remove_shortest_path_query", _updating(server,
                 lambda name, q: d.remove_shortest_path_query(q)), arity=2)
    rpc.register(
        "get_shortest_path",
        lambda name, q: d.get_shortest_path(q[0], q[1], int(q[2]), q[3] if len(q) > 3 else None),
        arity=2,
    )
    rpc.register("update_index", _updating(server, lambda name: d.update_index()), arity=1)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)
    rpc.register(
        "get_node",
        lambda name, nid: (lambda n: [n["property"], n["in_edges"], n["out_edges"]])(d.get_node(nid)),
        arity=2,
    )
    rpc.register(
        "get_edge",
        lambda name, nid, eid: (lambda e: [e["property"], e["source"],
                                           e["target"]])(d.get_edge(nid, int(eid))),
        arity=3,
    )
    rpc.register("create_node_here", _updating(server, lambda name, nid: d.create_node_here(nid)), arity=2)
    rpc.register("remove_global_node", _updating(server, lambda name, nid: d.remove_global_node(nid)),
                 arity=2)
    rpc.register(
        "create_edge_here",
        _updating(server, lambda name, eid, e: d.create_edge_here(int(eid), *edge_parts(e))),
        arity=3,
    )


@_binder("burst")
def _bind_burst(rpc: RpcServer, server: Any) -> None:
    d = server.driver

    def wire_window(w):
        """driver window dict → wire [start_pos, [[all, rel, weight]...]]."""
        return [w["start_pos"],
                [[b["all_data_count"], b["relevant_data_count"],
                  b["burst_weight"]] for b in w["batches"]]]

    rpc.register(
        "add_documents",
        lambda name, docs: _updating(
            server,
            lambda: d.add_documents([(float(p), t) for p, t in docs]),
            count=lambda r: r,
        )(),
        arity=2,
    )
    rpc.register("get_result", lambda name, kw: wire_window(d.get_result(kw)), arity=2)
    rpc.register("get_result_at", lambda name, kw, pos: wire_window(d.get_result_at(kw, float(pos))), arity=3)
    rpc.register(
        "get_all_bursted_results",
        lambda name: {k: wire_window(w) for k, w in d.get_all_bursted_results().items()},
        arity=1,
    )
    rpc.register(
        "get_all_bursted_results_at",
        lambda name, pos: {k: wire_window(w) for k, w in d.get_all_bursted_results_at(float(pos)).items()},
        arity=2,
    )
    rpc.register(
        "get_all_keywords",
        lambda name: [[k["keyword"], k["scaling_param"], k["gamma"]] for k in d.get_all_keywords()],
        arity=1,
    )
    rpc.register(
        "add_keyword",
        _updating(server, lambda name, kw: d.add_keyword(kw[0], float(kw[1]), float(kw[2]))),
        arity=2,
    )
    rpc.register("remove_keyword", _updating(server, lambda name, kw: d.remove_keyword(kw)), arity=2)
    rpc.register("remove_all_keywords", _updating(server, lambda name: d.remove_all_keywords()), arity=1)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("clustering")
def _bind_clustering(rpc: RpcServer, server: Any) -> None:
    d = server.driver

    def wd(pair):  # (weight, Datum) → wire weighted_datum
        return [float(pair[0]), _wire_datum(pair[1])]

    def wi(pair):  # (weight, id) → wire weighted_index
        return [float(pair[0]), pair[1]]

    rpc.register(
        "push",
        _updating(server, lambda name, points: d.push([(p[0], _datum(p[1])) for p in points])),
        arity=2,
    )
    rpc.register("get_revision", lambda name: int(d.get_revision()), arity=1)
    rpc.register("get_core_members", lambda name: [[wd(p) for p in c] for c in d.get_core_members()], arity=1)
    rpc.register("get_core_members_light",
                 lambda name: [[wi(p) for p in c] for c in d.get_core_members_light()], arity=1)
    rpc.register("get_k_center", lambda name: [_wire_datum(c) for c in d.get_k_center()], arity=1)
    rpc.register("get_nearest_center", lambda name, p: _wire_datum(d.get_nearest_center(_datum(p))), arity=2)
    rpc.register("get_nearest_members", lambda name, p: [wd(x) for x in d.get_nearest_members(_datum(p))],
                 arity=2)
    rpc.register("get_nearest_members_light",
                 lambda name, p: [wi(x) for x in d.get_nearest_members_light(_datum(p))], arity=2)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("stat")
def _bind_stat(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register("push", _updating(server, lambda name, key, val: d.push(key, float(val))), arity=3)
    rpc.register("sum", lambda name, key: float(d.sum(key)), arity=2)
    rpc.register("stddev", lambda name, key: float(d.stddev(key)), arity=2)
    rpc.register("max", lambda name, key: float(d.max(key)), arity=2)
    rpc.register("min", lambda name, key: float(d.min(key)), arity=2)
    rpc.register("entropy", lambda name, key: float(d.entropy(key)), arity=2)
    rpc.register("moment", lambda name, key, deg, center: float(d.moment(key, int(deg), float(center))),
                 arity=4)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("bandit")
def _bind_bandit(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register("register_arm", _updating(server, lambda name, a: d.register_arm(a)), arity=2)
    rpc.register("delete_arm", _updating(server, lambda name, a: d.delete_arm(a)), arity=2)
    rpc.register("select_arm", _updating(server, lambda name, p: d.select_arm(p)), arity=2)
    rpc.register("register_reward", _updating(server,
                 lambda name, p, a, r: d.register_reward(p, a, float(r))), arity=4)
    rpc.register(
        "get_arm_info",
        lambda name, p: {
            arm: [int(info["trial_count"]), float(info["weight"])]
            for arm, info in d.get_arm_info(p).items()
        },
        arity=2,
    )
    rpc.register("reset", _updating(server, lambda name, p: d.reset(p)), arity=2)
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)


@_binder("weight")
def _bind_weight(rpc: RpcServer, server: Any) -> None:
    d = server.driver
    rpc.register(
        "update",
        lambda name, dat: [[k, float(v)] for k, v in _updating(server, lambda: d.update(_datum(dat)))()],
        arity=2,
    )
    rpc.register(
        "calc_weight",
        lambda name, dat: [[k, float(v)] for k, v in d.calc_weight(_datum(dat))],
        arity=2,
    )
    rpc.register("clear", _updating(server, lambda name: (d.clear(), True)[1]), arity=1)
