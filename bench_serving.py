"""End-to-end serving-plane benchmark: RPC train samples/s through a real
EngineServer (VERDICT r1 item 2 — measure the product, not the kernel).

Path measured: client msgpack encode -> TCP loopback -> transport framing ->
native ingest parse (C++: datum decode + fv convert + feature hashing,
native/fast_ingest.cpp) -> microbatch coalesce -> jitted AROW update on the
bench device; the Python-converter path serves as the fallback A/B. This is
the reference's hot ingest path (classifier_serv.cpp:127-146) reshaped for
TPU (SURVEY.md §3.2).

Clients are separate PROCESSES (their encode work must not share the
server's GIL — in-process client threads understate the server by ~2x),
and they PRE-ENCODE their request frames once, then pump raw bytes: this
host gives the whole bench ONE CPU core (client processes, server, and the
C++ baseline all share it), and a Python client's msgpack encode costs
~20 us/sample — 16 Python clients alone cannot generate 200k samples/s of
traffic on that core. The reference's clients are C++ (encode ~ns-scale);
pre-encoding emulates C++-speed clients so the metric measures the SERVER
plane (framing, C++ ingest parse, coalescing, device step, response), which
does full per-request work either way. A warmup phase triggers every
bucket-shape compile before timing starts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

N_CLIENTS = 16
CALL_BATCH = 500
K = 32                  # numeric features per datum
WARMUP_SECONDS = 12.0
MEASURE_SECONDS = 20.0
TEXT_MEASURE_SECONDS = 12.0
#: base seed for every client worker's rng (ISSUE 12 satellite): each
#: client derives its stream from [SEED, client_idx], so a whole run's
#: traffic trace is reproducible across runs — the pid-seeded rngs the
#: clients used before made no two runs comparable. --seed overrides.
SEED = 1729

CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {"num_rules": [{"key": "*", "type": "num"}]},
}

#: text workload (VERDICT r2 item 6): space splitter + tf sample weight —
#: the reference's canonical text shape (≙ config/classifier/pa.json's
#: string_rules, tokenized) — native-expressible since round 2/3
TEXT_CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {"string_rules": [
        {"key": "*", "type": "space", "sample_weight": "tf",
         "global_weight": "bin"}]},
}

#: idf global weight: since round 3 the native parser takes the
#: WeightManager's dense df tables and replays observe+scale in C++
#: (fraction 1.0); before that this metric measured the Python-converter
#: fallback at ~6.5k samples/s
TEXT_IDF_CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {"string_rules": [
        {"key": "*", "type": "space", "sample_weight": "tf",
         "global_weight": "idf"}]},
}

#: combination rules (≙ config/classifier/arow_combinational_feature.json):
#: native-expressible since round 4 — the C++ parser runs the named cross
#: product (K numeric features -> K*(K-1)/2 extra pairs per datum)
COMBO_CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "num_rules": [{"key": "*", "type": "num"}],
        "combination_rules": [
            {"key_left": "*", "key_right": "*", "type": "mul"}],
    },
}

#: string filters ride the HYBRID fast path since round 5: the regex
#: itself runs in Python (std::regex vs `re` divergence risk — round-3
#: finding), memoized per distinct input, via a request rewrite; the
#: datum walk/tokenize/tf/hash stay in C++ (fraction 1.0; the mode is
#: recorded in e2e_text_filter_mode)
TEXT_FILTER_CONF = {
    "method": "AROW",
    "parameter": {"regularization_weight": 1.0},
    "converter": {
        "string_filter_types": {
            "strip_digits": {"method": "regexp", "pattern": "[0-9]+",
                             "replace": ""}},
        "string_filter_rules": [
            {"key": "*", "type": "strip_digits", "suffix": "-nodigit"}],
        "string_rules": [
            {"key": "*", "type": "space", "sample_weight": "tf",
             "global_weight": "bin"}]},
}

_CLIENT_PROG = r"""
import os, socket, sys, time
import numpy as np
import msgpack
port, call_batch, k, warmup, measure, workload = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
    float(sys.argv[4]), float(sys.argv[5]), sys.argv[6])
from jubatus_tpu.client import Datum
# replayable traffic (ISSUE 12): per-client stream derived from the
# run's base seed + this client's index ("pid" keeps the old behavior)
seed, idx = sys.argv[7], int(sys.argv[8])
rng = (np.random.default_rng(os.getpid()) if seed == "pid"
       else np.random.default_rng([int(seed), idx]))
VOCAB = [f"w{i:03d}" for i in range(400)]

def mk_datum():
    if workload.startswith("text"):
        words = rng.choice(len(VOCAB), size=k)
        return Datum({"body": " ".join(VOCAB[w] for w in words)})
    return Datum({f"f{j}": float(v)
                  for j, v in enumerate(rng.normal(size=k))})

frames = []
train_frames = []
for _ in range(8):
    batch = []
    for _ in range(call_batch):
        label = "a" if rng.random() < 0.5 else "b"
        batch.append([label, mk_datum().to_msgpack()])
    train_frames.append(msgpack.packb([0, 1, "train", ["bench", batch]],
                                      use_bin_type=True))
if workload == "classify":
    # query plane: read-mostly traffic against a model the warmup trains
    for _ in range(8):
        batch = [mk_datum().to_msgpack() for _ in range(call_batch)]
        frames.append(msgpack.packb([0, 1, "classify", ["bench", batch]],
                                    use_bin_type=True))
else:
    frames = train_frames
sock = socket.create_connection(("127.0.0.1", port), timeout=120.0)
sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
unp = msgpack.Unpacker()
PIPELINE = 4  # msgpack-rpc pipelining: keep the server core saturated

def read_reply():
    while True:
        try:
            msg = unp.unpack()
            if msg[2] is not None:  # msgpack-rpc error slot: a failing
                raise RuntimeError(msg[2])  # server must fail the bench
            return
        except msgpack.OutOfData:
            pass
        data = sock.recv(65536)
        if not data:
            raise ConnectionError("server closed")
        unp.feed(data)

in_flight = 0
def call(frame):
    global in_flight
    sock.sendall(frame)
    in_flight += 1
    if in_flight >= PIPELINE:
        read_reply()
        in_flight -= 1

if workload == "classify":
    # give the model labels/weights before querying it
    call(train_frames[0])
    while in_flight:
        read_reply(); in_flight -= 1
deadline_warm = time.perf_counter() + warmup
i = 0
while time.perf_counter() < deadline_warm:
    call(frames[i % len(frames)]); i += 1
count = 0
t0 = time.perf_counter()
deadline = t0 + measure
while time.perf_counter() < deadline:
    call(frames[i % len(frames)]); i += 1; count += call_batch
while in_flight:  # completed-work accounting: drain before the clock stops
    read_reply(); in_flight -= 1
elapsed = time.perf_counter() - t0
print(f"CLIENT {count} {elapsed:.4f}")
"""


def _latency_keys(trace_snapshot: dict, suffix: str) -> dict:
    """Steady-state per-RPC latency quantiles from the server's span
    histograms (utils/tracing.py), keyed for the BENCH json. mean_ms
    rides along because it is CONTINUOUS (total/count) where the
    quantiles are bucket-quantized (~19% steps) — the overhead A/Bs'
    <2% budgets are only resolvable against the mean."""
    out = {}
    for m in ("train", "classify"):
        for q in ("p50_ms", "p99_ms", "mean_ms"):
            k = f"trace.rpc.{m}.{q}"
            if k in trace_snapshot:
                out[f"e2e_rpc_{m}_{q}_{suffix}"] = trace_snapshot[k]
    return out


def _default_microbatch() -> int:
    """Flush-size cap by platform: on the chip big flushes amortize the
    dispatch (the kernel's sweet spot is 32k, docs/PERF_NOTES.md); on a
    CPU backend the device step runs ON the bench cores, so a big flush
    starves the loadgen (measured on one core: cap 32k = 113k samples/s
    vs cap 8k = 145k, same shape otherwise)."""
    import jax

    return 32768 if jax.default_backend() != "cpu" else 8192


def run(transport: str = "python", workload: str = "numeric",
        conf: dict = CONF, measure: float = MEASURE_SECONDS,
        tag: str = "", microbatch: int = 0, native_ingest: bool = True,
        forensics: bool = True, model_health=None,
        profile_hz=None, events_enabled=None, quality=None,
        usage=None, seed=None) -> dict:
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    prev_ing = os.environ.get("JUBATUS_TPU_NATIVE_INGEST")
    # native is the DEFAULT transport now; "0" forces the Python one
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = \
        "1" if transport == "native" else "0"
    # set BOTH ways (like NATIVE_RPC above): an inherited =0 from an
    # operator shell must not silently turn the native rows into
    # Python-ingest runs and flatten the A/B to ~1.0
    os.environ["JUBATUS_TPU_NATIVE_INGEST"] = "1" if native_ingest else "0"
    # model_health (ISSUE 7): None keeps the stock server (the other
    # benches' behavior); True arms the FULL observability load —
    # 1 s telemetry ticks driving time-series ring sampling + SLO
    # burn-rate evaluation against live SLOs; False strips the plane
    # entirely (no ring, no SLO engine, no sampler thread) — the
    # honest "off" side of the overhead A/B
    health_args: dict = {}
    if model_health is True:
        health_args = dict(
            telemetry_interval=1.0,
            slo=["latency:rpc.classify:p99:50", "error_rate:*:0.01"],
            slo_fast_window=5.0, slo_slow_window=30.0)
    elif model_health is False:
        health_args = dict(telemetry_interval=0.0, timeseries_capacity=0)
    # profile_hz (ISSUE 8): None keeps the stock server (the always-on
    # sampler at its default rate); a number pins the sampling rate for
    # the profiling-overhead A/B (0 = sampler thread fully off)
    if profile_hz is not None:
        health_args["profile_hz"] = float(profile_hz)
    # events_enabled (ISSUE 14): None keeps the stock server (journal at
    # its default depth + incident triggers armed); False strips the
    # event plane entirely (capacity 0 = emit() no-ops, auto-capture
    # off) — the honest "off" side of the event-plane overhead A/B
    if events_enabled is False:
        health_args["event_capacity"] = 0
        health_args["incident_window"] = 0.0
    # quality (ISSUE 17): None keeps the stock server (data-quality
    # plane at its default sampling); True arms it at the documented
    # production rate (5% of train/score rows feed the sketches);
    # False disarms it entirely (sample 0.0 = admit() never fires,
    # recorder calls are a single float compare) — the honest "off"
    # side of the quality-overhead A/B
    if quality is True:
        health_args["quality_sample"] = 0.05
    elif quality is False:
        health_args["quality_sample"] = 0.0
    # usage (ISSUE 19): None keeps the stock server (usage ledger armed
    # at its default top-64 table); True pins the documented default
    # explicitly; False disarms the attribution plane entirely (top 0 =
    # no ledger object, the span sink is never installed, recorder
    # hooks stay None) — the honest "off" side of the usage-overhead A/B
    if usage is True:
        health_args["usage_top"] = 64
    elif usage is False:
        health_args["usage_top"] = 0
    try:
        srv = EngineServer(
            "classifier", conf,
            args=ServerArgs(engine="classifier", thread=N_CLIENTS,
                            listen_addr="127.0.0.1",
                            microbatch_max=microbatch
                            or _default_microbatch(), **health_args))
        # forensics=False: histograms stay on (the p50/p99 keys below need
        # them) but the span store + slow log are disabled — the A/B for
        # ISSUE 4's <2% overhead budget
        if not forensics:
            srv.rpc.trace.set_forensics(False)
        port = srv.start(0)
    finally:
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
        if prev_ing is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_INGEST", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_INGEST"] = prev_ing

    repo = os.path.dirname(os.path.abspath(__file__))
    from bench_mix import scrub_child_env  # one owner for the env scrub

    env = scrub_child_env(os.environ)
    procs = []
    total, elapsed_max = 0, 0.0
    # "mixed": half the clients write (train), half read (classify),
    # concurrently against one server — the snapshot-read-under-write-load
    # story the reference settles with a process-wide rw lock
    # (server_helper.hpp:296-303); here reads coalesce against model
    # snapshots while writes flush (VERDICT r4 #6)
    wl_list = (["numeric" if i % 2 == 0 else "classify"
                for i in range(N_CLIENTS)]
               if workload == "mixed" else [workload] * N_CLIENTS)
    per_wl = {wl: 0 for wl in wl_list}
    stats = {}
    trace_snapshot: dict = {}
    # quantile hygiene: reset the server's span registry once the clients'
    # warmup window closes, so the histograms embedded in the BENCH json
    # cover steady state only (warmup includes every bucket-shape compile)
    reset_timer = threading.Timer(WARMUP_SECONDS + 1.0, srv.rpc.trace.reset)
    reset_timer.daemon = True
    reset_timer.start()
    # try/finally like run_proxy: a communicate() timeout or client crash
    # must not leak the server + up to N_CLIENTS load generators into the
    # next trial's measurement window (they'd share the single bench core)
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _CLIENT_PROG, str(port),
                 str(CALL_BATCH), str(K), str(WARMUP_SECONDS), str(measure),
                 wl, str(SEED if seed is None else seed), str(idx)],
                env=env, cwd=repo, stdout=subprocess.PIPE, text=True)
            for idx, wl in enumerate(wl_list)
        ]
        dead: list = []
        for idx, (p, wl) in enumerate(zip(procs, wl_list)):
            out, _ = p.communicate(timeout=WARMUP_SECONDS + measure + 240)
            reported = False
            for line in out.splitlines():
                if line.startswith("CLIENT "):
                    _, cnt, el = line.split()
                    total += int(cnt)
                    per_wl[wl] += int(cnt)
                    elapsed_max = max(elapsed_max, float(el))
                    reported = True
            # a client that died without a CLIENT line would otherwise
            # contribute a silent 0 and the run would report a
            # plausible-but-low number as if every client were counted
            if p.returncode != 0 or not reported:
                dead.append(f"client {idx} ({wl}): rc={p.returncode}, "
                            f"tail={out[-120:]!r}")
        for nm, co in srv.coalescers.items():
            stats[nm] = co.stats()
        # steady-state latency quantiles off the server's own registry
        # (reset at warmup end above) — the per-request tail the
        # throughput number hides
        trace_snapshot = srv.rpc.trace.trace_status()
    finally:
        reset_timer.cancel()
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        srv.stop()
    sps = total / elapsed_max if elapsed_max else 0.0
    if dead:
        err = "; ".join(dead)
        if workload == "mixed":
            return {"e2e_mixed_error": err}
        return {f"e2e_rpc_{workload}_error_{tag or transport}": err}
    if workload == "mixed":
        out = {
            "e2e_mixed_train_classify_samples_per_sec": round(sps, 1),
            "e2e_mixed_train_samples_per_sec": round(
                per_wl.get("numeric", 0) / elapsed_max, 1)
            if elapsed_max else 0.0,
            "e2e_mixed_classify_samples_per_sec": round(
                per_wl.get("classify", 0) / elapsed_max, 1)
            if elapsed_max else 0.0,
        }
        out.update(_latency_keys(trace_snapshot, "mixed"))
        return out
    fast_items = stats.get("train_raw", {}).get("item_count", 0)
    slow_items = stats.get("train", {}).get("item_count", 0)
    avg_batch = 0.0
    for s in stats.values():
        if s.get("item_count"):
            avg_batch = max(avg_batch, s.get("avg_batch", 0.0))
    suffix = tag or transport
    verb = "classify" if workload == "classify" else "train"
    out = {f"e2e_rpc_{verb}_samples_per_sec_{suffix}": round(sps, 1)}
    out.update(_latency_keys(trace_snapshot, suffix))
    ing = getattr(srv, "ingest_stats", None) or {}
    if verb == "train":  # coalescer stats are train-plane only
        out[f"e2e_avg_device_batch_{suffix}"] = round(avg_batch, 1)
        out[f"e2e_fast_path_fraction_{suffix}"] = round(
            fast_items / max(fast_items + slow_items, 1), 3)
        # host/device overlap (ISSUE 5): fraction of stage-1 featurize
        # time hidden under an active device flush, from whichever train
        # coalescer carried the traffic (PipelinedCoalescer stats)
        ov = max((s.get("overlap_fraction", 0.0)
                  for s in stats.values() if s.get("prep_seconds", 0.0) > 0),
                 default=None)
        if ov is not None:
            out[f"e2e_fv_overlap_fraction_{suffix}"] = round(ov, 4)
        nf = (ing.get("schema_flushes", 0) + ing.get("sparse_flushes", 0)
              + ing.get("combo_flushes", 0))
        if nf:  # dense-submatrix plan engagement (uniform key schema)
            out[f"e2e_schema_flush_fraction_{suffix}"] = round(
                ing.get("schema_flushes", 0) / nf, 3)
            # device-side combo expansion engagement (base-width wire)
            if ing.get("combo_flushes", 0):
                out[f"e2e_combo_flush_fraction_{suffix}"] = round(
                    ing.get("combo_flushes", 0) / nf, 3)
    else:
        # the query-plane claim is LAUNCH collapse (VERDICT r4 weak #3):
        # dispatches/s and avg coalesced batch are the numbers of record
        qs = stats.get("classify_raw", {}) or stats.get("estimate_raw", {})
        if qs.get("flush_count") and elapsed_max:
            # flush_count covers warmup+measure; scale by the measured
            # fraction of traffic for an honest per-second figure
            frac = total / max(qs.get("item_count", total), 1)
            out[f"e2e_{verb}_dispatches_per_sec_{suffix}"] = round(
                qs["flush_count"] * frac / elapsed_max, 1)
            out[f"e2e_{verb}_avg_coalesced_batch_{suffix}"] = round(
                qs.get("avg_batch", 0.0), 1)
        nq = (ing.get("schema_query_flushes", 0)
              + ing.get("sparse_query_flushes", 0))
        if nq:
            out[f"e2e_schema_query_flush_fraction_{suffix}"] = round(
                ing.get("schema_query_flushes", 0) / nq, 3)
    return out


def run_fv_convert(seconds: float = 2.0) -> dict:
    """Pure host-featurization throughput for the two shapes ISSUE 5
    targets (no server, no device): ``convert_batch`` over 2048-datum
    batches, K=32 features/datum — the featurize-plane numbers the e2e
    keys decompose against. tools/bench_fv_sweep.py is the full
    batch-size x config sweep; this embeds the two keys of record."""
    import numpy as np

    from jubatus_tpu.core import Datum
    from jubatus_tpu.core.fv import make_fv_converter

    rng = np.random.default_rng(0)
    vocab = [f"w{i:03d}" for i in range(400)]
    out = {}
    for tag, conf in (("combo", COMBO_CONF), ("text_idf", TEXT_IDF_CONF)):
        if tag == "combo":
            data = [Datum({f"f{j}": float(v)
                           for j, v in enumerate(rng.normal(size=K))})
                    for _ in range(2048)]
        else:
            data = [Datum({"body": " ".join(
                vocab[w] for w in rng.choice(len(vocab), size=K))})
                for _ in range(2048)]
        conv = make_fv_converter(conf["converter"], dim_bits=18)
        conv.convert_batch(data[:64], update_weights=True)  # warm plans
        n = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            conv.convert_batch(data, update_weights=True)
            n += 1
            now = time.perf_counter()
            if now >= deadline:
                break
        out[f"e2e_fv_convert_samples_per_sec_{tag}"] = round(
            n * len(data) / (now - t0), 1)
    return out


def run_tracing_overhead(transport: str = "python",
                         measure: float = TEXT_MEASURE_SECONDS) -> dict:
    """ISSUE 4 satellite: the forensics layer ships with its cost
    measured. Adjacent A/B on the classify (query) plane — span store +
    slow log ENABLED vs DISABLED (histograms on both sides, so the
    steady-state p50/p99 keys come from the same machinery) — and the
    p50 ratio of record, budgeted at <2% regression. One bench core
    swings ~±10% run to run, so the ok-flag uses the MEDIAN-free single
    adjacent pair plus slack only in the honest direction: a ratio a
    hair over 1.02 on a noisy host is reported as-is."""
    out: dict = {}
    sides = {}
    for tag, forensics in (("forensics_on", True), ("forensics_off", False)):
        try:
            r = run(transport, workload="classify", measure=measure,
                    tag=tag, forensics=forensics)
        except Exception as e:  # noqa: BLE001 — partial results beat none
            out[f"e2e_{tag}_error"] = repr(e)[:200]
            continue
        out.update(r)
        sides[tag] = r
    p50_on = sides.get("forensics_on", {}).get(
        "e2e_rpc_classify_p50_ms_forensics_on")
    p50_off = sides.get("forensics_off", {}).get(
        "e2e_rpc_classify_p50_ms_forensics_off")
    if p50_on and p50_off:
        ratio = p50_on / p50_off
        out["e2e_tracing_overhead_p50_ratio"] = round(ratio, 4)
        out["e2e_tracing_overhead_ok"] = bool(ratio <= 1.02)
    p99_on = sides.get("forensics_on", {}).get(
        "e2e_rpc_classify_p99_ms_forensics_on")
    p99_off = sides.get("forensics_off", {}).get(
        "e2e_rpc_classify_p99_ms_forensics_off")
    if p99_on and p99_off:
        out["e2e_tracing_overhead_p99_ratio"] = round(p99_on / p99_off, 4)
    return out


def run_observability_overhead(transport: str = "python",
                               measure: float = TEXT_MEASURE_SECONDS
                               ) -> dict:
    """ISSUE 7 satellite: the FULL observability plane's cost, measured
    the same adjacent-A/B way as the ISSUE 4 tracing overhead — but the
    "on" side now also carries time-series ring sampling + live SLO
    burn-rate evaluation on a 1 s telemetry tick, and the "off" side
    strips forensics AND the model-health plane entirely. Same classify
    workload, same <2% p50 budget
    (``e2e_observability_overhead_p50_ratio``)."""
    out: dict = {}
    sides = {}
    for tag, forensics, health in (("obs_on", True, True),
                                   ("obs_off", False, False)):
        try:
            r = run(transport, workload="classify", measure=measure,
                    tag=tag, forensics=forensics, model_health=health)
        except Exception as e:  # noqa: BLE001 — partial results beat none
            out[f"e2e_{tag}_error"] = repr(e)[:200]
            continue
        out.update(r)
        sides[tag] = r
    p50_on = sides.get("obs_on", {}).get("e2e_rpc_classify_p50_ms_obs_on")
    p50_off = sides.get("obs_off", {}).get("e2e_rpc_classify_p50_ms_obs_off")
    if p50_on and p50_off:
        ratio = p50_on / p50_off
        out["e2e_observability_overhead_p50_ratio"] = round(ratio, 4)
        out["e2e_observability_overhead_ok"] = bool(ratio <= 1.02)
    p99_on = sides.get("obs_on", {}).get("e2e_rpc_classify_p99_ms_obs_on")
    p99_off = sides.get("obs_off", {}).get("e2e_rpc_classify_p99_ms_obs_off")
    if p99_on and p99_off:
        out["e2e_observability_overhead_p99_ratio"] = round(
            p99_on / p99_off, 4)
    return out


def run_event_plane_overhead(transport: str = "python",
                             measure: float = TEXT_MEASURE_SECONDS
                             ) -> dict:
    """ISSUE 14 satellite: the event plane ships with its serving cost
    measured. The plane is OFF the request hot path by design (events
    fire on state transitions, not per request), so the A/B — journal
    at default depth + incident triggers armed vs capacity 0 + triggers
    off — measures the residual hook cost under the same classify
    workload and <2% p50 budget as the other observability planes.
    A per-emit microbench (``e2e_event_emit_us``) pins the cost one
    transition pays when it DOES fire."""
    out: dict = {}
    sides = {}
    for tag, enabled in (("events_on", None), ("events_off", False)):
        try:
            r = run(transport, workload="classify", measure=measure,
                    tag=tag, events_enabled=enabled)
        except Exception as e:  # noqa: BLE001 — partial results beat none
            out[f"e2e_{tag}_error"] = repr(e)[:200]
            continue
        out.update(r)
        sides[tag] = r
    p50_on = sides.get("events_on", {}).get(
        "e2e_rpc_classify_p50_ms_events_on")
    p50_off = sides.get("events_off", {}).get(
        "e2e_rpc_classify_p50_ms_events_off")
    if p50_on and p50_off:
        ratio = p50_on / p50_off
        out["e2e_event_plane_overhead_p50_ratio"] = round(ratio, 4)
        out["e2e_event_plane_overhead_ok"] = bool(ratio <= 1.02)
    mean_on = sides.get("events_on", {}).get(
        "e2e_rpc_classify_mean_ms_events_on")
    mean_off = sides.get("events_off", {}).get(
        "e2e_rpc_classify_mean_ms_events_off")
    if mean_on and mean_off:
        out["e2e_event_plane_overhead_mean_ratio"] = round(
            mean_on / mean_off, 4)
    # per-emit cost: what one state transition pays to land on the
    # timeline (journal append + HLC tick + trace-context probe)
    from jubatus_tpu.utils.events import EventJournal

    j = EventJournal(capacity=2048)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        j.emit("bench", "tick", seq=i)
    out["e2e_event_emit_us"] = round(
        (time.perf_counter() - t0) / n * 1e6, 3)
    return out


def run_profiling_overhead(transport: str = "python",
                           measure: float = TEXT_MEASURE_SECONDS,
                           pairs: int = 3) -> dict:
    """ISSUE 8 satellite: the always-on stack sampler ships with its
    cost measured. Adjacent A/B PAIRS on the classify plane — sampler
    ON at the default ~67 Hz vs fully OFF (no thread) — with
    median-of-pairs ratios: the histogram quantiles move in ~19%
    bucket steps, so a single pair's p50 ratio reads either 1.0 or a
    full bucket (dry runs: 1.0, 1.0, 1.1892 from identical code). The
    <2% budget (``e2e_profiling_overhead_ok``) therefore gates on the
    CONTINUOUS mean-latency ratio, with the median p50 ratio required
    to stay within one bucket step."""
    out: dict = {}
    r_p50, r_p99, r_mean = [], [], []
    for i in range(max(1, pairs)):
        sides = {}
        for tag, hz in (("prof_on", 67.0), ("prof_off", 0.0)):
            try:
                r = run(transport, workload="classify", measure=measure,
                        tag=tag, profile_hz=hz)
            except Exception as e:  # noqa: BLE001 — partial beats none
                out[f"e2e_{tag}_error"] = repr(e)[:200]
                continue
            if i == 0:
                out.update(r)  # per-side keys of record: first pair
            sides[tag] = r
        for key, acc in (("p50_ms", r_p50), ("p99_ms", r_p99),
                         ("mean_ms", r_mean)):
            on = sides.get("prof_on", {}).get(
                f"e2e_rpc_classify_{key}_prof_on")
            off = sides.get("prof_off", {}).get(
                f"e2e_rpc_classify_{key}_prof_off")
            if on and off:
                acc.append(on / off)
    import numpy as _np

    if r_p50:
        med_p50 = float(_np.median(r_p50))
        out["e2e_profiling_overhead_p50_ratio"] = round(med_p50, 4)
        if r_mean:
            med_mean = float(_np.median(r_mean))
            out["e2e_profiling_overhead_mean_ratio"] = round(med_mean, 4)
            # mean resolves the 2%; p50 can only prove "same bucket"
            out["e2e_profiling_overhead_ok"] = bool(
                med_mean <= 1.02 and med_p50 <= 1.19)
        out["e2e_profiling_overhead_note"] = (
            f"median of {len(r_p50)} adjacent on/off pairs; p50/p99 are "
            "bucket-quantized (~19% steps), the mean ratio carries the "
            "<2% verdict")
    if r_p99:
        out["e2e_profiling_overhead_p99_ratio"] = round(
            float(_np.median(r_p99)), 4)
    return out


def run_quality_overhead(transport: str = "python",
                         measure: float = TEXT_MEASURE_SECONDS,
                         pairs: int = 3) -> dict:
    """ISSUE 17: the data-quality plane ships with its serving cost
    measured. Adjacent A/B PAIRS on the classify plane — recorder
    armed at the documented 5% sample vs ``--quality-sample 0`` (the
    off side's recorder calls collapse to one float compare in
    ``admit``) — through the Python converter so the ``convert_batch``
    recording hook sits ON the measured path. Same protocol and <2%
    budget as run_profiling_overhead: a single pair swings ~±10% on
    the shared core, so the verdict is the MEDIAN-of-pairs mean ratio,
    with the median p50 ratio held to one histogram bucket step
    (~19%)."""
    out: dict = {}
    r_p50, r_mean = [], []
    for i in range(max(1, pairs)):
        sides = {}
        for tag, armed in (("quality_on", True), ("quality_off", False)):
            try:
                r = run(transport, workload="classify", measure=measure,
                        tag=tag, native_ingest=False, quality=armed)
            except Exception as e:  # noqa: BLE001 — partial beats none
                out[f"e2e_{tag}_error"] = repr(e)[:200]
                continue
            if i == 0:
                out.update(r)  # per-side keys of record: first pair
            sides[tag] = r
        for key, acc in (("p50_ms", r_p50), ("mean_ms", r_mean)):
            on = sides.get("quality_on", {}).get(
                f"e2e_rpc_classify_{key}_quality_on")
            off = sides.get("quality_off", {}).get(
                f"e2e_rpc_classify_{key}_quality_off")
            if on and off:
                acc.append(on / off)
    import numpy as _np

    if r_p50 and r_mean:
        med_p50 = float(_np.median(r_p50))
        med_mean = float(_np.median(r_mean))
        out["e2e_quality_overhead_p50_ratio"] = round(med_p50, 4)
        out["e2e_quality_overhead_mean_ratio"] = round(med_mean, 4)
        out["e2e_quality_overhead_ok"] = bool(
            med_mean <= 1.02 and med_p50 <= 1.19)
        out["e2e_quality_overhead_note"] = (
            f"median of {len(r_mean)} adjacent on/off pairs; the mean "
            "ratio carries the <2% verdict, p50 is bucket-quantized "
            "(~19% steps)")
    return out


def run_usage_overhead(transport: str = "python",
                       measure: float = TEXT_MEASURE_SECONDS,
                       pairs: int = 3) -> dict:
    """ISSUE 19: the usage-attribution plane ships with its serving
    cost measured. Adjacent A/B PAIRS on the classify plane — ledger
    armed at the documented top-64 table vs ``--usage-top 0`` (the off
    side never constructs a ledger: no span sink, no recorder hooks,
    no per-request principal swap billing) — same protocol and <2%
    budget as run_quality_overhead: a single pair swings ~±10% on the
    shared core, so the verdict is the MEDIAN-of-pairs mean ratio,
    with the median p50 ratio held to one histogram bucket step
    (~19%)."""
    out: dict = {}
    r_p50, r_mean = [], []
    for i in range(max(1, pairs)):
        sides = {}
        for tag, armed in (("usage_on", True), ("usage_off", False)):
            try:
                r = run(transport, workload="classify", measure=measure,
                        tag=tag, native_ingest=False, usage=armed)
            except Exception as e:  # noqa: BLE001 — partial beats none
                out[f"e2e_{tag}_error"] = repr(e)[:200]
                continue
            if i == 0:
                out.update(r)  # per-side keys of record: first pair
            sides[tag] = r
        for key, acc in (("p50_ms", r_p50), ("mean_ms", r_mean)):
            on = sides.get("usage_on", {}).get(
                f"e2e_rpc_classify_{key}_usage_on")
            off = sides.get("usage_off", {}).get(
                f"e2e_rpc_classify_{key}_usage_off")
            if on and off:
                acc.append(on / off)
    import numpy as _np

    if r_p50 and r_mean:
        med_p50 = float(_np.median(r_p50))
        med_mean = float(_np.median(r_mean))
        out["e2e_usage_overhead_p50_ratio"] = round(med_p50, 4)
        out["e2e_usage_overhead_mean_ratio"] = round(med_mean, 4)
        out["e2e_usage_overhead_ok"] = bool(
            med_mean <= 1.02 and med_p50 <= 1.19)
        out["e2e_usage_overhead_note"] = (
            f"median of {len(r_mean)} adjacent on/off pairs; the mean "
            "ratio carries the <2% verdict, p50 is bucket-quantized "
            "(~19% steps)")
    return out


def run_usage_attribution(nproc: int = 4, seconds: float = 18.0,
                          base_rate: float = 40.0, seed=None) -> dict:
    """ISSUE 19: the usage ledger's books must BALANCE. A mixed
    3-tenant fleet_sim profile (checkout/search/ads, tenant id on the
    envelope's 7th element) drives proxy + two backends; afterwards the
    conservation gate compares, per node, the ledger's accounted
    CPU-thread-seconds against the span plane's process totals (sum of
    ``rpc.*`` dispatch-histogram ``total_s``, client spans excluded) and
    the accounted device-seconds against the coalescers' measured device
    time. Both sides observe the SAME work through different pipes — a
    gap means requests are escaping attribution.

    Keys of record:

    - ``e2e_usage_attribution_err_frac`` — worst per-node relative gap
      across both planes; gated ≤ 0.10 (``..._ok``).
    - ``e2e_usage_tenants_distinct_ok`` — the fleet-merged doc (live
      ``get_usage`` through the proxy, folded with
      ``usage.merge_usage``) shows ≥ 2 tenants with distinct nonzero
      CPU cost — attribution, not just accounting.
    - ``e2e_capacity_headroom`` — a backend's published headroom gauge
      after a forced capacity tick.
    """
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs
    from jubatus_tpu.utils import usage as usage_mod
    from bench_mix import scrub_child_env

    fleet_sim = _fleet_sim()
    seed = SEED if seed is None else int(seed)
    # flat rate, no flash: the gate is about books, not elasticity
    model = fleet_sim.TrafficModel(seed=seed, base_rate=base_rate,
                                   diurnal_amplitude=0.0)
    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = "0"
    servers: list = []
    proxy = None
    out: dict = {}
    try:
        store = _Store()
        for _ in range(2):
            srv = EngineServer(
                "classifier", CONF,
                args=ServerArgs(engine="classifier",
                                coordinator="(shared)", name="usage",
                                listen_addr="127.0.0.1", thread=32,
                                interval_sec=1e9, interval_count=1 << 30,
                                telemetry_interval=1.0),
                coord=MemoryCoordinator(store))
            srv.start(0)
            servers.append(srv)
        proxy = Proxy(ProxyArgs(engine="classifier",
                                listen_addr="127.0.0.1", thread=64,
                                interconnect_timeout=120.0),
                      coord=MemoryCoordinator(store))
        pport = proxy.start(0)
        res = fleet_sim.drive(
            pport, model, nproc, seconds, cluster="usage",
            workload="train", call_batch=4, lat_slo_ms=1000.0,
            inflight_cap=16, env=scrub_child_env(os.environ))
        out["e2e_usage_driven_done"] = int(res.get("done", 0))

        # -- conservation: ledger vs span plane, per node ---------------
        errs = []
        for node in servers + [proxy]:
            hists = node.rpc.trace.snapshot()["hists"]
            span_s = sum(
                h["total_s"] for n, h in hists.items()
                if n.startswith("rpc.") and
                not n.startswith("rpc.client."))
            tot = node.usage.totals()
            if span_s > 1e-3:
                errs.append(abs(tot["cpu_seconds"] - span_s) / span_s)
        # device plane: billed device shares vs the coalescers' clock
        dev_led = sum(s.usage.totals()["device_seconds"]
                      for s in servers)
        dev_clock = sum(
            co.stats().get("device_seconds", 0.0)
            for s in servers for co in s.coalescers.values())
        if dev_clock > 1e-3:
            errs.append(abs(dev_led - dev_clock) / dev_clock)
        err = max(errs) if errs else 1.0
        out["e2e_usage_attribution_err_frac"] = round(err, 4)
        out["e2e_usage_attribution_ok"] = bool(err <= 0.10)

        # -- distinct per-tenant cost via the LIVE fold path ------------
        # (the same pipe jubactl -c usage reads: get_usage through the
        # proxy broadcasts to members; merge is sketch/table fold,
        # never gauge averaging)
        with RpcClient("127.0.0.1", pport, timeout=30.0) as c:
            docs = c.call("get_usage", "usage")
        fleet = usage_mod.merge_usage(
            [d for d in docs.values() if d])
        rows = usage_mod.principal_rows(fleet)
        tenant_cpu = {p: agg["cpu_seconds"] for p, agg in rows
                      if not p.startswith("(") and
                      agg["cpu_seconds"] > 0.0}
        out["e2e_usage_tenants_seen"] = len(tenant_cpu)
        out["e2e_usage_tenants_distinct_ok"] = bool(
            len(tenant_cpu) >= 2 and
            len(set(round(v, 6) for v in tenant_cpu.values())) >= 2)
        for p, v in sorted(tenant_cpu.items()):
            out[f"e2e_usage_cpu_s_{p}"] = round(v, 4)

        # -- capacity headroom gauge ------------------------------------
        srv0 = servers[0]
        srv0.usage.tick(srv0._capacity_rows_per_sec())
        st = srv0.usage.stats()
        if "headroom" in st:
            out["e2e_capacity_headroom"] = round(
                float(st["headroom"]), 4)
    finally:
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
        if proxy is not None:
            proxy.stop()
        for s in servers:
            s.stop()
    return out


def run_usage(transport: str = "python",
              measure: float = TEXT_MEASURE_SECONDS) -> dict:
    """ISSUE 19 aggregate: attribution conservation + overhead A/B."""
    out: dict = {}
    try:
        out.update(run_usage_attribution())
    except Exception as e:  # noqa: BLE001 — partial beats none
        out["e2e_usage_attribution_error"] = repr(e)[:200]
    try:
        out.update(run_usage_overhead(transport, measure=measure))
    except Exception as e:  # noqa: BLE001 — partial beats none
        out["e2e_usage_overhead_error"] = repr(e)[:200]
    return out


def run_quality_prequential(batches: int = 80, batch: int = 40,
                            holdout: int = 400) -> dict:
    """ISSUE 17: the prequential (test-then-train) estimate must TRACK
    reality. Margin-separated linear labels (PA converges within the
    first batches), microbatch OFF so the train handler's current-model
    scoring is synchronous and deterministic, one quality window that
    never rolls. After training, a FRESH holdout is classified with the
    final model; the streaming estimate must sit within one point of
    that held-out accuracy (``e2e_prequential_tracks_holdout_ok``)."""
    import numpy as np
    from jubatus_tpu.client import Datum
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    rng = np.random.default_rng(SEED)
    w = rng.standard_normal(8)
    w /= float(np.linalg.norm(w))

    def draw(n):
        rows = []
        while len(rows) < n:
            x = rng.uniform(-1.0, 1.0, size=8)
            m = float(x @ w)
            if abs(m) < 0.3:  # margin: PA separates this in one pass
                continue
            rows.append(("pos" if m > 0 else "neg",
                         Datum({f"f{j}": float(x[j]) for j in range(8)})))
        return rows

    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = "0"
    srv = None
    out: dict = {}
    try:
        srv = EngineServer(
            "classifier", CONF,
            args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                            thread=4, microbatch_max=0,
                            telemetry_interval=0.0, quality_sample=1.0,
                            quality_window=1e6))
        port = srv.start(0)
        with RpcClient("127.0.0.1", port, timeout=120.0) as c:
            for _ in range(batches):
                c.call("train", "quality",
                       [[lab, d.to_msgpack()] for lab, d in draw(batch)])
            ok = n = 0
            rows = draw(holdout)
            for i in range(0, len(rows), 50):
                chunk = rows[i:i + 50]
                ranked = c.call("classify", "quality",
                                [d.to_msgpack() for _lab, d in chunk])
                for (lab, _d), r in zip(chunk, ranked):
                    n += 1
                    if not r:
                        continue
                    top = max(r, key=lambda kv: float(kv[1]))[0]
                    if isinstance(top, bytes):
                        top = top.decode()
                    ok += int(top == lab)
        st = srv.quality.stats()
    finally:
        if srv is not None:
            srv.stop()
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
    preq = st.get("prequential_accuracy")
    hold = round(ok / max(n, 1), 4)
    out["e2e_prequential_accuracy"] = preq
    out["e2e_holdout_accuracy"] = hold
    out["e2e_prequential_scored_rows"] = st.get("scored_rows", 0)
    if preq is not None:
        out["e2e_prequential_tracks_holdout_ok"] = bool(
            abs(preq - hold) <= 0.01 + 1e-9)
    return out


def run_quality_drift_drill(nproc: int = 4, shift_at: float = 15.0,
                            magnitude: float = 1.5, window_s: float = 6.0,
                            base_rate: float = 80.0,
                            threshold: float = 0.2) -> dict:
    """ISSUE 17 drill: a seeded mid-run covariate+concept shift
    (fleet_sim ``--shift-at``) must light the whole reporting chain:
    ``quality.drift.<group>`` crosses the threshold within two windows
    of the shift, the drift SLO (plain ``gauge:`` grammar — zero new
    SLO machinery) fires, and exactly ONE incident bundle captures the
    offending feature group's reference/live sketch pair.

    ``e2e_drift_baseline_psi`` is the pre-shift false-alarm level
    (down-good: a rising baseline means the detector is noisy);
    ``e2e_shift_peak_score`` records the drill's magnitude for context
    (its absolute value tracks the injected shift, not code quality).

    Sizing: clean-window PSI noise rides the number of DISTINCT user
    draws per group-window (``call_batch`` duplicates the same datum,
    adding no information). 80 req/s over 6 s windows gives the
    smallest tenant (ads, weight 0.2) ~96 draws/window — enough to
    hold the clean-phase level under the 0.2 operating point."""
    import tempfile

    from jubatus_tpu.client import Datum
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from bench_mix import scrub_child_env

    fleet_sim = _fleet_sim()
    seconds = 2.0 * shift_at  # symmetric clean/shifted phases
    model = fleet_sim.TrafficModel(
        seed=SEED, base_rate=base_rate, diurnal_amplitude=0.0,
        shift_at=shift_at, shift_magnitude=magnitude)
    feature_groups = {t[:2] for t, _w in model.tenants}
    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    prev_ing = os.environ.get("JUBATUS_TPU_NATIVE_INGEST")
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = "0"
    # Python ingest: feature NAMES must reach the recorder so drift
    # lands in the per-tenant groups the incident is meant to name
    # (the native raw path records under the one "hashed" group)
    os.environ["JUBATUS_TPU_NATIVE_INGEST"] = "0"
    inc_dir = tempfile.mkdtemp(prefix="jubatus_quality_drill_")
    srv = None
    res: dict = {}
    records: list = []
    stop = threading.Event()
    out: dict = {
        "e2e_shift_at_s": shift_at, "e2e_shift_magnitude": magnitude,
        "e2e_quality_window_s": window_s}
    try:
        srv = EngineServer(
            "classifier", CONF,
            args=ServerArgs(
                engine="classifier", name="fleet",
                listen_addr="127.0.0.1", thread=32,
                interval_sec=1e9, interval_count=1 << 30,
                telemetry_interval=1.0,
                quality_sample=1.0, quality_window=window_s,
                quality_ref_windows=1,
                slo=[f"drift=gauge:quality.drift.max:{threshold:g}"],
                slo_fast_window=window_s, slo_slow_window=2 * window_s,
                incident_dir=inc_dir))
        port = srv.start(0)
        # warm the jit caches before the clock starts (the first train
        # compiles ~seconds and would eat the clean phase) WITHOUT
        # letting the constant warm-up rows pollute the reference
        # window the clean traffic pins
        srv.quality.arm(sample=0.0)
        warm = [["a", Datum({f"{t[:2]}{j}": 0.5 for j in range(8)}
                            ).to_msgpack()] for t, _w in model.tenants]
        with RpcClient("127.0.0.1", port, timeout=120.0) as c:
            c.call("train", "fleet", warm * 4)
        srv.rpc.trace.reset()

        from jubatus_tpu.utils.quality import OUTPUT_DRIFT_KEYS

        def monitor():
            while not stop.wait(0.5):
                try:
                    scores = {g: v for g, v in
                              srv.quality.drift_scores().items()
                              if g not in OUTPUT_DRIFT_KEYS}
                    records.append({
                        "ts": time.time(),
                        "drift_max": max(scores.values())
                        if scores else 0.0,
                        "alerts": [a["name"] for a in
                                   (srv.slo.alerts() if srv.slo
                                    else [])]})
                except Exception:  # noqa: BLE001 — bench monitor
                    pass

        mon = threading.Thread(target=monitor, daemon=True,
                               name="quality-drill-monitor")
        mon.start()
        # re-arm just after the workers' start barrier falls, so the
        # first live window (-> the pinned reference) covers exactly
        # one window of real traffic, not the idle warm-up stretch
        rearm = threading.Timer(5.3, srv.quality.arm, kwargs={
            "sample": 1.0})
        rearm.daemon = True
        rearm.start()
        res = fleet_sim.drive(
            port, model, nproc, seconds, cluster="fleet",
            workload="train", call_batch=4, lat_slo_ms=1000.0,
            inflight_cap=16, start_delay_s=5.0,
            env=scrub_child_env(os.environ))
        # grace: the final window's drift + the SLO's slow-burn window
        # may settle a few ticks after the trace ends
        deadline = time.monotonic() + 3.0 * window_s
        while time.monotonic() < deadline:
            if records and records[-1]["alerts"]:
                break
            time.sleep(0.5)
        stop.set()
        mon.join(timeout=5.0)
        scores = srv.quality.drift_scores()
        inc = srv.incidents.list()
        bundles = inc.get("incidents", [])
        inc_doc = (srv.incidents.get(bundles[0]["id"])
                   if len(bundles) == 1 else {})
    finally:
        stop.set()
        if srv is not None:
            srv.stop()
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
        if prev_ing is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_INGEST", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_INGEST"] = prev_ing
    if res.get("dead"):
        out["e2e_drift_drill_dead_clients"] = "; ".join(res["dead"])
    shift_wall = res.get("t0_wall", 0.0) + shift_at
    clean = [r["drift_max"] for r in records if r["ts"] < shift_wall]
    out["e2e_drift_baseline_psi"] = round(max(clean), 4) if clean else 0.0
    out["e2e_shift_peak_score"] = round(
        max((r["drift_max"] for r in records), default=0.0), 4)
    first = next((r for r in records if r["ts"] >= shift_wall
                  and r["drift_max"] > threshold), None)
    lag = round(first["ts"] - shift_wall, 1) if first else -1.0
    out["e2e_drift_detection_lag_s"] = lag
    # "within two windows" with one tick of slack: the live window only
    # crosses min-count ~a second into the shifted regime
    out["e2e_drift_detected_ok"] = bool(
        first is not None and lag <= 2.0 * window_s + 1.5)
    out["e2e_drift_slo_fired_ok"] = any(
        "drift" in r["alerts"] for r in records)
    feat = {g: v for g, v in scores.items() if g in feature_groups}
    if feat:
        out["e2e_shift_group"] = max(feat.items(),
                                     key=lambda kv: kv[1])[0]
    out["e2e_drift_incident_count"] = len(bundles)
    top = (inc_doc.get("quality") or {}).get("top_drift_group", "") \
        if inc_doc else ""
    out["e2e_drift_incident_ok"] = bool(
        len(bundles) == 1 and top in feature_groups)
    if top:
        out["e2e_drift_incident_group"] = top
    return out


def run_quality(transport: str = "python",
                measure: float = TEXT_MEASURE_SECONDS) -> dict:
    """ISSUE 17 slice: quality-plane overhead A/B + prequential-vs-
    holdout tracking + the seeded concept-shift drill."""
    out: dict = {}
    try:
        out.update(run_quality_overhead(transport, measure))
    except Exception as e:  # noqa: BLE001 — partial results beat none
        out["e2e_quality_overhead_error"] = repr(e)[:200]
    try:
        out.update(run_quality_prequential())
    except Exception as e:  # noqa: BLE001
        out["e2e_prequential_error"] = repr(e)[:200]
    try:
        out.update(run_quality_drift_drill())
    except Exception as e:  # noqa: BLE001
        out["e2e_drift_drill_error"] = repr(e)[:200]
    return out


def run_proxy(transport: str = "python",
              measure: float = MEASURE_SECONDS) -> dict:
    """Proxy-tier path (VERDICT r2 item 8): clients -> Proxy (random
    routing, session pool) -> EngineServer, numeric workload. Proxy and
    server share this process (the host has ONE core, so separate
    processes would interleave on it exactly like threads do); the proxy
    hop's real cost — decode, route, re-encode, second socket — is all
    here. Reference shape: juba*_proxy, proxy.hpp:502-593."""
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs

    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    # native is the DEFAULT transport now; "0" forces the Python one
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = \
        "1" if transport == "native" else "0"
    srv = proxy = None
    procs = []
    try:
        store = _Store()
        srv = EngineServer(
            "classifier", CONF,
            args=ServerArgs(engine="classifier", coordinator="(shared)",
                            name="bench", listen_addr="127.0.0.1",
                            thread=N_CLIENTS, interval_sec=1e9,
                            interval_count=1 << 30),
            coord=MemoryCoordinator(store))
        srv.start(0)
        # interconnect timeout must cover the backend's coalescer wait
        # (train blocks until its flush; the server grants timeout*6):
        # the default 10 s intermittently fires under full pipelining on
        # the one-core host, failing the whole trial with a timeout the
        # raw relay correctly refuses to retry (double-apply risk)
        proxy = Proxy(ProxyArgs(engine="classifier", listen_addr="127.0.0.1",
                                thread=N_CLIENTS,
                                interconnect_timeout=120.0),
                      coord=MemoryCoordinator(store))
        pport = proxy.start(0)
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev

        repo = os.path.dirname(os.path.abspath(__file__))
        from bench_mix import scrub_child_env

        env = scrub_child_env(os.environ)
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _CLIENT_PROG, str(pport),
                 str(CALL_BATCH), str(K), str(WARMUP_SECONDS), str(measure),
                 "numeric", str(SEED), str(idx)],
                env=env, cwd=repo, stdout=subprocess.PIPE, text=True)
            for idx in range(N_CLIENTS)
        ]
        total, elapsed_max = 0, 0.0
        for p in procs:
            out, _ = p.communicate(
                timeout=WARMUP_SECONDS + measure + 240)
            for line in out.splitlines():
                if line.startswith("CLIENT "):
                    _, cnt, el = line.split()
                    total += int(cnt)
                    elapsed_max = max(elapsed_max, float(el))
    finally:
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
        for p in procs:
            if p.poll() is None:
                p.kill()
        if proxy is not None:
            proxy.stop()
        if srv is not None:
            srv.stop()
    sps = total / elapsed_max if elapsed_max else 0.0
    out = {f"e2e_rpc_train_samples_per_sec_proxy_{transport}":
           round(sps, 1)}
    # self-healing plane quiescence proof (ISSUE 3): on the happy path
    # the retry/failover budget must not be spent and no breaker may
    # trip — a nonzero rate here means the plane is misfiring under
    # normal load, not healing anything
    counters = proxy.rpc.trace.counters() if proxy is not None else {}
    forwards = max(1, proxy.forward_count) if proxy is not None else 1
    out["e2e_retry_rate"] = round(
        counters.get("rpc.retries", 0) / forwards, 6)
    out["e2e_breaker_open_total"] = sum(
        b.get("opened_total", 0)
        for b in (proxy.breakers.snapshot().values()
                  if proxy is not None else []))
    out["e2e_fanout_timeouts_total"] = counters.get(
        "proxy.fanout_timeouts", 0)
    return out


#: churn-tolerant load generator (elastic membership, ISSUE 10): counts
#: per-call errors instead of dying on the first one, and reconnects
#: when the proxy drops the connection — the churn bench measures the
#: CLUSTER's error behavior, so the client must survive to report it
_CHURN_CLIENT_PROG = r"""
import os, socket, sys, time
import numpy as np
import msgpack
port, call_batch, k, warmup, measure, workload = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
    float(sys.argv[4]), float(sys.argv[5]), sys.argv[6])
from jubatus_tpu.client import Datum
# replayable traffic (ISSUE 12): same per-client stream derivation as
# the main client program — churn traces replay across runs too
seed, idx = sys.argv[7], int(sys.argv[8])
rng = (np.random.default_rng(os.getpid()) if seed == "pid"
       else np.random.default_rng([int(seed), idx]))

def mk_datum():
    return Datum({f"f{j}": float(v)
                  for j, v in enumerate(rng.normal(size=k))})

frames = []
for _ in range(8):
    batch = []
    for _ in range(call_batch):
        label = "a" if rng.random() < 0.5 else "b"
        batch.append([label, mk_datum().to_msgpack()])
    if workload == "classify":
        frames.append(msgpack.packb(
            [0, 1, "classify", ["bench", [d for _l, d in batch]]],
            use_bin_type=True))
    else:
        frames.append(msgpack.packb([0, 1, "train", ["bench", batch]],
                                    use_bin_type=True))

sock = None
unp = msgpack.Unpacker()
def connect():
    global sock, unp
    if sock is not None:
        try: sock.close()
        except OSError: pass
    sock = socket.create_connection(("127.0.0.1", port), timeout=30.0)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    unp = msgpack.Unpacker()
connect()

errors = 0
def call(frame):
    # one call in flight (no pipelining: per-call error accounting)
    global errors
    try:
        sock.sendall(frame)
        while True:
            try:
                msg = unp.unpack()
                break
            except msgpack.OutOfData:
                pass
            data = sock.recv(65536)
            if not data:
                raise ConnectionError("closed")
            unp.feed(data)
        if msg[2] is not None:
            errors += 1
        return True
    except (OSError, ConnectionError):
        errors += 1
        for _ in range(20):
            try:
                connect()
                return False
            except OSError:
                time.sleep(0.25)
        raise

deadline_warm = time.perf_counter() + warmup
i = 0
while time.perf_counter() < deadline_warm:
    call(frames[i % len(frames)]); i += 1
count = 0
errors = 0  # steady-state accounting only
t0 = time.perf_counter()
deadline = t0 + measure
while time.perf_counter() < deadline:
    if call(frames[i % len(frames)]):
        count += call_batch
    i += 1
elapsed = time.perf_counter() - t0
print(f"CHURNCLIENT {workload} {count} {errors} {elapsed:.4f}")
"""


def run_churn(transport: str = "python", measure: float = 60.0,
              churn_period: float = 30.0, backends: int = 3) -> dict:
    """Churn chaos bench (elastic membership, ISSUE 10): 16 mixed
    clients (8 train / 8 classify) against a proxy over ``backends``
    classifier servers while a churn thread KILLS one backend and boots
    a replacement every ``churn_period`` seconds.

    Keys of record:

    - ``e2e_churn_mixed_error``  — error fraction of IDEMPOTENT
      (classify) traffic during churn; the breaker/failover/ring-refresh
      planes must hold it ~0.
    - ``e2e_churn_train_error``  — error fraction of effectful traffic
      (bounded, not zero: a train in flight on the killed socket cannot
      be blindly re-forwarded).
    - ``e2e_churn_p99_inflation_ratio`` — churn-window p99 over the
      quiescent p99 measured first on the same topology (max over
      train/classify at the proxy hop).
    - ``e2e_churn_epoch`` — final membership epoch (join/leave count).
    """
    import numpy as _np

    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs

    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = \
        "1" if transport == "native" else "0"
    store = _Store()

    def boot():
        srv = EngineServer(
            "classifier", CONF,
            args=ServerArgs(engine="classifier", coordinator="(shared)",
                            name="bench", listen_addr="127.0.0.1",
                            thread=8, interval_sec=1e9,
                            interval_count=1 << 30),
            coord=MemoryCoordinator(store))
        srv.start(0)
        return srv

    servers = []
    proxy = None
    procs = []
    stop_churn = threading.Event()
    churn_events = [0]
    try:
        servers = [boot() for _ in range(backends)]
        proxy = Proxy(ProxyArgs(engine="classifier", listen_addr="127.0.0.1",
                                thread=N_CLIENTS,
                                interconnect_timeout=120.0),
                      coord=MemoryCoordinator(store))
        pport = proxy.start(0)
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev

        def churn_loop():
            rng = _np.random.default_rng(0)
            while not stop_churn.wait(churn_period):
                victim_i = int(rng.integers(len(servers)))
                victim = servers[victim_i]
                victim.stop()  # hard kill: ephemeral regs vanish
                churn_events[0] += 1
                if stop_churn.wait(2.0):  # let breakers/refresh react
                    return
                servers[victim_i] = boot()
                churn_events[0] += 1

        repo = os.path.dirname(os.path.abspath(__file__))
        from bench_mix import scrub_child_env

        env = scrub_child_env(os.environ)
        # phase 1 (quiescent): same topology, no churn — the p99
        # baseline the inflation ratio divides by
        quiet_measure = max(measure / 3.0, 10.0)
        wl_list = ["numeric" if i % 2 == 0 else "classify"
                   for i in range(N_CLIENTS)]

        def load(seconds):
            ps = [subprocess.Popen(
                [sys.executable, "-c", _CHURN_CLIENT_PROG, str(pport),
                 str(CALL_BATCH), str(K), str(WARMUP_SECONDS / 2),
                 str(seconds), wl, str(SEED), str(idx)],
                env=env, cwd=repo, stdout=subprocess.PIPE, text=True)
                for idx, wl in enumerate(wl_list)]
            procs.extend(ps)
            # quantile hygiene (same stance as run()): drop the clients'
            # warmup window (compiles, cold sockets) from the phase's
            # histograms so quiet-vs-churn p99 compares steady states
            rt = threading.Timer(WARMUP_SECONDS / 2 + 1.0,
                                 proxy.rpc.trace.reset)
            rt.daemon = True
            rt.start()
            counts = {"numeric": 0, "classify": 0}
            errs = {"numeric": 0, "classify": 0}
            calls = {"numeric": 0, "classify": 0}
            elapsed = 0.0
            for p in ps:
                out, _ = p.communicate(timeout=seconds + 300)
                for line in out.splitlines():
                    if line.startswith("CHURNCLIENT "):
                        _, wl, cnt, er, el = line.split()
                        counts[wl] += int(cnt)
                        errs[wl] += int(er)
                        calls[wl] += int(cnt) // CALL_BATCH + int(er)
                        elapsed = max(elapsed, float(el))
            return counts, errs, calls, elapsed

        proxy.rpc.trace.reset()
        load(quiet_measure)
        quiet = proxy.rpc.trace.trace_status()
        # phase 2 (churn): kill/boot cycle under the same load
        proxy.rpc.trace.reset()
        churner = threading.Thread(target=churn_loop, daemon=True,
                                   name="churn")
        churner.start()
        counts, errs, calls, elapsed = load(measure)
        stop_churn.set()
        churner.join(timeout=10.0)
        churned = proxy.rpc.trace.trace_status()
    finally:
        stop_churn.set()
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
        for p in procs:
            if p.poll() is None:
                p.kill()
        if proxy is not None:
            proxy.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown
                pass
    out = {
        "e2e_churn_events": churn_events[0],
        "e2e_churn_mixed_error": round(
            errs["classify"] / max(calls["classify"], 1), 6),
        "e2e_churn_train_error": round(
            errs["numeric"] / max(calls["numeric"], 1), 6),
        "e2e_churn_mixed_samples_per_sec": round(
            (counts["numeric"] + counts["classify"]) / elapsed, 1)
        if elapsed else 0.0,
    }
    from jubatus_tpu.coord.memory import MemoryCoordinator as _MC

    from jubatus_tpu.coord import membership as _membership

    out["e2e_churn_epoch"] = _membership.get_epoch(
        _MC(store), "classifier", "bench")
    ratios = []
    for m in ("train", "classify"):
        q = quiet.get(f"trace.rpc.{m}.p99_ms")
        c = churned.get(f"trace.rpc.{m}.p99_ms")
        if q and c:
            out[f"e2e_churn_rpc_{m}_p99_ms"] = c
            ratios.append(c / q)
    if ratios:
        out["e2e_churn_p99_inflation_ratio"] = round(max(ratios), 3)
        out["e2e_churn_p99_inflation_ok"] = bool(max(ratios) <= 3.0)
    return out


def run_killall_drill(nodes: int = 3, train_seconds: float = 10.0,
                      store_interval: float = 0.4) -> dict:
    """Kill-everything chaos drill (durable model plane, ISSUE 18): a
    fleet uploading to a shared snapshot store is hard-killed in its
    entirety — no drain, no save, every process gone at once — then
    rebooted from the store alone.

    Keys of record:

    - ``e2e_fleet_coldstart_to_serving_s`` — boot an EMPTY fleet and
      train it to its working model: the price of losing the model.
    - ``e2e_warmboot_recovery_s`` — boot the SAME fleet from the store
      after the massacre: snapshot download + chain replay, no
      retraining.
    - ``e2e_warmboot_beats_cold_ok`` — the whole point: recovery must
      beat retraining.
    - ``e2e_killall_model_loss_rows`` — acked training rows lost BEYOND
      the diff-chain tail. The store's contract is bounded loss: rows
      trained after the last uploaded record (the tail window, at most
      one ``--store-interval``) may die with the fleet; anything the
      chain acknowledged must replay. This key must be 0.
    - ``e2e_killall_tail_window_rows`` — rows in the allowed tail
      window (informational: bounded by interval x ingest rate).
    """
    import shutil as _shutil
    import tempfile as _tempfile

    from jubatus_tpu.client import ClassifierClient, Datum
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.framework.model_store import LocalDirBackend, ModelStore
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    store_dir = _tempfile.mkdtemp(prefix="jubatus_killall_store_")
    coord_store = _Store()

    def boot():
        srv = EngineServer(
            "classifier", CONF,
            args=ServerArgs(engine="classifier", coordinator="(shared)",
                            name="bench", listen_addr="127.0.0.1",
                            thread=4, interval_sec=1e9,
                            interval_count=1 << 30,
                            telemetry_interval=0.1,
                            store_dir=store_dir,
                            store_interval=store_interval,
                            store_compact_every=6),
            coord=MemoryCoordinator(coord_store))
        srv.start(0)
        return srv

    def boot_fleet():
        """All processes restart concurrently after a massacre — boot
        in parallel, exactly like init respawning the whole host."""
        slots: list = [None] * nodes
        def one(i):
            slots[i] = boot()
        ts = [threading.Thread(target=one, args=(i,)) for i in range(nodes)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if any(s is None for s in slots):
            raise RuntimeError("fleet boot failed")
        return slots

    def first_classify(srv):
        """Serving = the node answers a query. Returns the client."""
        c = ClassifierClient("127.0.0.1", srv.rpc.port, "bench",
                             timeout=10.0)
        c.classify([Datum({f"f{j}": 0.0 for j in range(4)})])
        return c

    def datum(rng):
        return Datum({f"f{j}": float(v)
                      for j, v in enumerate(rng.normal(size=4))})

    rng = __import__("numpy").random.default_rng(SEED)
    servers: list = []
    out: dict = {}
    try:
        # ---- phase 1: cold start — empty store, boot + train to the
        # working model. This is what dying WITHOUT a store costs.
        t0 = time.monotonic()
        servers = boot_fleet()
        clients = [first_classify(s) for s in servers]
        acked = [0] * nodes
        deadline = time.monotonic() + train_seconds
        while time.monotonic() < deadline:
            for i, c in enumerate(clients):
                batch = [("pos" if rng.random() < 0.5 else "neg",
                          datum(rng)) for _ in range(50)]
                acked[i] += c.train(batch)
        for c in clients:
            c.classify([datum(rng)])
        cold_s = time.monotonic() - t0
        out["e2e_fleet_coldstart_to_serving_s"] = round(cold_s, 3)
        # let the last diff land, then freeze the per-node chain tails:
        # everything at/under these versions MUST survive the massacre
        time.sleep(store_interval + 0.5)
        reader = ModelStore(LocalDirBackend(store_dir), cluster="bench",
                            engine="classifier")
        tails = {}
        for rec in reader.records():
            tails[rec.node] = max(tails.get(rec.node, 0), rec.version)
        acked_by_node = {s._store_node_name(): acked[i]
                        for i, s in enumerate(servers)}
        # ---- phase 2: the massacre — every process hard-killed at
        # once (stop() drops ephemeral regs and persists NOTHING)
        for s in servers:
            s.stop()
        servers = []
        # ---- phase 3: warm reboot from the store alone
        t0 = time.monotonic()
        servers = boot_fleet()
        clients = [first_classify(s) for s in servers]
        warm_s = time.monotonic() - t0
        out["e2e_warmboot_recovery_s"] = round(warm_s, 3)
        out["e2e_warmboot_beats_cold_ok"] = bool(warm_s < cold_s)
        outcomes = [s.warmboot.get("outcome") for s in servers]
        out["e2e_killall_warm_nodes"] = outcomes.count("warm")
        out["e2e_warmboot_load_s"] = round(max(
            float(s.warmboot.get("seconds", 0.0)) for s in servers), 3)
        out["e2e_warmboot_chain_len"] = max(
            int(s.warmboot.get("chain_len", 0)) for s in servers)
        # ---- verdict: replay every pre-kill chain and count rows lost
        # beyond each tail (must be 0 — the chain acked them), plus the
        # allowed tail window (acked but never uploaded before death)
        loss_beyond_tail = 0
        tail_window = 0
        for node, tail_version in tails.items():
            _blob, meta = reader.materialize(node=node)
            loss_beyond_tail += max(0, tail_version
                                    - int(meta["model_version"]))
            tail_window += max(0, acked_by_node.get(node, 0)
                               - tail_version)
        out["e2e_killall_model_loss_rows"] = loss_beyond_tail
        out["e2e_killall_tail_window_rows"] = tail_window
        out["e2e_killall_acked_rows"] = sum(acked)
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown
                pass
        _shutil.rmtree(store_dir, ignore_errors=True)
    return out


def run_migration_cycle(rows: int = 2000) -> dict:
    """Join -> migrate -> drain cycle on a nearest_neighbor cluster
    (elastic membership, ISSUE 10): measures the state-migration data
    plane's throughput and proves row parity across a full membership
    cycle.

    - ``e2e_migration_mb_per_sec`` — chunked double-buffered pull rate
      (framework/migration.py RangePuller) for a fresh joiner.
    - ``e2e_churn_rows_lost`` — rows missing from the union of
      survivors after join + drain (MUST be 0).
    """
    import numpy as _np

    from jubatus_tpu.client import Datum as _Datum
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "lsh", "parameter": {"hash_num": 64},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    store = _Store()

    def boot(auto=True):
        srv = EngineServer(
            "nearest_neighbor", conf,
            args=ServerArgs(engine="nearest_neighbor",
                            coordinator="(shared)", name="nn",
                            listen_addr="127.0.0.1", thread=4,
                            interval_sec=1e9, interval_count=1 << 30,
                            auto_rebalance=auto),
            coord=MemoryCoordinator(store))
        srv.start(0)
        return srv

    servers = [boot(), boot()]
    out: dict = {}
    try:
        rng = _np.random.default_rng(7)
        clients = [RpcClient("127.0.0.1", s.args.rpc_port)
                   for s in servers]
        for i in range(rows):
            d = _Datum({f"f{j}": float(v)
                        for j, v in enumerate(rng.normal(size=16))})
            clients[i % 2].call("set_row", "nn", f"row{i:06d}",
                                d.to_msgpack())
        # join cold, then a measured explicit rebalance = the migration
        # data plane's number of record
        joiner = boot(auto=False)
        servers.append(joiner)
        jc = RpcClient("127.0.0.1", joiner.args.rpc_port)
        pull = jc.call("rebalance", "nn")
        out["e2e_migration_mb_per_sec"] = float(pull.get("mb_per_sec", 0.0))
        out["e2e_migration_rows_pulled"] = int(pull.get("rows", 0))
        # drain the first server; every row must survive on the union
        clients[0].call("drain", "nn", False)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            st = clients[0].call("drain_status", "nn")
            state = st.get("state")
            state = state.decode() if isinstance(state, bytes) else state
            if state == "drained":
                break
            time.sleep(0.2)
        survivors = set()
        for s in servers[1:]:
            c = RpcClient("127.0.0.1", s.args.rpc_port)
            for rid in c.call("get_all_rows", "nn"):
                survivors.add(rid.decode()
                              if isinstance(rid, bytes) else rid)
            c.close()
        expect = {f"row{i:06d}" for i in range(rows)}
        out["e2e_churn_rows_total"] = rows
        out["e2e_churn_rows_lost"] = len(expect - survivors)
        for c in clients:
            c.close()
        jc.close()
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown
                pass
    return out


def run_async_mix(rounds: int = 12, storm_seconds: float = 4.0) -> dict:
    """Asynchronous staleness-bounded mix bench (ISSUE 11): the round
    barrier off the serving path, measured.

    Phase 1 — drift-parity gate on matched fresh 3-member clusters
    (sync linear vs --mix-async) fed IDENTICAL training: the async
    fold's convergence telemetry and folded model must match the sync
    plane's (``e2e_async_mix_drift_parity_ok``).

    Phase 2 — cadence/stall storm on the async cluster: train/classify
    clients hammer every member while rounds stream back to back.

    - ``e2e_train_stall_during_mix_ms`` — worst measured model-lock
      hold attributable to the mix plane (snapshot + apply gauges)
      while rounds streamed: the "train never waits on a round" claim
      as a number.
    - ``e2e_async_mix_rounds_per_sec`` vs ``e2e_sync_mix_rounds_per_sec``
      — fold cadence under identical load; the async/sync ratio is the
      cadence headroom (``e2e_async_mix_cadence_x``).
    - ``e2e_async_classify_p99_during_mix_ms`` — serving tail while
      rounds stream (and the sync twin for comparison).
    """
    import threading as _threading

    import numpy as _np

    from jubatus_tpu.client import Datum as _Datum
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "PA",
            "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}

    def boot_cluster(mix_async: bool):
        store = _Store()
        servers = []
        for _ in range(3):
            srv = EngineServer(
                "classifier", conf,
                args=ServerArgs(engine="classifier",
                                coordinator="(shared)", name="asyncmix",
                                listen_addr="127.0.0.1", thread=4,
                                interval_sec=1e9,
                                interval_count=1 << 30,
                                telemetry_interval=0,
                                mix_async=mix_async),
                coord=MemoryCoordinator(store))
            srv.start(0)
            servers.append(srv)
        return servers

    def train(srv, rows):
        with RpcClient("127.0.0.1", srv.args.rpc_port) as c:
            c.call("train", "asyncmix",
                   [[label, _Datum(d).to_msgpack()] for label, d in rows])

    out: dict = {}
    # -- phase 1: drift parity on identical, quiesced traffic ---------------
    sync_cluster = boot_cluster(False)
    async_cluster = boot_cluster(True)
    try:
        assert async_cluster[0].mixer.mix_now() is not None  # master+hint
        rows_by_member = [
            [("l0", {"x": 1.0, "y": -0.5}), ("l1", {"x": -1.0, "y": 2.0})],
            [("l0", {"x": 0.5, "y": -2.0}), ("l1", {"x": -0.25, "y": 1.0})],
            [("l1", {"x": -2.0, "y": 0.75}), ("l0", {"x": 2.0, "y": -1.0})],
        ]
        div_sync, div_async = [], []
        for _ in range(3):
            for i in range(3):
                train(sync_cluster[i], rows_by_member[i])
                train(async_cluster[i], rows_by_member[i])
            rs = sync_cluster[0].mixer.mix_now()
            for s in async_cluster[1:]:
                s.mixer.submit_now()
            ra = async_cluster[0].mixer.mix_now()
            div_sync.append((rs or {}).get("health", {}).get(
                "premix_divergence_mean", 0.0))
            div_async.append((ra or {}).get("health", {}).get(
                "premix_divergence_mean", 0.0))
            rows_by_member = rows_by_member[1:] + rows_by_member[:1]
        out["e2e_async_mix_divergence_sync"] = round(
            float(_np.mean(div_sync)), 6)
        out["e2e_async_mix_divergence_async"] = round(
            float(_np.mean(div_async)), 6)
        # identical contributions + all-fresh weights must agree to
        # float noise; 5% absolute headroom keeps the gate honest
        # without riding rounding
        out["e2e_async_mix_drift_parity_ok"] = bool(
            _np.allclose(div_async, div_sync, rtol=1e-3, atol=0.05))

        # -- phase 2: cadence/stall storm under live traffic ----------------
        def storm(servers, is_async, window=storm_seconds):
            stop = _threading.Event()
            p99_lat: list = []

            def writer(idx):
                rng = _np.random.default_rng(idx)
                with RpcClient("127.0.0.1",
                               servers[idx].args.rpc_port) as c:
                    k = 0
                    while not stop.is_set():
                        d = _Datum({"x": float(rng.normal()),
                                    "y": float(rng.normal())})
                        try:
                            c.call("train", "asyncmix",
                                   [[f"l{k % 2}", d.to_msgpack()]])
                        except Exception:  # noqa: BLE001 — bench load
                            return
                        k += 1

            def reader():
                with RpcClient("127.0.0.1",
                               servers[0].args.rpc_port) as c:
                    while not stop.is_set():
                        t0 = time.perf_counter()
                        try:
                            c.call("classify", "asyncmix",
                                   [_Datum({"x": 1.0, "y": 0.0})
                                    .to_msgpack()])
                        except Exception:  # noqa: BLE001
                            return
                        p99_lat.append(
                            (time.perf_counter() - t0) * 1e3)

            threads = [_threading.Thread(target=writer, args=(i,))
                       for i in range(3)]
            threads.append(_threading.Thread(target=reader))
            if is_async:
                # each member pushes on its own background cadence —
                # the production shape: a delayed submitter blocks only
                # its own thread, never the fold
                def submitter(idx):
                    while not stop.is_set():
                        try:
                            servers[idx].mixer.submit_now()
                        except Exception:  # noqa: BLE001 — bench load
                            return
                        time.sleep(0.02)

                threads += [_threading.Thread(target=submitter, args=(i,))
                            for i in (1, 2)]
            for t in threads:
                t.start()
            time.sleep(0.3)  # traffic flowing before rounds start
            done_rounds = 0
            t0 = time.perf_counter()
            deadline = t0 + window
            while time.perf_counter() < deadline and \
                    done_rounds < rounds:
                if servers[0].mixer.mix_now() is not None:
                    done_rounds += 1
            wall = time.perf_counter() - t0
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            stall = 0.0
            for s in servers:
                g = s.rpc.trace.gauges()
                stall = max(stall,
                            g.get("mix.apply_stall_ms", 0.0),
                            g.get("mix.snapshot_stall_ms", 0.0))
            p99 = float(_np.percentile(p99_lat, 99)) if p99_lat else 0.0
            return done_rounds / wall if wall > 0 else 0.0, stall, p99

        sync_rps, sync_stall, sync_p99 = storm(sync_cluster, False)
        async_rps, async_stall, async_p99 = storm(async_cluster, True)
        out["e2e_sync_mix_rounds_per_sec"] = round(sync_rps, 2)
        out["e2e_async_mix_rounds_per_sec"] = round(async_rps, 2)
        if sync_rps > 0:
            out["e2e_async_mix_cadence_x"] = round(async_rps / sync_rps, 2)
        out["e2e_train_stall_during_mix_ms"] = round(async_stall, 3)
        out["e2e_sync_train_stall_during_mix_ms"] = round(sync_stall, 3)
        out["e2e_async_classify_p99_during_mix_ms"] = round(async_p99, 2)
        out["e2e_sync_classify_p99_during_mix_ms"] = round(sync_p99, 2)
        lag = max(getattr(s.mixer, "async_lag_rounds", 0)
                  for s in async_cluster)
        out["e2e_async_mix_lag_rounds"] = int(lag)
        out["e2e_async_mix_dropped_stale"] = int(sum(
            getattr(s.mixer, "async_dropped_stale", 0)
            for s in async_cluster))

        # -- phase 3: straggler cadence — the round-barrier number ----------
        # One member delayed ~10x the round cadence. The sync gather
        # WAITS for it every round; the async fold never does — the
        # cadence ratio under the same fault is the headline of record
        # (ISSUE 11: "mix cadence raisable 10x at the same serving
        # p99"), and the async p99 must stay flat while it happens.
        from jubatus_tpu.utils import faults as _faults

        delay = 2.5
        sync_victim = sync_cluster[2]
        sync_rule = (f"rpc.call.mix_get_diff."
                     f"127.0.0.1:{sync_victim.args.rpc_port}"
                     f":delay:{delay}")
        async_victim = async_cluster[2]
        async_rule = (f"mix.async.submit."
                      f"{async_victim.self_nodeinfo().name}"
                      f":delay:{delay}")
        rules = _faults.arm(sync_rule)
        try:
            s_rps, _s_stall, s_p99 = storm(sync_cluster, False,
                                           window=2.5 * delay)
        finally:
            _faults.disarm(rules)
        rules = _faults.arm(async_rule)
        try:
            a_rps, a_stall, a_p99 = storm(async_cluster, True,
                                          window=2.5 * delay)
        finally:
            _faults.disarm(rules)
        out["e2e_sync_mix_straggler_rounds_per_sec"] = round(s_rps, 3)
        out["e2e_async_mix_straggler_rounds_per_sec"] = round(a_rps, 3)
        if s_rps > 0:
            out["e2e_async_mix_straggler_cadence_x"] = round(
                a_rps / s_rps, 1)
        out["e2e_async_classify_p99_straggler_ms"] = round(a_p99, 2)
        out["e2e_sync_classify_p99_straggler_ms"] = round(s_p99, 2)
        out["e2e_train_stall_straggler_ms"] = round(a_stall, 3)
    finally:
        for s in sync_cluster + async_cluster:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown
                pass
    return out


def run_poison_drill(rounds: int = 6) -> dict:
    """Model-integrity poison drill (ISSUE 15): the guard, measured as
    load-bearing.

    Phase 1 — guarded fleet vs clean twin: a 3-member cluster under
    ``--mix-guard quarantine`` with member 2 armed as a poisoner
    (``mix.diff.poison.<node>:nan``, then a fresh cluster with
    ``scale:1e6``) runs ``rounds`` mix rounds of fixed traffic. The
    twin runs the same traffic with member 2 simply NOT training —
    which is exactly what a perfect quarantine reduces the poisoner
    to. Keys:

    - ``e2e_poison_quarantined_total`` — contributions the guard kept
      out of folds (must be > 0: the poisoner is caught every round);
    - ``e2e_poison_zero_nonfinite_applied_ok`` — no member's model
      ever carries a non-finite weight;
    - ``e2e_poison_drift_vs_clean`` — relative L2 distance between the
      guarded fleet's folded model and the clean twin's (float noise:
      the quarantine removed the poison and nothing else).

    Phase 2 — rollback recovery: a hand-poisoned put_diff total against
    a snapshotted member must be refused, auto-roll back to last-good,
    and leave the member serving — ``e2e_rollback_recovery_s`` is
    refusal→serving wall time.

    Phase 3 — the control: the SAME nan poisoner against a fleet with
    ``--mix-guard off`` must corrupt the model
    (``e2e_poison_unguarded_corrupted``) — proving the guard is what
    stood between the drill and a poisoned fleet
    (``e2e_poison_guard_load_bearing_ok``)."""
    import jax as _jax
    import numpy as _np

    from jubatus_tpu.client import Datum as _Datum
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.utils import faults as _faults

    conf = {"method": "PA",
            "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}

    def boot(name: str, guard: str, n: int = 3):
        store = _Store()
        servers = []
        for _ in range(n):
            srv = EngineServer(
                "classifier", conf,
                args=ServerArgs(engine="classifier",
                                coordinator="(shared)", name=name,
                                listen_addr="127.0.0.1", thread=2,
                                interval_sec=1e9,
                                interval_count=1 << 30,
                                telemetry_interval=0,
                                mix_guard=guard, mix_norm_bound=8.0),
                coord=MemoryCoordinator(store))
            srv.start(0)
            servers.append(srv)
        return servers

    def train(srv, name, rows):
        with RpcClient("127.0.0.1", srv.args.rpc_port) as c:
            c.call("train", name,
                   [[label, _Datum(d).to_msgpack()] for label, d in rows])

    def float_leaves(srv):
        leaves = _jax.tree_util.tree_flatten(srv.driver.pack())[0]
        out = []
        for x in leaves:
            a = _np.asarray(x)
            if a.dtype != object and _np.issubdtype(a.dtype,
                                                    _np.floating):
                out.append(a.reshape(-1))
        return out

    def model_finite(srv) -> bool:
        return all(bool(_np.isfinite(a).all()) for a in float_leaves(srv))

    def model_vec(srv):
        parts = float_leaves(srv)
        return _np.concatenate(parts) if parts else _np.zeros(1)

    def rows_of(rnd: int, i: int):
        return [("l0", {"x": float(rnd + 1), "y": -0.5 * (i + 1)}),
                ("l1", {"x": -1.0 * (i + 1), "y": float(rnd + 1)})]

    def drive(servers, name, victim_trains=True, rule=""):
        rules = _faults.arm(rule) if rule else []
        try:
            for rnd in range(rounds):
                for i, s in enumerate(servers):
                    if i == 2 and not victim_trains:
                        continue
                    train(s, name, rows_of(rnd, i))
                servers[0].mixer.mix_now()
        finally:
            if rules:
                _faults.disarm(rules)

    def quarantined_total(servers) -> int:
        return int(sum(s.rpc.trace.counters().get("mix.quarantined", 0)
                       for s in servers))

    def rel_drift(a, b) -> float:
        va, vb = model_vec(a), model_vec(b)
        if va.shape != vb.shape:
            return float("inf")
        denom = float(_np.linalg.norm(vb)) + 1e-12
        return float(_np.linalg.norm(va - vb)) / denom

    out: dict = {}
    clusters: list = []
    try:
        # -- phase 1: guarded drill vs clean twin, nan then scale -------
        drifts = []
        quarantined = 0
        finite_ok = True
        for tag, mode_rule in (("nan", "nan"), ("scale", "scale:1e6")):
            drill = boot(f"pd_{tag}", "quarantine")
            # the twin is the fleet a PERFECT quarantine reduces the
            # drill to: the poisoner's whole contribution (count leaf
            # included) absent from every fold — i.e. a 2-member
            # cluster running members 0/1's identical traffic
            twin = boot(f"pt_{tag}", "quarantine", n=2)
            clusters += [drill, twin]
            victim = drill[2].self_nodeinfo().name
            drive(drill, f"pd_{tag}",
                  rule=f"mix.diff.poison.{victim}:{mode_rule}")
            drive(twin, f"pt_{tag}")
            quarantined += quarantined_total(drill)
            finite_ok = finite_ok and all(model_finite(s) for s in drill)
            drifts.append(rel_drift(drill[0], twin[0]))
            out[f"e2e_poison_{tag}_quarantined"] = quarantined_total(drill)
        out["e2e_poison_quarantined_total"] = quarantined
        out["e2e_poison_zero_nonfinite_applied_ok"] = bool(finite_ok)
        out["e2e_poison_drift_vs_clean"] = round(max(drifts), 6)
        out["e2e_poison_drift_ok"] = bool(max(drifts) < 1e-3)

        # -- phase 2: rollback recovery ---------------------------------
        from jubatus_tpu.framework.linear_mixer import PROTOCOL_VERSION

        srv = clusters[0][0]
        srv.take_snapshot()
        m = srv.mixer
        with srv.driver.lock:
            diffs = {n: mx.get_diff()
                     for n, mx in srv.driver.get_mixables().items()}

        def _nanify(x):
            a = _np.asarray(x)
            if a.dtype != object and _np.issubdtype(a.dtype,
                                                    _np.floating):
                return _np.full_like(a, _np.nan)
            return a

        poisoned = {"protocol": PROTOCOL_VERSION,
                    "schema": m.local_get_schema(),
                    "base_version": m.model_version,
                    "diffs": _jax.tree_util.tree_map(_nanify, diffs)}
        t0 = time.perf_counter()
        applied = m.local_put_obj(poisoned)
        with RpcClient("127.0.0.1", srv.args.rpc_port) as c:
            c.call("classify", srv.args.name,
                   [_Datum({"x": 1.0, "y": 0.0}).to_msgpack()])
        recovery = time.perf_counter() - t0
        out["e2e_rollback_recovery_s"] = round(recovery, 3)
        out["e2e_rollback_refused_and_restored_ok"] = bool(
            not applied and srv.rollbacks >= 1 and model_finite(srv))

        # -- phase 3: guard off — the poison lands (the control) --------
        exposed = boot("pd_off", "off")
        clusters.append(exposed)
        victim = exposed[2].self_nodeinfo().name
        drive(exposed, "pd_off",
              rule=f"mix.diff.poison.{victim}:nan")
        corrupted = not all(model_finite(s) for s in exposed)
        out["e2e_poison_unguarded_corrupted"] = float(corrupted)
        out["e2e_poison_guard_load_bearing_ok"] = bool(
            corrupted and finite_ok and quarantined > 0)
    finally:
        for cluster in clusters:
            for s in cluster:
                try:
                    s.stop()
                except Exception:  # noqa: BLE001 — teardown
                    pass
    return out


def _fleet_sim():
    """Import tools/fleet_sim.py (tools/ is not a package)."""
    repo = os.path.dirname(os.path.abspath(__file__))
    tools = os.path.join(repo, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import fleet_sim

    return fleet_sim


def run_fleet(nproc: int = 8, initial: int = 2, max_replicas: int = 6,
              quiet: float = 12.0, flash_len: float = 28.0,
              tail: float = 15.0, seed=None,
              per_flush_s: float = 0.1, flush_examples: int = 12,
              base_rate: float = 8.0, flash_mult: float = 10.0,
              call_batch: int = 4, slo_ms: float = 400.0) -> dict:
    """Autoscale flash-crowd drill (ISSUE 12): a seeded 10x traffic
    step against proxy + classifier fleet, autoscaled vs a static
    control fleet.

    Sizing: per-replica capacity is pinned at ``flush_examples /
    per_flush_s`` = 120 examples/s = 30 req/s. Base load 8 req/s runs
    the initial 2 replicas at ~13%; the 10x step offers 80 req/s —
    1.33x the static fleet's capacity (pinned underwater for the whole
    flash) but 0.44 utilization at the autoscaled max of 6, so
    queueing settles well under the 400 ms SLO (4 flush quanta) after
    scale-out. The whole peak stays beneath the one bench core's REAL
    Python proxy+backend throughput ceiling (~190 req/s measured):
    above it, CPU — which added replicas share — becomes the binding
    constraint and the drill would measure the box, not the control
    loop.

    Each backend's device flush is throttled to a fixed per-flush cost
    (a GIL-releasing sleep) with the flush size capped at
    ``flush_examples``, so per-replica capacity is pinned to
    ``flush_examples / per_flush_s`` examples/s and replica count — not
    the one bench core — bounds fleet capacity: scale-out genuinely
    adds capacity, which is the property under test, and overload
    genuinely backs up in ``microbatch.queue_depth``. Load comes from
    tools/fleet_sim.py (diurnal curve + zipf hot users + tenant mix +
    one flash-crowd step at ``quiet`` seconds), identical traffic on
    both runs (same seed).

    Keys of record:

    - ``e2e_scaleout_recovery_s`` — flash onset to the first 3-second
      violation-free stretch on the autoscaled fleet (client-observed).
    - ``e2e_autoscale_slo_violation_s`` / ``e2e_static_slo_violation_s``
      — violated seconds from flash onset on each fleet;
      ``e2e_autoscale_beats_static_ok`` gates autoscaled < static.
    - ``e2e_capacity_per_replica`` — late-flash completed examples/s
      per serving replica on the autoscaled fleet.
    - ``e2e_autoscale_scaleout_latency_s`` — flash onset to the first
      scale_out journal record (the control loop's reaction time).
    """
    from jubatus_tpu.coord.autoscaler import (AutoscaleConfig, Autoscaler,
                                              HookActuator)
    from jubatus_tpu.coord.base import NodeInfo
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs
    from jubatus_tpu.server.proxy import Proxy, ProxyArgs
    from bench_mix import scrub_child_env

    fleet_sim = _fleet_sim()
    seed = SEED if seed is None else int(seed)
    seconds = quiet + flash_len + tail
    model = fleet_sim.TrafficModel(
        seed=seed, base_rate=base_rate, diurnal_period_s=240.0,
        diurnal_amplitude=0.15, flash=((quiet, flash_len, flash_mult),))

    prev = os.environ.get("JUBATUS_TPU_NATIVE_RPC")
    os.environ["JUBATUS_TPU_NATIVE_RPC"] = "0"

    def throttle(srv):
        # fixed per-flush device cost + capped flush size: capacity
        # rides replica count, not the shared bench core (the sleep
        # releases the GIL; the batch itself is never touched — the
        # pipelined coalescer's device stage receives PREPARED batches
        # whose shape is the flush fn's business, not ours)
        for co in srv.coalescers.values():
            orig = co._flush

            def slowed(batch, _orig=orig):
                time.sleep(per_flush_s)
                return _orig(batch)

            co._flush = slowed

    def run_side(autoscaled: bool) -> dict:
        store = _Store()
        servers = []
        srv_lock = threading.Lock()
        stop = threading.Event()

        def boot():
            srv = EngineServer(
                "classifier", CONF,
                args=ServerArgs(
                    engine="classifier", coordinator="(shared)",
                    name="fleet", listen_addr="127.0.0.1", thread=32,
                    interval_sec=1e9, interval_count=1 << 30,
                    microbatch_max=flush_examples,
                    telemetry_interval=1.0,
                    slo=[f"latency:rpc.train:p99:{slo_ms:g}"],
                    slo_fast_window=5.0, slo_slow_window=15.0),
                coord=MemoryCoordinator(store))
            srv.start(0)
            throttle(srv)
            with srv_lock:
                servers.append(srv)
            return srv

        def spawn(n):
            for _ in range(int(n)):
                boot()

        def drain(target):
            node = NodeInfo.from_name(target)
            with srv_lock:
                victim = next((s for s in servers
                               if s.args.rpc_port == node.port), None)
            if victim is None:
                raise RuntimeError(f"no local server {target}")
            with RpcClient(node.host, node.port, timeout=30.0) as c:
                c.call("drain", "fleet", False)
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    st = c.call("drain_status", "fleet")
                    state = st.get("state")
                    state = state.decode() if isinstance(state, bytes) \
                        else state
                    if state == "drained":
                        break
                    time.sleep(0.2)
            victim.stop()
            with srv_lock:
                servers.remove(victim)

        proxy = scaler = None
        try:
            for _ in range(initial):
                boot()
            # each forwarded train call parks a proxy worker for the
            # backend's full coalesce latency — the pool must cover the
            # clients' aggregate in-flight or the PROXY becomes the
            # capacity ceiling and scale-out can't show
            proxy = Proxy(ProxyArgs(engine="classifier",
                                    listen_addr="127.0.0.1", thread=256,
                                    interconnect_timeout=120.0),
                          coord=MemoryCoordinator(store))
            pport = proxy.start(0)
            # warm the jit caches (first train compiles ~seconds) and
            # drop the compile-era histograms BEFORE the clock starts:
            # the drill measures the control loop, not XLA compilation.
            # In-process replicas share one jit cache, so later spawns
            # boot warm.
            from jubatus_tpu.client import Datum as _Datum

            warm_batches = []
            for tenant, _w in model.tenants:
                d = _Datum({f"{tenant[:2]}{j}": 0.5 for j in range(8)})
                for b in (1, call_batch, flush_examples // call_batch):
                    warm_batches.append([["a", d.to_msgpack()]]
                                        * max(b, 1))
            for s in list(servers):
                with RpcClient("127.0.0.1", s.args.rpc_port,
                               timeout=60.0) as c:
                    for batch in warm_batches:
                        c.call("train", "fleet", batch)
                s.rpc.trace.reset()
            cfg = AutoscaleConfig(
                min_replicas=initial, max_replicas=max_replicas,
                poll_interval_s=1.0, window_s=8.0, burn_hot=2.0,
                queue_hot=100.0, queue_cold_fraction=0.3,
                scale_out_confirm=2, scale_out_step=2,
                # scale-in is proven by run_fleet_scalein; inside the
                # drill it must not shrink the fleet mid-phase
                scale_in_confirm=10_000,
                cooldown_s=3.0, backoff_initial_s=1.0,
                dry_run=not autoscaled)
            scaler = Autoscaler(MemoryCoordinator(store), "classifier",
                                "fleet", HookActuator(spawn, drain),
                                config=cfg)
            sizes = []  # (wall_ts, fleet size) sampled per poll

            def tick_loop():
                while not stop.wait(cfg.poll_interval_s):
                    try:
                        rec = scaler.tick()
                        sizes.append((rec["ts"],
                                      rec["signals"]["replicas"]))
                    except Exception:  # noqa: BLE001 — bench loop
                        pass

            ctl = threading.Thread(target=tick_loop, daemon=True,
                                   name="fleet-autoscaler")
            ctl.start()
            t0_wall = time.time()
            out = fleet_sim.drive(
                pport, model, nproc, seconds, cluster="fleet",
                workload="train", call_batch=call_batch,
                lat_slo_ms=slo_ms, inflight_cap=16,
                env=scrub_child_env(os.environ))
            stop.set()
            ctl.join(timeout=10.0)
            # worker-reported clock anchor beats the pre-spawn wall
            # time (worker imports cost seconds before the trace runs)
            out.setdefault("t0_wall", t0_wall)
            out["journal"] = list(scaler.journal)
            out["sizes"] = sizes
            out["final_replicas"] = len(servers)
            out["counters"] = {
                k: v for k, v in scaler.registry.counters().items()
                if k.startswith("autoscale.")}
            return out
        finally:
            stop.set()
            if scaler is not None:
                scaler.stop()
            if proxy is not None:
                proxy.stop()
            with srv_lock:
                doomed = list(servers)
            for s in doomed:
                try:
                    s.stop()
                except Exception:  # noqa: BLE001 — teardown
                    pass

    out: dict = {"e2e_fleet_nproc": nproc, "e2e_fleet_seed": seed,
                 "e2e_fleet_offered_req_per_sec_base": base_rate,
                 "e2e_fleet_flash_multiplier": flash_mult}
    try:
        auto = run_side(autoscaled=True)
        static = run_side(autoscaled=False)
    finally:
        if prev is None:
            os.environ.pop("JUBATUS_TPU_NATIVE_RPC", None)
        else:
            os.environ["JUBATUS_TPU_NATIVE_RPC"] = prev
    onset = int(quiet)
    for tag, side in (("autoscale", auto), ("static", static)):
        viol = fleet_sim.violation_seconds(
            side["per_sec"], start=onset, end=int(seconds) + 1)
        out[f"e2e_{tag}_slo_violation_s"] = len(viol)
        out[f"e2e_{tag}_done_total"] = side["done"]
        out[f"e2e_{tag}_shed_total"] = side["shed"]
        out[f"e2e_{tag}_error_total"] = side["errors"]
        if side.get("dead"):
            out[f"e2e_{tag}_dead_clients"] = "; ".join(side["dead"])
        if tag == "autoscale":
            rec = fleet_sim.recovery_second(viol, onset,
                                            horizon=int(seconds))
            out["e2e_scaleout_recovery_s"] = (
                round(rec - onset, 1) if rec is not None else -1.0)
    # control-loop reaction time + fleet trajectory (autoscaled side)
    spawns = [j for j in auto["journal"] if j["action"] == "scale_out"]
    if spawns:
        out["e2e_autoscale_scaleout_latency_s"] = round(
            spawns[0]["ts"] - (auto["t0_wall"] + quiet), 1)
    out["e2e_autoscale_spawns"] = auto["counters"].get(
        "autoscale.spawns", 0)
    out["e2e_autoscale_drains"] = auto["counters"].get(
        "autoscale.drains", 0)
    out["e2e_autoscale_blocked"] = auto["counters"].get(
        "autoscale.blocked", 0)
    out["e2e_autoscale_final_replicas"] = auto["final_replicas"]
    # capacity per replica: late-flash completed examples/s over the
    # serving fleet size then (sizes sampled per poll, wall-clock)
    late0, late1 = int(quiet + flash_len - 8), int(quiet + flash_len)
    done = auto["per_sec"]["done"][late0:late1]
    late_sizes = [n for ts, n in auto["sizes"]
                  if auto["t0_wall"] + late0 <= ts
                  <= auto["t0_wall"] + late1]
    if done and late_sizes:
        out["e2e_capacity_per_replica"] = round(
            (sum(done) * call_batch / len(done))
            / max(sum(late_sizes) / len(late_sizes), 1.0), 1)
    both = ("e2e_autoscale_slo_violation_s" in out
            and "e2e_static_slo_violation_s" in out)
    if both:
        out["e2e_autoscale_beats_static_ok"] = bool(
            out["e2e_autoscale_slo_violation_s"]
            < out["e2e_static_slo_violation_s"])
    return out


def run_fleet_scalein(rows: int = 600) -> dict:
    """Scale-in half of the drill: an IDLE 3-member nearest_neighbor
    fleet goes sustained-cold, the autoscaler drains the least-loaded
    member through the ISSUE 10 state machine, and every row survives
    on the remaining members — ``e2e_churn_rows_lost`` must stay 0
    through an autoscaler-initiated drain."""
    import numpy as _np

    from jubatus_tpu.client import Datum as _Datum
    from jubatus_tpu.coord.autoscaler import (AutoscaleConfig, Autoscaler,
                                              HookActuator)
    from jubatus_tpu.coord.base import NodeInfo
    from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
    from jubatus_tpu.rpc.client import RpcClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "lsh", "parameter": {"hash_num": 64},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    store = _Store()
    servers = []

    def boot():
        srv = EngineServer(
            "nearest_neighbor", conf,
            args=ServerArgs(engine="nearest_neighbor",
                            coordinator="(shared)", name="fleet",
                            listen_addr="127.0.0.1", thread=4,
                            interval_sec=1e9, interval_count=1 << 30,
                            telemetry_interval=1.0),
            coord=MemoryCoordinator(store))
        srv.start(0)
        servers.append(srv)
        return srv

    def drain(target):
        node = NodeInfo.from_name(target)
        victim = next(s for s in servers
                      if s.args.rpc_port == node.port)
        with RpcClient(node.host, node.port, timeout=60.0) as c:
            c.call("drain", "fleet", False)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                st = c.call("drain_status", "fleet")
                state = st.get("state")
                state = state.decode() if isinstance(state, bytes) \
                    else state
                if state == "drained":
                    break
                time.sleep(0.2)
        victim.stop()
        servers.remove(victim)

    out: dict = {}
    scaler = None
    try:
        for _ in range(3):
            boot()
        rng = _np.random.default_rng(SEED)
        clients = [RpcClient("127.0.0.1", s.args.rpc_port, timeout=30.0)
                   for s in servers]
        for i in range(rows):
            d = _Datum({f"f{j}": float(v)
                        for j, v in enumerate(rng.normal(size=16))})
            clients[i % 3].call("set_row", "fleet", f"row{i:06d}",
                                d.to_msgpack())
        for c in clients:
            c.close()
        scaler = Autoscaler(
            MemoryCoordinator(store), "nearest_neighbor", "fleet",
            HookActuator(lambda n: boot(), drain),
            config=AutoscaleConfig(
                min_replicas=2, max_replicas=3, poll_interval_s=0.5,
                scale_in_confirm=3, cooldown_s=0.0))
        deadline = time.monotonic() + 60.0
        drained = 0
        while time.monotonic() < deadline and drained == 0:
            rec = scaler.tick()
            drained = scaler.registry.counters().get(
                "autoscale.drains", 0)
            time.sleep(0.5)
        out["e2e_autoscale_scalein_drains"] = drained
        survivors = set()
        for s in servers:
            with RpcClient("127.0.0.1", s.args.rpc_port,
                           timeout=30.0) as c:
                for rid in c.call("get_all_rows", "fleet"):
                    survivors.add(rid.decode()
                                  if isinstance(rid, bytes) else rid)
        expect = {f"row{i:06d}" for i in range(rows)}
        out["e2e_churn_rows_total"] = rows
        out["e2e_churn_rows_lost"] = len(expect - survivors)
        out["e2e_autoscale_scalein_replicas"] = len(servers)
    finally:
        if scaler is not None:
            scaler.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — teardown
                pass
    return out


_SHARDED_KNN_CHILD = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp

rows = int(float(sys.argv[1])); shards = int(sys.argv[2])
hash_num, B, k = 64, 4, 10
rng = np.random.default_rng(3)
from jubatus_tpu.ops import knn
words = knn.packed_words(hash_num)
# synthesize the signature table directly: the bench measures the QUERY
# plane (scan + top-k merge), not 1e8 python-side row inserts
sigs_h = rng.integers(0, 2 ** 32, size=(rows, words), dtype=np.uint32)
q = jnp.asarray(rng.integers(0, 2 ** 32, size=(B, words), dtype=np.uint32))

if shards > 1:
    from jax.sharding import Mesh
    from jubatus_tpu.parallel import sharded_knn
    pad = (-rows) % shards
    if pad:
        sigs_h = np.pad(sigs_h, ((0, pad), (0, 0)))
    mesh = Mesh(np.asarray(jax.devices()[:shards]), ("shard",))
    sigs = sharded_knn.shard_table(mesh, jnp.asarray(sigs_h))
    valid = sharded_knn.shard_table(
        mesh, jnp.asarray(np.arange(len(sigs_h)) < rows))
    query = lambda: sharded_knn.sharded_hamming_topk(
        mesh, q, sigs, hash_num=hash_num, k=k, valid=valid)
else:
    sigs = jnp.asarray(sigs_h)

    import functools
    @functools.partial(jax.jit, static_argnames=("k",))
    def dense_topk(q, sigs, k):
        d = knn._hamming_distances_batch_xla(q, sigs, hash_num=hash_num)
        nd, idx = jax.lax.top_k(-d, k)
        return -nd, idx
    query = lambda: dense_topk(q, sigs, k)
per_dev = {}
for sh in sigs.addressable_shards:
    per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + int(
        np.prod(sh.data.shape)) * 4
jax.block_until_ready(query())          # compile + warm
trials = 12 if rows >= 10 ** 7 else 25
ts = []
for _ in range(trials):
    t0 = time.perf_counter()
    jax.block_until_ready(query())
    ts.append(time.perf_counter() - t0)
ts = np.asarray(ts) * 1e3
print(json.dumps({
    "p99_ms": round(float(np.percentile(ts, 99)), 2),
    "p50_ms": round(float(np.median(ts)), 2),
    "table_mb_per_device_max": round(max(per_dev.values()) / 2 ** 20, 1),
    "trials": trials, "batch": B, "k": k,
}))
"""


def run_sharded_knn(shard_counts=(1, 8), scales=("1e6", "1e8"),
                    timeout: float = 3600.0) -> dict:
    """Sharded row-store query bench (ISSUE 13): global top-k over a
    synthesized LSH signature table at 10⁶ and 10⁸ rows, single- vs
    multi-shard (per-shard partial top-k + log-depth on-device merge),
    each in a subprocess with that many virtual devices. Emits
    ``knn_query_p99_ms_rows{1e6,1e8}_{s}shard`` (down-good). Virtual
    CPU devices share one core: multi-shard wall bounds orchestration +
    merge cost; the per-device table slice is the capacity win."""
    import bench_mix

    out: dict = {}
    for scale in scales:
        for s in shard_counts:
            env = bench_mix.scrub_child_env(dict(os.environ))
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if "device_count" not in f]
            env["XLA_FLAGS"] = " ".join(
                flags +
                [f"--xla_force_host_platform_device_count={max(s, 1)}"])
            tag = f"rows{scale}_{s}shard"
            try:
                proc = subprocess.run(
                    [sys.executable, "-c", _SHARDED_KNN_CHILD, scale,
                     str(s)],
                    capture_output=True, text=True, timeout=timeout,
                    env=env)
                doc = json.loads(proc.stdout.strip().splitlines()[-1])
            except Exception as e:  # noqa: BLE001 — partial results
                out[f"knn_query_error_{tag}"] = repr(e)[:200]
                continue
            out[f"knn_query_p99_ms_{tag}"] = doc["p99_ms"]
            out[f"knn_query_p50_ms_{tag}"] = doc["p50_ms"]
            out[f"knn_query_table_mb_per_device_{tag}"] = \
                doc["table_mb_per_device_max"]
    return out


_SHARDED_IVF_CHILD = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp

rows = int(float(sys.argv[1])); shards = int(sys.argv[2])
n_cells = int(sys.argv[3]); nprobe = int(sys.argv[4])
hash_num, B, k = 64, 4, 10
rng = np.random.default_rng(3)
from jubatus_tpu.ops import ivf, knn
from jax.sharding import Mesh
from jubatus_tpu.parallel import sharded_knn
from jubatus_tpu.parallel.sharded_ivf import sharded_ivf_topk

words = knn.packed_words(hash_num)
assert rows % shards == 0
c_local = rows // shards

# CLUSTERED table — the regime an IVF tier serves (and the regime real
# row stores live in): 4096 planted centers, each row = its center XOR
# sparse bit-noise (AND of 4 random words ~= 2 flipped bits per 64)
n_true = 4096
centers = rng.integers(0, 2 ** 32, size=(n_true, words), dtype=np.uint32)
owner = rng.integers(0, n_true, size=rows)
noise = rng.integers(0, 2 ** 32, size=(rows, words), dtype=np.uint32)
for _ in range(3):
    noise &= rng.integers(0, 2 ** 32, size=(rows, words), dtype=np.uint32)
sigs_h = centers[owner] ^ noise
del noise, owner

# queries: perturbed planted centers (near-data, like live traffic)
qc = centers[rng.integers(0, n_true, size=64)]
qn = rng.integers(0, 2 ** 32, size=(64, words), dtype=np.uint32)
for _ in range(3):
    qn &= rng.integers(0, 2 ** 32, size=(64, words), dtype=np.uint32)
q_all = jnp.asarray(qc ^ qn)
q = q_all[:B]

# ---- build the IVF index (timed: ann_build_rows_per_sec) ----------------
t_build0 = time.perf_counter()
samp = sigs_h[rng.choice(rows, size=min(rows, 65536), replace=False)]
emb_s = np.asarray(ivf.embed_signatures(jnp.asarray(samp),
                                        method="lsh", hash_num=hash_num))
cen = np.array(ivf.train_centroids(emb_s, n_cells, iters=4, seed=0))
n_super = max(8, 2 * int(np.sqrt(n_cells)))
supers, members = ivf.build_super(cen, n_super=n_super, seed=0)
cells = np.empty(rows, np.int32)
CHUNK = 1 << 21
for a in range(0, rows, CHUNK):
    b = min(a + CHUNK, rows)
    e = np.asarray(ivf.embed_signatures(jnp.asarray(sigs_h[a:b]),
                                        method="lsh", hash_num=hash_num))
    cells[a:b] = ivf.assign_cells_grouped(e, cen, supers, members,
                                          top_supers=2)
# split hot cells on TRUE counts (the online tier's resplit, done once
# at build): a cell past 1.5x the mean forces the fixed-shape slot cap
# -- and the rescore gather cost is nprobe*cap -- so k-sub-means each
# hot cell into ~mean-sized children before laying out the table
mean_c = rows / n_cells
T = int(2.0 * mean_c)
cnt0 = np.bincount(cells, minlength=n_cells)
hot = np.nonzero(cnt0 > T)[0]
if hot.size:
    lut = np.full(n_cells, -1, np.int32)
    lut[hot] = np.arange(hot.size, dtype=np.int32)
    idxs = np.nonzero(lut[cells] >= 0)[0]
    hcells = cells[idxs]
    he = np.empty((idxs.size, hash_num), np.float32)
    for a in range(0, idxs.size, CHUNK):
        b = min(a + CHUNK, idxs.size)
        he[a:b] = np.asarray(ivf.embed_signatures(
            jnp.asarray(sigs_h[idxs[a:b]]), method="lsh",
            hash_num=hash_num))
    def np_kmeans(pts, k2, seed):
        # pure-numpy lloyd: the split fit is tiny (<=16384 x E, 3
        # iters) and per-cell shapes all differ -- jitting each would
        # mean hundreds of one-shot XLA compiles
        r2 = np.random.default_rng(seed)
        c0 = pts[r2.choice(pts.shape[0], size=k2, replace=False)].copy()
        for _ in range(3):
            a0 = np.argmin((c0 * c0).sum(1)[None] - 2.0 * (pts @ c0.T), 1)
            for j in range(k2):
                m2 = a0 == j
                if m2.any():
                    c0[j] = pts[m2].mean(0)
        return c0
    extra, next_id = [], n_cells
    fit_rng = np.random.default_rng(7)
    for ci in hot:
        mi = np.nonzero(hcells == ci)[0]
        sub_k = max(2, int(np.ceil(cnt0[ci] / mean_c)))
        fit = mi if mi.size <= 16384 else fit_rng.choice(mi, 16384,
                                                        replace=False)
        sc = np_kmeans(he[fit], sub_k, seed=int(ci))
        a2 = np.argmin((sc * sc).sum(1)[None] - 2.0 * (he[mi] @ sc.T), 1)
        ids = np.concatenate(
            [[ci], next_id + np.arange(sub_k - 1)]).astype(np.int32)
        cells[idxs[mi]] = ids[a2]
        cen[ci] = sc[0]
        extra.append(sc[1:])
        next_id += sub_k - 1
    cen = np.concatenate([cen] + extra).astype(np.float32)
    del he, idxs, hcells, lut
cen_j = jnp.asarray(cen)
n_cells_f = cen.shape[0]
# group rows into per-(shard, cell) slot lists: [S*n_cells, cap] int32,
# -1 padded, LOCAL slots -- and permute each shard's arena CELL-
# CONTIGUOUS (the compacted layout a rebuild converges to) so a probed
# cell's rescore gather is a sequential stream, not C/S-wide random
# cache misses
key = cells.astype(np.int64) + (np.arange(rows) // c_local) * n_cells_f
order = np.argsort(key, kind="stable")
sigs_h = sigs_h[order]
cnt = np.bincount(key, minlength=shards * n_cells_f)
starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
cap = 1 << int(np.ceil(np.log2(max(int(cnt.max()), 1))))
table = np.full((shards * n_cells_f, cap), -1, np.int32)
ks = key[order]
pos = np.arange(rows) - starts[ks]
table[ks, pos] = (np.arange(rows) % c_local).astype(np.int32)
build_s = time.perf_counter() - t_build0
del key, order, ks, pos

mesh = Mesh(np.asarray(jax.devices()[:shards]), ("shard",))
sigs = sharded_knn.shard_table(mesh, jnp.asarray(sigs_h))
slots = sharded_knn.shard_table(mesh, jnp.asarray(table))
cen_r = sharded_knn.replicate(mesh, cen_j)
del table

def embed(qq):
    return ivf.embed_signatures(qq, method="lsh", hash_num=hash_num)

ivf_query = lambda qq: sharded_ivf_topk(
    mesh, qq, embed(qq), sigs, cen_r, slots,
    method="lsh", hash_num=hash_num, k=k, nprobe=nprobe)
exact_query = lambda qq: sharded_knn.sharded_hamming_topk(
    mesh, qq, sigs, hash_num=hash_num, k=k)

def p99(fn, qq, trials):
    jax.block_until_ready(fn(qq))            # compile + warm
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(qq))
        ts.append(time.perf_counter() - t0)
    ts = np.asarray(ts) * 1e3
    return (round(float(np.percentile(ts, 99)), 2),
            round(float(np.median(ts)), 2))

trials = 12 if rows >= 10 ** 7 else 25
ivf_p99, ivf_p50 = p99(ivf_query, q, max(trials, 25))
exact_p99, exact_p50 = p99(exact_query, q, trials)

# recall@10 over 64 near-data queries, by distance threshold: an IVF
# answer counts if its distance <= the exact 10th-nearest distance
# (hamming quantizes hard — id-set overlap would punish legal tie
# resolution, not index quality)
hit = tot = 0
for a in range(0, 64, 8):
    qq = q_all[a:a + 8]
    ed, _ = exact_query(qq)
    ad, _ = ivf_query(qq)
    kth = np.sort(np.asarray(ed), axis=1)[:, k - 1:k]
    hit += int((np.asarray(ad)[:, :k] <= kth + 1e-6).sum())
    tot += 8 * k
print(json.dumps({
    "ivf_p99_ms": ivf_p99, "ivf_p50_ms": ivf_p50,
    "exact_p99_ms": exact_p99, "exact_p50_ms": exact_p50,
    "recall_at_10": round(hit / tot, 4),
    "build_rows_per_sec": round(rows / build_s, 1),
    "build_s": round(build_s, 2), "cells": n_cells_f, "nprobe": nprobe,
    "cells_base": n_cells, "hot_split": int(n_cells_f - n_cells),
    "cell_cap": int(cap), "trials": trials, "batch": B, "k": k,
}))
"""


def run_sharded_knn_ivf(scales=("1e6", "1e8"), shards: int = 8,
                        timeout: float = 7200.0) -> dict:
    """IVF ANN-tier bench (ISSUE 16): two-phase probe+rescore vs the
    exact sharded scan over a CLUSTERED signature table (4096 planted
    centers — the exact-scan cliff is identical, but the data has the
    cell structure real row stores do). Emits
    ``knn_query_p99_ms_rows{scale}_{S}shard_ivf`` (down-good),
    ``ann_recall_at_10_rows{scale}`` (up-good, distance-threshold
    recall vs the exact scan on the SAME table) and
    ``ann_build_rows_per_sec`` (up-good, train + assign + group wall).
    Exact p99 is re-measured in the same child so the speedup quote is
    same-process, same-table honest."""
    import math

    import bench_mix

    out: dict = {}
    for scale in scales:
        rows = int(float(scale))
        n_cells = min(8192,
                      max(64, 1 << int(round(math.log2(rows ** 0.5)))))
        nprobe = max(32, n_cells // 256)
        env = bench_mix.scrub_child_env(dict(os.environ))
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={shards}"])
        tag = f"rows{scale}_{shards}shard"
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _SHARDED_IVF_CHILD, scale,
                 str(shards), str(n_cells), str(nprobe)],
                capture_output=True, text=True, timeout=timeout, env=env)
            if not proc.stdout.strip():
                raise RuntimeError(
                    f"exit {proc.returncode}: "
                    + (proc.stderr or "")[-250:])
            doc = json.loads(proc.stdout.strip().splitlines()[-1])
        except Exception as e:  # noqa: BLE001 — partial results
            out[f"knn_query_error_{tag}_ivf"] = repr(e)[:200]
            continue
        out[f"knn_query_p99_ms_{tag}_ivf"] = doc["ivf_p99_ms"]
        out[f"knn_query_p50_ms_{tag}_ivf"] = doc["ivf_p50_ms"]
        out[f"knn_query_p99_ms_{tag}"] = doc["exact_p99_ms"]
        out[f"knn_query_p50_ms_{tag}"] = doc["exact_p50_ms"]
        out[f"ann_recall_at_10_rows{scale}"] = doc["recall_at_10"]
        out[f"ann_cells_rows{scale}"] = doc["cells"]
        out[f"ann_nprobe_rows{scale}"] = doc["nprobe"]
        out["ann_build_rows_per_sec"] = doc["build_rows_per_sec"]
    return out


def run_tune_regret(dim_bits: int = 24, regret_band: float = 1.25,
                    round_budget: int = 24, trials: int = 5,
                    observe_rounds: int = 6) -> dict:
    """Self-tuning regret bench (ISSUE 20): does the closed loop find
    what a hand sweep finds, and how fast?

    Phase 1 — oracle: hand-sweep every (wire mode x chunk size) plan on
    the tuner's own ladders over a d24-shaped loopback psum (one
    [2, 2^23] f32 leaf = 2^24 params, the BASELINE.md Criteo shape) —
    the best median round is the hand-tuned optimum the tuner is graded
    against (exactly tools/bench_mix_chunk_sweep.py's recipe, single
    process).

    Phase 2 — the REAL control loop from default knobs: a PerfTuner in
    ``on`` mode (bench-paced: settle_rounds=1/confirm=1/no cooldown —
    the bench ticks once per measured round, so the production pacing
    knobs would only multiply wall clock, not change the search) drives
    the same measured psum through a closure adapter. Each tick feeds
    the tuner the true round ms + phase ratios of the CURRENTLY applied
    plan; its apply_mix actuates the plan the next round measures.

    - ``e2e_tune_regret_ratio`` — oracle round ms of the plan the tuner
      SETTLED on / oracle optimum (1.0 = found the hand-tuned plan;
      the acceptance band is <= 1.25).
    - ``e2e_tune_converge_rounds`` — mix rounds consumed before the
      applied plan first measured inside the regret band (target <= 12).
    - ``e2e_tune_observe_overhead_ratio`` — mean round ms with an
      observe-mode (dry-run) tuner ticking every round vs none (the
      <2% A/B budget).
    """
    import numpy as _np

    from jubatus_tpu.coord.perf_tuner import (TUNER_DEFAULTS, PerfTuner,
                                              TunerConfig)
    from jubatus_tpu.parallel.collective import (DEFAULT_CHUNK_MB,
                                                 ErrorFeedback,
                                                 psum_pytree)

    rng = _np.random.default_rng(SEED)
    diff = {"dw": rng.normal(
        size=(2, 1 << (dim_bits - 1))).astype(_np.float32)}
    ef = ErrorFeedback()
    warmed: set = set()

    def measure(mode: str, chunk: float):
        """Best-of-trials round ms (+ last phases) of the loopback psum
        under one plan; first visit warms the (mode, chunk) compile so
        every scored sample is steady-state. Min, not median: host-
        scheduling noise on a shared CPU is strictly additive and
        swings wider than the chunk-size signal itself."""
        kw = {"feedback": ef} if mode == "int8" else {}
        if (mode, chunk) not in warmed:
            psum_pytree(diff, compress=mode, chunk_mb=chunk, **kw)
            warmed.add((mode, chunk))
        times, ph = [], {}
        for _ in range(trials):
            ph = {}
            t0 = time.perf_counter()
            psum_pytree(diff, compress=mode, chunk_mb=chunk, phases=ph,
                        **kw)
            times.append((time.perf_counter() - t0) * 1e3)
        return float(min(times)), ph

    out: dict = {}
    # -- phase 1: the hand-tuned oracle over the tuner's own ladders --------
    oracle: dict = {}
    for mode in TUNER_DEFAULTS["wire_ladder"]:
        for chunk in TUNER_DEFAULTS["chunk_ladder_mb"]:
            oracle[(mode, float(chunk))] = measure(mode, float(chunk))[0]
    best_plan = min(oracle, key=oracle.get)
    oracle_ms = oracle[best_plan]
    out["e2e_tune_oracle_plan"] = f"{best_plan[0]}/{best_plan[1]}mb"
    out["e2e_tune_oracle_round_ms"] = round(oracle_ms, 2)
    default_plan = ("off", float(DEFAULT_CHUNK_MB))
    out["e2e_tune_default_round_ms"] = round(oracle[default_plan], 2)

    # -- phase 2: the closed loop from default knobs ------------------------
    class _Adapter:
        wire, chunk = default_plan
        rounds = 0
        last_ms = 0.0
        ship_frac = 0.5

        def mix_signals(self):
            return {"rounds": self.rounds, "round_ms": self.last_ms,
                    "wire": self.wire, "chunk_mb": self.chunk,
                    "ef_drift": 0.0, "ship_frac": self.ship_frac}

        def apply_mix(self, wire, chunk_mb):
            self.wire, self.chunk = wire, float(chunk_mb)

        def coalescer_signals(self):
            return []

        def cadence_signals(self):
            return None

    ad = _Adapter()
    tuner = PerfTuner(TunerConfig(mode="on", confirm=1, cooldown_s=0.0,
                                  settle_rounds=1), ad)
    converged_at = None
    now = 0.0
    for r in range(1, round_budget + 1):
        ms, ph = measure(ad.wire, ad.chunk)
        denom = sum(float(ph.get(k, 0.0)) for k in
                    ("ship_ms", "reduce_ms", "readback_ms"))
        ad.ship_frac = float(ph.get("ship_ms", 0.0)) / denom \
            if denom > 0 else 0.5
        ad.rounds, ad.last_ms = r, ms
        # grade the plan this round actually ran (by its oracle score,
        # so measurement noise can't flap the convergence round)
        if converged_at is None and \
                oracle[(ad.wire, ad.chunk)] <= regret_band * oracle_ms:
            converged_at = r
        now += 1.0
        tuner.tick(now)
        if tuner.mix is not None and tuner.mix.converged:
            break
    settled = (ad.wire, float(ad.chunk))
    out["e2e_tune_settled_plan"] = f"{settled[0]}/{settled[1]}mb"
    # regret of record: settled vs oracle plan RE-MEASURED in adjacent
    # alternation (the oracle table's samples are a process-epoch old —
    # on a shared CPU that drift alone can exceed the chunk signal, and
    # cross-epoch ratios would grade the scheduler, not the tuner)
    if settled == best_plan:
        out["e2e_tune_regret_ratio"] = 1.0
    else:
        s_ts, o_ts = [], []
        for _ in range(3):
            s_ts.append(measure(*settled)[0])
            o_ts.append(measure(*best_plan)[0])
        # <1.0 means the re-measure inverted the sweep's pick (a flat
        # surface): that is zero regret, not negative
        out["e2e_tune_regret_ratio"] = round(
            max(1.0, min(s_ts) / min(o_ts)), 3)
    out["e2e_tune_converge_rounds"] = converged_at or round_budget
    out["e2e_tune_rounds_total"] = ad.rounds
    out["e2e_tune_plans_scored"] = len(tuner.mix.scores) \
        if tuner.mix is not None else 0

    # -- phase 3: observe-mode A/B (dry-run tick on the round path) ---------
    # interleaved plain/observed rounds, median vs median: adjacent
    # alternation is the same honesty protocol the transport ratio uses
    # (sequential arms ride ±10% host-scheduling swings that dwarf a
    # microsecond tick)
    obs_ad = _Adapter()
    obs = PerfTuner(TunerConfig(mode="observe"), obs_ad)
    plain_times, observe_times = [], []
    t = 1000.0
    for r in range(observe_rounds):
        for arm in (plain_times, observe_times):
            t0 = time.perf_counter()
            psum_pytree(diff, compress=default_plan[0],
                        chunk_mb=default_plan[1])
            arm.append((time.perf_counter() - t0) * 1e3)
            if arm is observe_times:
                obs_ad.rounds, obs_ad.last_ms = r + 1, arm[-1]
                t += 1.0
                obs.tick(t)
    plain_ms = float(_np.median(plain_times))
    observe_ms = float(_np.median(observe_times))
    out["e2e_tune_observe_overhead_ratio"] = round(
        observe_ms / plain_ms, 3) if plain_ms > 0 else 1.0
    return out


def collect(trials: int = 2) -> dict:
    """Alternate transports and keep each one's best trial: run-to-run
    spread was ~±10% in the chip runs of 2026-08-02 and earlier, so a
    single-shot A/B regularly inverts. Alternating A/B/A/B
    in one process and comparing per-transport bests keeps the comparison
    honest without tripling the wall clock. The proxy RATIO is computed
    from MEDIANS of both sides (direct's spread on the shared core is
    ±12%; a best-vs-best ratio would be a race between two maxima)."""
    out = {"e2e_clients": N_CLIENTS, "e2e_call_batch": CALL_BATCH,
           "e2e_features_per_datum": K,
           "e2e_microbatch_max": _default_microbatch()}
    transports = ["python"]
    try:
        from jubatus_tpu.rpc import native_server

        if native_server.available():
            transports.append("native")
    except Exception as e:  # noqa: BLE001
        out["e2e_native_error"] = repr(e)[:200]
    best: dict = {}
    runs_by_tr: dict = {tr: [] for tr in transports}
    for t in range(trials):
        for tr in transports:
            try:
                r = run(tr)
            except Exception as e:  # noqa: BLE001 — partial results beat
                out[f"e2e_{tr}_error"] = repr(e)[:200]  # a dead bench
                continue
            key = f"e2e_rpc_train_samples_per_sec_{tr}"
            runs_by_tr[tr].append(r[key])
            if key not in best or r[key] > best[key]:
                best.update(r)
    out.update(best)
    # the native-transport margin, of record (VERDICT r4 #7): median vs
    # median over the SAME adjacent A/B/A/B alternation the runs came
    # from (best-vs-best would race two maxima; early-vs-late would ride
    # the process-age trend). If the margin is genuinely small now that
    # microbatching dominates, this key is the honest record of that.
    import numpy as _np

    if runs_by_tr.get("python") and runs_by_tr.get("native"):
        out["e2e_transport_ratio_native_vs_python"] = round(
            float(_np.median(runs_by_tr["native"]))
            / float(_np.median(runs_by_tr["python"])), 3)
        out["e2e_transport_ratio_note"] = (
            f"median of {len(runs_by_tr['native'])} native vs "
            f"{len(runs_by_tr['python'])} python runs, adjacent alternation")
    # text workloads, once each on the preferred transport: the canonical
    # tokenized shape and the idf variant — BOTH on the native fast path
    # since round 3 (idf rides the C++ parser with the df tables)
    text_tr = "native" if "native" in transports else "python"
    for tag, conf, wl, ning in (
            ("text", TEXT_CONF, "text", True),
            ("text_idf", TEXT_IDF_CONF, "text", True),
            ("combo", COMBO_CONF, "numeric", True),
            # the Python-converter A/B for the combo fast path: same
            # wire traffic, native parser declined (VERDICT r4 #3)
            ("combo_python", COMBO_CONF, "numeric", False),
            ("text_filter", TEXT_FILTER_CONF, "text", True)):
        try:
            out.update(run(text_tr, workload=wl, conf=conf,
                           measure=TEXT_MEASURE_SECONDS, tag=tag,
                           native_ingest=ning))
        except Exception as e:  # noqa: BLE001
            out[f"e2e_{tag}_error"] = repr(e)[:200]
    # honesty: the text_filter fast path is HYBRID — the regex runs in
    # Python (std::regex/`re` divergence risk), memoized per distinct
    # input; the datum walk/tokenize/tf/hash/emit stay in C++
    out["e2e_text_filter_mode"] = "hybrid: python regex (memoized) + C++ parse"
    # featurize-plane throughput of record (ISSUE 5): convert_batch on
    # the combo and idf shapes, no server/device in the loop
    try:
        out.update(run_fv_convert())
    except Exception as e:  # noqa: BLE001
        out["e2e_fv_convert_error"] = repr(e)[:200]
    # headline host/device overlap: the Python-converter combo run rides
    # the pipelined generic train path (featurize||device by design)
    if "e2e_fv_overlap_fraction_combo_python" in out:
        out["e2e_fv_overlap_fraction"] = \
            out["e2e_fv_overlap_fraction_combo_python"]
    ck = "e2e_rpc_train_samples_per_sec_combo"
    if out.get(ck) and out.get(ck + "_python"):
        out["e2e_combo_native_vs_python"] = round(
            out[ck] / out[ck + "_python"], 2)
    # features-per-datum for the combo shape, so throughput normalizes
    # per EMITTED feature (K base keys -> K + K*(K-1)/2 with the
    # wildcard x wildcard mul rule)
    out["e2e_combo_features_per_datum"] = K + K * (K - 1) // 2
    # query plane: classify samples/s against the trained numeric model
    # (snapshot reads through the raw classify handler — no coalescer)
    try:
        out.update(run(text_tr, workload="classify",
                       measure=TEXT_MEASURE_SECONDS))
    except Exception as e:  # noqa: BLE001
        out["e2e_classify_error"] = repr(e)[:200]
    # mixed plane: 8 writers + 8 readers concurrently (VERDICT r4 #6) —
    # the workload the reference's process-wide rw lock serializes
    try:
        out.update(run(text_tr, workload="mixed",
                       measure=TEXT_MEASURE_SECONDS))
    except Exception as e:  # noqa: BLE001
        out["e2e_mixed_error"] = repr(e)[:200]
    # forensics overhead A/B (ISSUE 4): span store + slow log on vs off,
    # p50 ratio of record with a <2% budget
    try:
        out.update(run_tracing_overhead(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_tracing_overhead_error"] = repr(e)[:200]
    # full observability-plane overhead A/B (ISSUE 7): forensics +
    # time-series sampling + SLO evaluation on vs everything off,
    # same <2% p50 budget
    try:
        out.update(run_observability_overhead(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_observability_overhead_error"] = repr(e)[:200]
    # continuous-profiling overhead A/B (ISSUE 8): the ~67 Hz stack
    # sampler on vs fully off, same <2% p50 budget
    try:
        out.update(run_profiling_overhead(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_profiling_overhead_error"] = repr(e)[:200]
    # event-plane overhead A/B (ISSUE 14): journal + incident triggers
    # on vs stripped, same <2% p50 budget + the per-emit microbench
    try:
        out.update(run_event_plane_overhead(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_event_plane_overhead_error"] = repr(e)[:200]
    # data-quality plane (ISSUE 17): recorder overhead A/B (<2% mean),
    # prequential-vs-holdout tracking, and the seeded concept-shift
    # drill (drift detection -> SLO -> incident bundle)
    try:
        out.update(run_quality(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_quality_error"] = repr(e)[:200]
    # usage-attribution plane (ISSUE 19): 3-tenant conservation gate
    # (accounted CPU/device within 10% of process totals) + ledger
    # overhead A/B (<2% mean)
    try:
        out.update(run_usage(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_usage_error"] = repr(e)[:200]
    # proxy tier: same numeric workload through the proxy hop. The
    # REPORTED keys stay best-of, but the ratio uses median-vs-median
    # over ADJACENT alternating (proxy, direct) pairs: the direct side
    # alone swings ~±12% run to run on the shared core AND trends with
    # process age, so early-direct-vs-late-proxy systematically biased
    # the ratio low (round 4 dry runs: adjacent protocol 0.83-0.87,
    # early/late split 0.79 from the same code).
    dkey = f"e2e_rpc_train_samples_per_sec_{text_tr}"
    pkey = f"e2e_rpc_train_samples_per_sec_proxy_{text_tr}"
    proxy_runs: list = []
    ratio_direct_runs: list = []
    for _ in range(max(trials, 3)):
        try:
            r = run_proxy(text_tr)
            proxy_runs.append(r.get(pkey, 0))
            if r.get(pkey, 0) > out.get(pkey, 0):
                out.update(r)
        except Exception as e:  # noqa: BLE001
            out["e2e_proxy_error"] = repr(e)[:200]
        try:
            d = run(text_tr)
            ratio_direct_runs.append(d[dkey])
            if d[dkey] > out.get(dkey, 0):
                out[dkey] = d[dkey]
        except Exception as e:  # noqa: BLE001
            out[f"e2e_{text_tr}_error"] = repr(e)[:200]
    if proxy_runs and ratio_direct_runs:
        med_d = float(_np.median(ratio_direct_runs))
        med_p = float(_np.median(proxy_runs))
        out["e2e_proxy_vs_direct"] = round(med_p / med_d, 3)
        out["e2e_proxy_vs_direct_note"] = (
            f"median of {len(proxy_runs)} proxy vs "
            f"{len(ratio_direct_runs)} direct runs, adjacent alternation")
    # elastic membership (ISSUE 10): the churn chaos bench (kill/add one
    # of N backends under the 16-client mixed load) + the join/migrate/
    # drain row-parity cycle
    try:
        out.update(run_churn(text_tr))
    except Exception as e:  # noqa: BLE001
        out["e2e_churn_error"] = repr(e)[:200]
    try:
        out.update(run_migration_cycle())
    except Exception as e:  # noqa: BLE001
        out["e2e_migration_error"] = repr(e)[:200]
    # async staleness-bounded mix (ISSUE 11): drift parity vs the sync
    # plane + cadence/stall storm (train-path stall of record)
    try:
        out.update(run_async_mix())
    except Exception as e:  # noqa: BLE001
        out["e2e_async_mix_error"] = repr(e)[:200]
    # model-integrity poison drill (ISSUE 15): armed poisoner
    # quarantined every round, guarded fleet matches a clean twin,
    # non-finite total auto-rolls back, guard-off control corrupts
    try:
        out.update(run_poison_drill())
    except Exception as e:  # noqa: BLE001
        out["e2e_poison_error"] = repr(e)[:200]
    # autoscaling flash-crowd drill (ISSUE 12): seeded 7x traffic step,
    # autoscaled vs static control fleet, plus the autoscaler-initiated
    # scale-in drain's row parity
    try:
        out.update(run_fleet())
    except Exception as e:  # noqa: BLE001
        out["e2e_fleet_error"] = repr(e)[:200]
    try:
        out.update(run_fleet_scalein())
    except Exception as e:  # noqa: BLE001
        out["e2e_fleet_scalein_error"] = repr(e)[:200]
    # durable model plane (ISSUE 18): kill-everything drill — whole
    # fleet hard-killed, rebooted from the shared snapshot store; zero
    # acked-row loss beyond the diff-chain tail, warm beats cold
    try:
        out.update(run_killall_drill())
    except Exception as e:  # noqa: BLE001
        out["e2e_killall_error"] = repr(e)[:200]
    # self-tuning plane (ISSUE 20): regret vs the hand-tuned oracle on
    # the d24 loopback psum + rounds-to-converge + observe-mode A/B
    try:
        out.update(run_tune_regret())
    except Exception as e:  # noqa: BLE001
        out["e2e_tune_error"] = repr(e)[:200]
    return out


if __name__ == "__main__":
    from jubatus_tpu.utils.compile_cache import configure as _configure_cache

    _configure_cache()
    # --seed N (ISSUE 12 satellite): override the base traffic seed for
    # any slice; every client stream derives from [SEED, client_idx]
    if "--seed" in sys.argv:
        i = sys.argv.index("--seed")
        SEED = int(sys.argv[i + 1])
        del sys.argv[i:i + 2]
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        # the autoscale drill on its own (flash-crowd step + scale-in
        # row parity), for ISSUE 12 iteration without the full bench
        out = {}
        nproc = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        out.update(run_fleet(nproc=nproc))
        out.update(run_fleet_scalein())
        print(json.dumps(out, indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "shardedknn":
        # the ISSUE 13 query slice on its own: 10^6/10^8-row top-k,
        # single- vs N-shard (default 8)
        shards = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        scales = tuple(sys.argv[3].split(",")) if len(sys.argv) > 3 \
            else ("1e6", "1e8")
        print(json.dumps(run_sharded_knn((1, shards), scales), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "shardedivf":
        shards = int(sys.argv[2]) if len(sys.argv) > 2 else 8
        scales = tuple(sys.argv[3].split(",")) if len(sys.argv) > 3 \
            else ("1e6", "1e8")
        print(json.dumps(run_sharded_knn_ivf(scales, shards), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "events":
        # the event-plane slice on its own (overhead A/B + per-emit
        # microbench), for ISSUE 14 iteration without the full bench
        print(json.dumps(run_event_plane_overhead(
            measure=float(sys.argv[2]) if len(sys.argv) > 2
            else TEXT_MEASURE_SECONDS), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "quality":
        # the data-quality slice on its own (overhead A/B +
        # prequential tracking + concept-shift drill), for ISSUE 17
        # iteration without the full bench
        print(json.dumps(run_quality(
            measure=float(sys.argv[2]) if len(sys.argv) > 2
            else TEXT_MEASURE_SECONDS), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "usage":
        # the usage-attribution slice on its own (3-tenant conservation
        # gate + ledger overhead A/B), for ISSUE 19 iteration without
        # the full bench
        print(json.dumps(run_usage(
            measure=float(sys.argv[2]) if len(sys.argv) > 2
            else TEXT_MEASURE_SECONDS), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "killall":
        # the ISSUE 18 chaos slice on its own: kill-everything, reboot
        # from the shared snapshot store, prove bounded loss
        print(json.dumps(run_killall_drill(
            train_seconds=float(sys.argv[2]) if len(sys.argv) > 2
            else 6.0), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "tune":
        # the self-tuning slice on its own (oracle sweep + closed-loop
        # regret + observe-mode A/B), for ISSUE 20 iteration without
        # the full bench
        print(json.dumps(run_tune_regret(
            dim_bits=int(sys.argv[2]) if len(sys.argv) > 2 else 24),
            indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "asyncmix":
        # the async-mix slice on its own (drift parity + cadence/stall
        # storm), for ISSUE 11 iteration without the full bench
        print(json.dumps(run_async_mix(), indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "poison":
        # the model-integrity slice on its own (poison drill +
        # rollback recovery + unguarded control), for ISSUE 15
        # iteration without the full bench
        print(json.dumps(run_poison_drill(
            rounds=int(sys.argv[2]) if len(sys.argv) > 2 else 6),
            indent=1))
    elif len(sys.argv) > 1 and sys.argv[1] == "churn":
        # the elastic-membership slice on its own (kill/add cycle +
        # join/migrate/drain parity), for churn iteration without the
        # full bench's half hour
        out = {}
        out.update(run_churn("python",
                             measure=float(sys.argv[2])
                             if len(sys.argv) > 2 else 60.0))
        out.update(run_migration_cycle())
        print(json.dumps(out, indent=1))
    else:
        print(json.dumps(collect(), indent=1))
