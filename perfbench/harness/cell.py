"""One run of one cell: start the configuration's servers, warm the
cell's shapes, cut a window out of a running stream, judge the answers,
reduce the trace, read the metrics. Everything that belongs to one
configuration, traffic mix or metric comes from its own file, found by the
name in ``BENCHMARK.json``; nothing here names a cell."""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import check, stats
from .loadgen import Client, Group, LoadGen
from .servers import Fleet

RUN_DIR = ".perfbench_run"


class NoAccelerator(RuntimeError):
    """The servers did not come up on the chips the cell asks for."""


class Run:
    """What a run observed; the metric readers take this."""

    def __init__(self) -> None:
        self.workload: Dict[str, Any] = {}
        self.config: Dict[str, Any] = {}
        self.traffic: Dict[str, Any] = {}
        self.peaks: Dict[str, Any] = {}
        self.groups: Dict[str, Dict[str, Any]] = {}
        self.records: List[tuple] = []
        self.turnaround: List[tuple] = []
        self.t0 = 0.0
        self.t1 = 0.0
        self.wall_offset = 0.0      # time.time() - time.monotonic()
        self.seconds = 0.0
        self.setup_s = 0.0
        self.first_answer_s = 0.0
        self.phases: Dict[str, float] = {}
        self.status0: List[Dict[str, Any]] = []
        self.status1: List[Dict[str, Any]] = []
        self.status_end: List[Dict[str, Any]] = []
        self.bytes_in_use_max: List[int] = []
        self.trace: Optional[Dict[str, Any]] = None
        self.loadgen_cpu_s = 0.0
        self.loadgen_wall_s = 0.0
        self.device: Dict[str, Any] = {}
        self.rehearsal = False
        #: the rows behind every pool call, and the lone warm-up calls in
        #: the order they were sent: what the reference replays
        self.control: Dict[str, Any] = {}
        self.readings: List[Dict[str, Any]] = []
        self.plan_failed = 0
        #: the configuration's engine, reference and row generator
        #: modules, by its names
        self.engine: Any = None
        self.reference: Any = None
        self.generator: Any = None
        self.pool_rows: Dict[str, List[List[Any]]] = {}
        self.warm_rows: List[tuple] = []

    def window(self, group_method: Optional[str] = None) -> List[tuple]:
        recs = stats.in_window(self.records, self.t0, self.t1)
        if group_method is None:
            return recs
        return [r for r in recs
                if self.groups[r[0]]["method"] == group_method]


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str) -> Any:
    """``<directory>/<name>.py`` as a module: how a reader, an engine's
    surface, a reference and a row generator are found by the name a data
    file gives."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{os.path.basename(directory)}_{name.replace('.', '_')}",
        os.path.join(directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_readers(directory: str) -> Dict[str, Any]:
    """Every ``*.py`` of a metrics directory is one reader: a module with
    ``NAME`` and ``read(run) -> number or None``."""
    mods = [load_module(directory, fn[:-3])
            for fn in sorted(os.listdir(directory))
            if fn.endswith(".py") and not fn.startswith("_")]
    return {mod.NAME: mod for mod in mods}


def load_cell(root: str, bdir: str, workload_name: str):
    """(``BENCHMARK.json``, a :class:`Run` that holds the cell's entry, its
    configuration with the engine, the reference and the row generator it
    names, its traffic mix and the peaks), each from its own file."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    run = Run()
    run.workload = next(w for w in bench["workloads"]
                        if w["name"] == workload_name)
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == run.workload["config"])
    run.config = load_json(os.path.join(root, conf_entry["file"]))
    run.traffic = load_json(os.path.join(
        bdir, "traffic", run.workload["traffic"] + ".json"))
    run.peaks = load_json(os.path.join(bdir, "peaks.json"))
    run.engine = load_module(os.path.join(bdir, "engines"),
                             run.config["engine"])
    run.reference = load_module(os.path.join(bdir, "references"),
                                run.config["reference"])
    if "generator" not in run.config["data"]:
        raise ValueError(
            f"{conf_entry['file']}: data.generator names no row generator "
            "(a file of perfbench/generators/, without its .py)")
    run.generator = load_module(os.path.join(bdir, "generators"),
                                run.config["data"]["generator"])
    return bench, run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pools(run: Run, seed: int, name: str) -> Dict[str, List[bytearray]]:
    """One pool of encoded requests per group of the traffic file."""
    pools: Dict[str, List[bytearray]] = {}
    for gi, g in enumerate(run.traffic["groups"]):
        rows = run.generator.make_rows(run.config["data"], seed, 10 + gi,
                                       g["pool_calls"] * g["rows_per_call"])
        enc = run.engine.ENCODERS[g["method"]]
        n = g["rows_per_call"]
        run.pool_rows[g["name"]] = [rows[i * n:(i + 1) * n]
                                    for i in range(g["pool_calls"])]
        pools[g["name"]] = [enc(name, r) for r in run.pool_rows[g["name"]]]
    return pools


def run_cell(root: str, workload_name: str, seed: int, seconds: float,
             trace: bool, t_process_start: float, rehearse: bool = False,
             server_entry: Optional[List[str]] = None,
             bench_dir: str = "perfbench", control: Optional[str] = None,
             check_seeds: Sequence[int] = ()) -> Dict[str, Any]:
    """Returns the result line's object. Raises :class:`NoAccelerator`
    when the servers are not on the chips the cell asks for (unless
    ``rehearse``, which only the tests reach). ``control`` and
    ``check_seeds`` are for the readings a limit is set from (see
    :func:`_judge`); the driver's runs pass neither."""
    bdir = os.path.join(root, bench_dir)
    bench, run = load_cell(root, bdir, workload_name)
    run.rehearsal = rehearse
    run.seconds = float(seconds)
    run.wall_offset = time.time() - time.monotonic()
    name = run.config["cluster_name"]
    n_servers = int(run.config["replicas"])
    dim = int(run.config["rehearsal"]["hash_max_size"] if rehearse
              else run.config["model"]["converter"]["hash_max_size"])
    run_dir = os.path.join(root, RUN_DIR, workload_name)
    shutil.rmtree(run_dir, ignore_errors=True)

    def phase(label: str) -> None:
        run.phases[label] = time.monotonic() - t_process_start

    conns = sum(g["connections"] for g in run.traffic["groups"])
    fleet = Fleet(root, run_dir, run.config, conns, rehearse, trace,
                  server_entry)
    gen: Optional[LoadGen] = None
    try:
        phase("servers_spawned_s")
        pools = _pools(run, seed, name)
        phase("request_pool_encoded_s")
        first = [fleet.wait_for_status(i, 900.0) for i in range(n_servers)]
        phase("first_answer_s")
        run.first_answer_s = run.phases["first_answer_s"]
        _check_devices(run, first, rehearse)
        log(f"device: platform={run.device['platform']} "
            f"kind={run.device['kind']!r} count={run.device['count']}"
            + ("  (CPU REHEARSAL: no number below is a device number)"
               if rehearse else ""))

        rows_sent = [0] * n_servers
        _warm_calls(run, fleet, name, seed, rows_sent)
        phase("warm_calls_s")

        # -- the stream ------------------------------------------------------
        groups: List[Group] = []
        for g in run.traffic["groups"]:
            for i in range(n_servers) if g.get("server", "each") == "each" \
                    else [int(g["server"])]:
                gname = f"{g['name']}@{i}"
                run.groups[gname] = dict(g, server=i)
                groups.append(Group(
                    gname, fleet.address(i), g["method"], g["connections"],
                    g["rows_per_call"], pools[g["name"]],
                    pool_offset=i * (g["pool_calls"] // n_servers),
                    rate_calls_per_s=float(g.get("rate_calls_per_s", 0.0)),
                    server=i, summarize=run.engine.SUMMARIZERS[g["method"]],
                    keep_every=int(g.get("keep_every", 0))))
        gen = LoadGen(groups)
        gen.start()
        t_stream = time.monotonic()
        _warm_stream(run, fleet, gen)
        phase("warm_stream_s")

        # -- the window ------------------------------------------------------
        run.status0 = [fleet.status(i) for i in range(n_servers)]
        run.t0 = time.monotonic()
        run.setup_s = run.t0 - t_process_start
        run.t1 = run.t0 + run.seconds
        run.bytes_in_use_max = [0] * n_servers
        profiler = _start_profiler(run, fleet, name) if trace else None
        while True:
            left = run.t1 - time.monotonic()
            if left <= 0:
                break
            time.sleep(min(1.0, left))
            if gen.error is not None:
                raise RuntimeError(f"load generator died: {gen.error!r}")
            fleet.check_alive()
            if run.t1 - time.monotonic() > 0.5:
                for i in range(n_servers):
                    st = fleet.status(i)
                    run.bytes_in_use_max[i] = max(
                        run.bytes_in_use_max[i],
                        int(st.get("runtime.jax_device_bytes_in_use", 0) or 0))
        run.status1 = [fleet.status(i) for i in range(n_servers)]
        # the stream runs on past the window, so that a call counts where
        # its answer lands and the runtime sample behind get_status (up to
        # 1 s old) has caught up with the window before it is read again
        time.sleep(run.traffic["run_past_s"])
        run.status_end = [fleet.status(i) for i in range(n_servers)]
        gen.stop_sending()
        gen.join(150)
        run.loadgen_wall_s = time.monotonic() - t_stream
        run.loadgen_cpu_s = gen.cpu_seconds
        run.records = gen.records
        run.turnaround = gen.turnaround
        if profiler is not None:
            profiler.join(120)
        for i, st in enumerate(run.status_end):
            run.bytes_in_use_max[i] = max(
                run.bytes_in_use_max[i],
                int(st.get("runtime.jax_device_bytes_in_use", 0) or 0))
        for rec in run.records:
            g = run.groups[rec[0]]
            if g["method"] == run.engine.UPDATE and rec[6]:
                rows_sent[g["server"]] += g["rows_per_call"]
        _print_window(run, gen)

        # -- correct -----------------------------------------------------------
        gc.disable()   # millions of small tuples: the collector only scans
        try:
            compared = _judge(run, fleet, gen, name, seed, dim, rows_sent,
                              control, check_seeds)
        finally:
            gc.enable()
    finally:
        if gen is not None and gen.is_alive():
            gen.stop_sending()
        problems = fleet.stop()
        for p in problems:
            log(f"stop: {p}")

    if trace:
        run.trace = _reduce_trace(bdir, fleet, run)
    result = _result(bdir, bench, run, compared, trace, gen)
    shutil.rmtree(run_dir, ignore_errors=True)
    return result


# -- set-up --------------------------------------------------------------------

def _check_devices(run: Run, first: Sequence[Dict[str, Any]],
                   rehearse: bool) -> None:
    want = "cpu" if rehearse else "tpu"
    platforms = {st.get("runtime.jax_platform") for st in first}
    if platforms != {want}:
        raise NoAccelerator(f"servers compute on {sorted(map(str, platforms))},"
                            f" the cell needs {want!r}")
    kinds = {st.get("runtime.jax_device_kind") for st in first}
    count = max(int(st.get("runtime.jax_device_count", 0)) for st in first)
    homes = {d for st in first for d in st.get("runtime.jax_array_devices", [])}
    chips = int(run.workload["chips"])
    if not rehearse and (count < chips or len(homes) < len(first)):
        raise NoAccelerator(f"{count} devices, models on {sorted(homes)}: "
                            f"the cell needs {chips} chips, one per server")
    run.device = {"platform": want, "kind": sorted(map(str, kinds))[0],
                  "count": count}
    if not rehearse and run.device["kind"] not in run.peaks["by_device_kind"]:
        raise NoAccelerator(f"no peaks known for {run.device['kind']!r}")


def _warm_calls(run: Run, fleet: Fleet, name: str, seed: int,
                rows_sent: List[int]) -> None:
    """The traffic file's lone warm-up calls, in order, on every server at
    once: each compiles (or loads) the program of one shape."""
    calls = run.traffic["warmup"].get("calls", [])
    frames = []
    for k, c in enumerate(calls):
        rows = run.generator.make_rows(run.config["data"], seed, 500 + k,
                                       c["rows"])
        run.warm_rows.append((c["method"], rows))
        frames.append((c, run.engine.ENCODERS[c["method"]](name, rows)))

    def one(i: int) -> None:
        with Client(fleet.address(i), timeout=900.0) as cl:
            for c, frame in frames:
                cl.call_frame(frame)
                if c["method"] == run.engine.UPDATE:
                    rows_sent[i] += c["rows"]

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(fleet.servers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1000)
    fleet.check_alive()


def _warm_stream(run: Run, fleet: Fleet, gen: LoadGen) -> None:
    """Let the stream run until it has answered the traffic file's number
    of calls and no server has compiled anything for two samples."""
    w = run.traffic["warmup"]
    deadline = time.monotonic() + w.get("timeout_s", 900.0)
    last = None
    steady = 0
    while True:
        time.sleep(0.5)
        if gen.error is not None:
            raise RuntimeError(f"load generator died: {gen.error!r}")
        fleet.check_alive()
        if len(gen.records) < w["stream_calls"]:
            if time.monotonic() > deadline:
                raise RuntimeError("warm-up did not finish in time")
            continue
        compiles = [fleet.status(i).get("runtime.jax_compile_count")
                    for i in range(len(fleet.servers))]
        steady = steady + 1 if compiles == last else 0
        last = compiles
        if steady >= w.get("steady_samples", 2):
            return
        if time.monotonic() > deadline:
            raise RuntimeError("servers kept compiling through warm-up")


def _start_profiler(run: Run, fleet: Fleet, name: str) -> threading.Thread:
    t = run.traffic["trace"]
    secs = min(float(t["seconds"]), max(0.2, run.seconds / 3.0))
    start = run.t0 + run.seconds * float(t["start_fraction"])

    def capture(i: int) -> None:
        time.sleep(max(0.0, start - time.monotonic()))
        try:
            with Client(fleet.address(i), timeout=120.0) as c:
                doc = c.call("profile_device", name, secs)
            log(f"trace: server{i}: {json.dumps(doc)[:300]}")
        except (OSError, RuntimeError, ValueError) as e:
            log(f"trace: server{i}: capture failed: {e!r}")

    def all_servers() -> None:
        ths = [threading.Thread(target=capture, args=(i,), daemon=True)
               for i in range(len(fleet.servers))]
        for th in ths:
            th.start()
        for th in ths:
            th.join(120)

    th = threading.Thread(target=all_servers, daemon=True)
    th.start()
    return th


# -- after the window ------------------------------------------------------------

def _print_window(run: Run, gen: LoadGen) -> None:
    ta = [s for _g, s in run.turnaround]
    if ta:
        log(f"loadgen: turnaround mean {sum(ta) / len(ta) * 1e6:.1f} us, "
            f"max {max(ta) * 1e6:.1f} us over {len(ta)} resends; its thread "
            f"used {100.0 * run.loadgen_cpu_s / run.loadgen_wall_s:.1f}% "
            "of one core")
    per_server: Dict[int, int] = {}
    for gname, g in run.groups.items():
        recs = stats.in_window(run.records, run.t0, run.t1, gname)
        if g.get("rate_calls_per_s") and recs:
            late = [r[4] - r[3] for r in recs]
            log(f"loadgen: {gname} (open loop, {g['rate_calls_per_s']} calls/s):"
                f" {len(recs)} calls answered in the window, sent "
                f"{sum(late) / len(late) * 1e3:.3f} ms after they were due "
                f"on average, {max(late) * 1e3:.1f} ms at most")
        if recs:
            lat = stats.latencies_ms(recs)
            fifths = [stats.percentile(stats.latencies_ms(stats.in_window(
                recs, run.t0 + k * run.seconds / 5,
                run.t0 + (k + 1) * run.seconds / 5)), 95) for k in range(5)]
            log(f"latency, {gname}: " + ", ".join(
                f"p{q} {stats.percentile(lat, q):.1f}" for q in (50, 95, 99))
                + f", longest {max(lat):.1f} ms over {len(lat)} calls; p95 by "
                "fifths of the window: " + " ".join(
                    "-" if v is None else f"{v:.0f}" for v in fifths))
        if g["method"] == run.engine.UPDATE:
            per_server[g["server"]] = per_server.get(g["server"], 0) \
                + sum(r[7] for r in recs if r[6] and isinstance(r[7], int))
            gap = run.traffic["flush_gap_ms"] / 1e3
            hist = stats.flush_histogram(recs, g["rows_per_call"], gap)
            log(f"flushes seen by the generator, {gname}: "
                + ", ".join(f"{rows} rows x {n}" for rows, n in hist.items()))
            usual = max(hist, key=hist.get) if hist else 0
            odd = [(recs[c[0]][5] - run.t0, len(c) * g["rows_per_call"])
                   for c in stats.clusters([r[5] for r in recs], gap)
                   if len(c) * g["rows_per_call"] != usual]
            if odd:
                log(f"  other than {usual} rows, at seconds into the window: "
                    + ", ".join(f"+{t:.2f} ({rows})" for t, rows in odd[:12]))
    queue = f"microbatch.{run.engine.QUEUE[run.engine.UPDATE]}"
    for i, rows in sorted(per_server.items()):
        flushes = stats.counter_delta(run.status0[i], run.status1[i],
                                      f"{queue}.flush_count")
        items = stats.counter_delta(run.status0[i], run.status1[i],
                                    f"{queue}.item_count")
        log(f"rows acknowledged in the window, server{i}: {rows}; its "
            f"coalescer counted {flushes:.0f} flushes of "
            f"{items / flushes if flushes else 0.0:.1f} rows on average")


def _judge(run: Run, fleet: Fleet, gen: LoadGen, name: str, seed: int,
           dim: int, rows_sent: List[int], control: Optional[str] = None,
           check_seeds: Sequence[int] = ()) -> check.Compared:
    """``control`` (``run.py --control``, for the readings a limit is set
    from): also read what the reference in that lower precision would have
    scored in the program's place. ``check_seeds``: then drive the check
    plan again through the same servers for each of these seeds, so that a
    dozen readings share one set-up."""
    chk = run.traffic["check"]
    subject = check.Subject(run.config, run.engine, run.reference,
                            run.generator, dim)
    addresses = [fleet.address(i) for i in range(len(fleet.servers))]
    queue = f"microbatch.{run.engine.QUEUE[run.engine.UPDATE]}"

    def planned(plan_seed: int) -> Tuple[check.Plan, Dict[str, Any]]:
        plan = check.Plan(subject, chk["steps"], addresses, name, plan_seed,
                          fleet.status, log)
        t = time.monotonic()
        plan.run()
        t_plan = time.monotonic() - t
        t = time.monotonic()
        gap, wrong, detail = plan.score_gap("float32")
        reading = {"seed": plan_seed, "score_gap": gap, "wrong_shape": wrong,
                   "not_as_planned": plan.plan_mismatch,
                   "failed": plan.failed, "plan_s": round(t_plan, 2)}
        if control:
            reading["control"] = control
            reading["control_score_gap"] = plan.score_gap(
                "float32", plan.control_answers(control))[0]
        reading["reference_s"] = round(time.monotonic() - t, 2)
        log(f"check: plan {t_plan:.2f} s on the servers, reference "
            f"{reading['reference_s']:.2f} s; {detail}")
        return plan, reading

    plan, reading = planned(seed)
    for i, n in enumerate(plan.rows_acked):
        rows_sent[i] += n
    counted = [int(fleet.status(i).get(f"{queue}.item_count", 0))
               for i in range(len(fleet.servers))]
    acks_wrong, malformed = check.judge_window(
        run.records, run.groups, run.engine, run.config["data"])
    limits = chk["limits"]
    c = check.Compared()
    c.add("score_gap", reading["score_gap"], limits["score_gap"])
    c.add("answers_wrong_shape", reading["wrong_shape"] + malformed, 0)
    c.add("acks_wrong_count", acks_wrong + plan.acks_wrong, 0)
    c.add("rows_unaccounted",
          sum(abs(a - b) for a, b in zip(counted, rows_sent)), 0)
    c.add("flushes_not_as_planned", plan.plan_mismatch, 0)
    c.add("calls_failed", gen.failed + plan.failed, 0)
    if control:
        run.control = {"precision": control,
                       "score_gap": reading["control_score_gap"]}
    ws = chk.get("window_scores")
    if ws:
        t = time.monotonic()
        worst, details = 0.0, []
        for i in range(len(fleet.servers)):
            w = check.WindowScores(
                subject, run.records, run.groups, run.pool_rows,
                run.warm_rows, run.t0, run.t1, i, seed, int(ws["sample"]))
            g, detail = w.gap("float32")
            worst = max(worst, g)
            details.append(detail)
            if control:
                run.control["window_score_gap"] = w.gap(
                    "float32", control=control)[0]
        log(f"check: window answers against the reference, "
            f"{time.monotonic() - t:.2f} s; {details}")
        c.add("window_score_gap", worst, limits["window_score_gap"])
    run.plan_failed = plan.failed
    for extra in check_seeds:
        run.readings.append(planned(int(extra))[1])
        log("reading: " + json.dumps(run.readings[-1]))
    return c


def _reduce_trace(bdir: str, fleet: Fleet, run: Run
                  ) -> Optional[Dict[str, Any]]:
    """The trace reduction, in a child pinned to the CPU (the servers are
    stopped by now, so nothing holds a chip)."""
    run_dir = os.path.dirname(fleet.profile_dirs[0])
    holder = os.path.join(run_dir, "profiles")
    os.makedirs(holder, exist_ok=True)
    for i, d in enumerate(fleet.profile_dirs):
        if os.path.isdir(d):
            shutil.move(d, os.path.join(holder, f"server{i}"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    programs = list(run.config["programs"].values())
    proc = subprocess.run(
        [sys.executable, os.path.join(bdir, "harness", "trace_reduce.py"),
         holder] + programs, env=env, capture_output=True, text=True,
        timeout=200)
    if proc.returncode != 0:
        log(f"trace: reduction failed:\n{proc.stderr[-2000:]}")
        return None
    red = json.loads(proc.stdout.strip().splitlines()[-1])
    log(f"trace: planes {red['planes']}, device lines {red['lines']}")
    log("trace: programs run: " + ", ".join(
        f"{k} x {v['events']} = {v['seconds']:.4f} s"
        for k, v in sorted(red["modules"].items(),
                           key=lambda kv: -kv[1]["seconds"])))
    for d in red["devices"]:
        log(f"trace: {d['plane']}: busy {d['busy_s']:.4f} s of "
            f"{d['window_s']:.4f} s, idle share "
            f"{100.0 * (1 - d['busy_s'] / d['window_s']):.3f}%")
    return red


def _result(bdir: str, bench: Dict[str, Any], run: Run,
            compared: check.Compared, trace: bool, gen: LoadGen
            ) -> Dict[str, Any]:
    wname = run.workload["name"]
    kind = "per_layer" if trace else "end_to_end"
    readers = load_readers(os.path.join(bdir, kind))
    metrics: Dict[str, Any] = {}
    for m in bench[kind]:
        if "workloads" in m and wname not in m["workloads"]:
            continue
        value = readers[m["name"]].read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    in_window = run.window()
    device = dict(run.device,
                  memory_peak_bytes=max(run.bytes_in_use_max or [0]))
    result: Dict[str, Any] = {
        "correct": bool(compared.correct),
        "attempted": len(in_window) + gen.failed,
        "failed": int(gen.failed + run.plan_failed),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    if run.rehearsal:
        result["rehearsal"] = "cpu: no number here is a device number"
    if run.control:
        result["control"] = run.control
    if run.readings:
        result["readings"] = run.readings
    log("set-up phases (s from process start): "
        + ", ".join(f"{k}={v:.2f}" for k, v in run.phases.items())
        + f", window_start={run.setup_s:.2f}")
    for i, st in enumerate(run.status0):
        log(f"server{i} at the window's start: compiled "
            f"{st.get('runtime.jax_compile_count')} programs in "
            f"{st.get('runtime.jax_compile_ms')} ms, cache hits "
            f"{st.get('runtime.jax_cache_hits')} misses "
            f"{st.get('runtime.jax_cache_misses')}, device bytes in use "
            f"{st.get('runtime.jax_device_bytes_in_use')}")
    for line in compared.lines():
        log(line)
    result["compared"] = compared.as_dict()
    return result
