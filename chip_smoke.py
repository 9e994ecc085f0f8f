#!/usr/bin/env python3
"""Does the serving path start and answer correctly on the TPU?

    python chip_smoke.py                 # one chip (the contract below)
    python chip_smoke.py --chips 4       # one host, four chips
    python chip_smoke.py --rehearse-on-cpu [--chips 4]   # tiny, CPU, no proof

This process is a launcher and a client: it never initialises a JAX
backend, because a chip belongs to one process and the processes that
need it are the children, started through the entry points a user would
call (``python -m jubatus_tpu.server ...``) and run one after another.

One chip, in order:
  1. kernel parity  — the Pallas signature scans, compiled (not
     interpreted), equal the XLA reference bit for bit at every shape the
     row store feeds them;
  2. classifier     — an AROW server at D=2^24 takes >= 64k rows over
     concurrent connections through the native transport, native ingest
     and the coalescer, and classifies held-out rows;
  3. nearest_neighbor on config/nearest_neighbor/{lsh,minhash}.json —
     5,000 rows, then a stored row comes back first at distance 0.

Four chips:
  A. four replicas, one chip each (``tpu_process_env``), a coordinator and
     a proxy; disjoint labels, ``do_mix`` over the collective mixer, then
     a replica classifies a label only another replica trained;
  B. one ``--shard-devices 4`` server with the weight table in four shards.

Any leg that raises, any child that dies, any platform other than ``tpu``
is a non-zero exit and no result line. ``--rehearse-on-cpu`` is the only
way to run off the chip: sizes shrink, children get JAX_PLATFORMS=cpu, and
the result line says it was a rehearsal.

The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as the serving process reported it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from jubatus_tpu.client import (ClassifierClient, Datum,
                                NearestNeighborClient)
from jubatus_tpu.cmd import tpu_process_env
from jubatus_tpu.utils.runtime_telemetry import jax_backend_initialized

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
HOST = "127.0.0.1"
NAME = "smoke"
SEED = 21
GIB = 1 << 30
#: the whole run must fit the caller's 1200 s with room to report
DEADLINE_S = 1100.0

FULL = dict(dim=1 << 24, conns=8, calls_per_conn=17, rows_per_call=500,
            feats=64, vocab=2048, holdout=2000, nn_rows=5000, nn_queries=20,
            shard_calls_per_conn=4,
            parity_b=(1, 7, 256), parity_c=(64, 2048, 8192, 5000),
            parity_w=(2, 8), parity_h=64)
TINY = dict(dim=1 << 16, conns=4, calls_per_conn=5, rows_per_call=100,
            feats=16, vocab=64, holdout=200, nn_rows=300, nn_queries=5,
            shard_calls_per_conn=5,
            parity_b=(1, 7), parity_c=(64, 300), parity_w=(2,), parity_h=8)


class SmokeFailure(Exception):
    pass


def check(cond: Any, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- children ------------------------------------------------------------------

class Child:
    """One process this smoke started; stderr goes to a log it can show."""

    def __init__(self, tag: str, argv: List[str], env: Dict[str, str]) -> None:
        os.makedirs(LOG_DIR, exist_ok=True)
        self.tag = tag
        self.log_path = os.path.join(LOG_DIR, f"{tag}.stderr.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=subprocess.PIPE,
            stderr=self._log)

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def check_alive(self) -> None:
        rc = self.proc.poll()
        check(rc is None, f"{self.tag} died with exit code {rc}:\n"
                          f"{self.log_tail()}")

    def terminate_cleanly(self, timeout: float = 120.0) -> None:
        """SIGTERM; the child must exit 0 and have logged no traceback."""
        self.check_alive()
        self.proc.send_signal(signal.SIGTERM)
        self.wait_clean(timeout)

    def wait_clean(self, timeout: float = 120.0) -> None:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.tag} ignored SIGTERM for {timeout}s")
        check(rc == 0, f"{self.tag} exited {rc} after SIGTERM:\n"
                       f"{self.log_tail()}")
        check("Traceback" not in self.log_tail(1 << 20),
              f"{self.tag} logged a traceback:\n{self.log_tail()}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        if self.proc.stdout is not None:
            self.proc.stdout.close()
        self._log.close()


class Fleet:
    """Every child of a run, so that all of them are stopped on any exit."""

    def __init__(self, rehearse: bool) -> None:
        self.rehearse = rehearse
        self.children: List[Child] = []
        self.t0 = time.monotonic()

    def env(self, extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p])
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(extra or {})
        return env

    def spawn(self, tag: str, argv: List[str],
              extra_env: Optional[Dict[str, str]] = None) -> Child:
        child = Child(tag, argv, self.env(extra_env))
        self.children.append(child)
        return child

    def remaining(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        check(left > 0, f"out of time ({DEADLINE_S:.0f}s)")
        return left

    def stop_all(self) -> None:
        for c in self.children:
            c.kill()


def free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


def write_config(tag: str, conf: dict) -> str:
    os.makedirs(LOG_DIR, exist_ok=True)
    path = os.path.join(LOG_DIR, f"{tag}.config.json")
    with open(path, "w") as f:
        json.dump(conf, f)
    return path


def wait_for_status(fleet: Fleet, child: Child, client, timeout: float = 600.0):
    """Poll get_status until the server answers; its one status map."""
    deadline = time.monotonic() + min(timeout, fleet.remaining())
    while True:
        child.check_alive()
        try:
            return only(client.get_status())
        except Exception as e:  # noqa: BLE001 — not up yet; retried
            check(time.monotonic() < deadline,
                  f"{child.tag} did not answer get_status in {timeout:.0f}s "
                  f"({e!r}):\n{child.log_tail()}")
            time.sleep(0.5)


def only(status_map: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    check(len(status_map) == 1, f"expected one node, got {list(status_map)}")
    return next(iter(status_map.values()))


def settled_status(client) -> Dict[str, Any]:
    """get_status after the server's runtime sample (cached for 1 s) has
    had time to see the work just done."""
    time.sleep(1.2)
    return only(client.get_status())


def check_where_it_computes(st: Dict[str, Any], rehearse: bool,
                            tag: str) -> Dict[str, Any]:
    """The server's own account of its device, transport and parser."""
    want = "cpu" if rehearse else "tpu"
    check(st.get("runtime.jax_backend_initialized") is True,
          f"{tag}: server reports no jax backend")
    check(st.get("runtime.jax_platform") == want,
          f"{tag}: server computes on {st.get('runtime.jax_platform')!r}, "
          f"not {want!r}")
    check(st.get("runtime.jax_device_kind"), f"{tag}: no device kind")
    check(st.get("runtime.jax_device_count", 0) >= 1, f"{tag}: no devices")
    check(st.get("runtime.jax_array_devices"),
          f"{tag}: status names no device holding the model")
    check(st.get("rpc.transport") == "native",
          f"{tag}: serving on the {st.get('rpc.transport')!r} transport "
          "(native build failed?)")
    return {"platform": st["runtime.jax_platform"],
            "kind": st["runtime.jax_device_kind"],
            "count": st["runtime.jax_device_count"]}


def compile_report(st: Dict[str, Any]) -> Dict[str, Any]:
    return {"cache_dir": st.get("runtime.jax_compilation_cache_dir"),
            "compile_count": st.get("runtime.jax_compile_count"),
            "compile_seconds": round(
                st.get("runtime.jax_compile_ms", 0.0) / 1e3, 2),
            "cache_hits": st.get("runtime.jax_cache_hits"),
            "cache_misses": st.get("runtime.jax_cache_misses")}


# -- data ----------------------------------------------------------------------

def separable_rows(rng: np.random.Generator, n: int, size: dict,
                   w_true: np.ndarray) -> List[Tuple[str, Datum]]:
    """``n`` labelled datums of ``feats`` numeric features drawn from a
    ``vocab``-name universe; the label is the side of the hyperplane
    ``w_true`` the datum falls on, and rows too close to it are redrawn,
    so the two labels are linearly separable with a margin."""
    k, v = size["feats"], size["vocab"]
    margin = 0.25 * np.sqrt(k)
    out: List[Tuple[str, Datum]] = []
    while len(out) < n:
        m = 2 * (n - len(out)) + 8
        keys = np.argsort(rng.random((m, v)), axis=1)[:, :k]
        vals = rng.normal(size=(m, k)).astype(np.float32)
        score = (w_true[keys] * vals).sum(axis=1)
        for i in np.nonzero(np.abs(score) >= margin)[0][: n - len(out)]:
            d = Datum({f"f{j}": float(x) for j, x in zip(keys[i], vals[i])})
            out.append(("pos" if score[i] > 0 else "neg", d))
    return out


def top_label(scored: List[Any]) -> str:
    return max(scored, key=lambda e: e[1])[0]


# -- leg: classifier at full width -----------------------------------------------

def classifier_config(dim: int) -> dict:
    return {"method": "AROW",
            "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}],
                          "hash_max_size": dim}}


def drive_classifier(fleet: Fleet, child: Child, port: int, size: dict,
                     calls_per_conn: int, tag: str) -> Dict[str, Any]:
    """Concurrent train, then held-out classify, against one server."""
    rng = np.random.default_rng(SEED)
    w_true = rng.normal(size=size["vocab"]).astype(np.float32)
    conns, per_call = size["conns"], size["rows_per_call"]
    batches = [[separable_rows(rng, per_call, size, w_true)
                for _ in range(calls_per_conn)] for _ in range(conns)]
    holdout = separable_rows(rng, size["holdout"], size, w_true)
    rows_sent = conns * calls_per_conn * per_call
    acked: List[int] = []
    errors: List[str] = []

    def writer(my_batches) -> None:
        try:
            with ClassifierClient(HOST, port, NAME, timeout=600) as c:
                for batch in my_batches:
                    acked.append(int(c.train(batch)))
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            errors.append(repr(e))

    t0 = time.monotonic()
    threads = [threading.Thread(target=writer, args=(b,)) for b in batches]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=fleet.remaining())
        check(not t.is_alive(), f"{tag}: a train connection hung")
    child.check_alive()
    check(not errors, f"{tag}: train calls failed: {errors[:3]}")
    check(len(acked) == conns * calls_per_conn and sum(acked) == rows_sent,
          f"{tag}: {sum(acked)} rows acknowledged of {rows_sent} sent")
    train_s = time.monotonic() - t0

    hits = 0
    with ClassifierClient(HOST, port, NAME, timeout=600) as c:
        for lo in range(0, len(holdout), per_call):
            part = holdout[lo: lo + per_call]
            res = c.classify([d for _, d in part])
            check(len(res) == len(part), f"{tag}: classify dropped rows")
            for (label, _), scored in zip(part, res):
                check(len(scored) == 2 and all(
                    np.isfinite(s) for _, s in scored),
                    f"{tag}: classify scores {scored!r}")
                hits += top_label(scored) == label
        st = settled_status(c)
    accuracy = hits / len(holdout)
    check(accuracy >= 0.9, f"{tag}: held-out accuracy {accuracy:.3f} < 0.9")
    check(st["update_count"] == rows_sent,
          f"{tag}: update_count {st['update_count']} != {rows_sent} rows sent")
    check(st.get("ingest.native") is True,
          f"{tag}: native ingest did not register")
    flushes = st["microbatch.train_raw.flush_count"]
    items = st["microbatch.train_raw.item_count"]
    check(items == rows_sent, f"{tag}: coalescer saw {items} rows")
    check(flushes * per_call < items,
          f"{tag}: no flush coalesced more than one request "
          f"({flushes} flushes for {items} rows)")
    return {"rows_trained": rows_sent, "train_wall_s": round(train_s, 2),
            "holdout_rows": len(holdout), "accuracy": round(accuracy, 4),
            "train_flushes": flushes,
            "avg_flush_rows": round(items / flushes, 1), "status": st}


def leg_classifier(fleet: Fleet, size: dict) -> Dict[str, Any]:
    port = free_port()
    cfg = write_config("classifier", classifier_config(size["dim"]))
    child = fleet.spawn("classifier", [
        sys.executable, "-m", "jubatus_tpu.server", "classifier",
        "-f", cfg, "-p", str(port), "-c", str(2 * size["conns"]),
        "-t", "600"])
    with ClassifierClient(HOST, port, NAME, timeout=30) as c:
        wait_for_status(fleet, child, c)
    out = drive_classifier(fleet, child, port, size, size["calls_per_conn"],
                           "classifier")
    st = out.pop("status")
    device = check_where_it_computes(st, fleet.rehearse, "classifier")
    in_use = st.get("runtime.jax_device_bytes_in_use")
    if not fleet.rehearse:
        check(in_use is not None and in_use >= 2 * GIB,
              f"classifier: {in_use} device bytes in use, expected >= 2 GiB "
              f"of tables at D={size['dim']}")
    child.terminate_cleanly()
    out.update(dim=size["dim"], device=device, device_bytes_in_use=in_use,
               model_devices=st["runtime.jax_array_devices"],
               transport=st["rpc.transport"],
               ingest_native=st["ingest.native"], **compile_report(st))
    return out


# -- leg: nearest_neighbor on the shipped configs --------------------------------

def leg_nearest_neighbor(fleet: Fleet, size: dict, method: str) -> Dict[str, Any]:
    tag = f"nn_{method}"
    port = free_port()
    cfg = os.path.join(HERE, "config", "nearest_neighbor", f"{method}.json")
    child = fleet.spawn(tag, [
        sys.executable, "-m", "jubatus_tpu.server", "nearest_neighbor",
        "-f", cfg, "-p", str(port), "-t", "600"])
    rng = np.random.default_rng(SEED)
    n = size["nn_rows"]
    # minhash weights must be positive; lsh takes either sign
    rows = [Datum({f"k{j}": float(x) for j, x in zip(
        rng.choice(1000, 16, replace=False), rng.uniform(0.1, 1.0, 16))})
        for _ in range(n)]
    with NearestNeighborClient(HOST, port, NAME, timeout=600) as c:
        wait_for_status(fleet, child, c)
        for i, d in enumerate(rows):
            check(c.set_row(f"row{i}", d), f"{tag}: set_row refused")
        for i in rng.choice(n, size["nn_queries"], replace=False):
            rid = f"row{i}"
            near = c.neighbor_row_from_id(rid, 4)
            check(near and near[0][1] == 0.0 and rid in
                  [r for r, dist in near if dist == 0.0],
                  f"{tag}: neighbor_row_from_id({rid}) -> {near!r}")
            sim = c.similar_row_from_datum(rows[i], 4)
            check(sim and sim[0][1] == 1.0 and rid in
                  [r for r, s in sim if s == 1.0],
                  f"{tag}: similar_row_from_datum({rid}) -> {sim!r}")
        st = settled_status(c)
    check(st["update_count"] == n, f"{tag}: update_count {st['update_count']}")
    device = check_where_it_computes(st, fleet.rehearse, tag)
    child.terminate_cleanly()
    return {"rows": n, "queries": 2 * size["nn_queries"], "device": device,
            **compile_report(st)}


# -- leg: Pallas kernels against the XLA reference (child process) ---------------

def kernel_parity_child(size: dict, rehearse: bool) -> int:
    """Runs in its own process (it is the one that holds the chip):
    ``pallas_kernels.*_distances_batch`` == the XLA formulation, exactly."""
    from jubatus_tpu.utils.compile_cache import configure

    configure()
    import jax
    import jax.numpy as jnp

    from jubatus_tpu.ops import knn, pallas_kernels

    dev = jax.devices()[0]
    if not rehearse:
        check(dev.platform == "tpu", f"parity child on {dev.platform!r}")
        check(pallas_kernels.enabled(), "pallas kernels are not enabled")
        check(not pallas_kernels._interpret(), "pallas would be interpreted")
    rng = np.random.default_rng(SEED)
    cases = 0
    for c in size["parity_c"]:
        for b in size["parity_b"]:
            for mode, widths in (("hamming", size["parity_w"]),
                                 ("minhash", (size["parity_h"],))):
                for w in widths:
                    # a narrow value range, so that lanes do collide
                    hi = 1 << 32 if mode == "hamming" else 4
                    rows = rng.integers(0, hi, size=(c, w), dtype=np.uint32)
                    q = rng.integers(0, hi, size=(b, w), dtype=np.uint32)
                    q[0] = rows[c - 1]  # an exact match in the last tile
                    rows, q = jnp.asarray(rows), jnp.asarray(q)
                    if mode == "hamming":
                        got = pallas_kernels.hamming_distances_batch(
                            q, rows, hash_num=32 * w)
                        ref = knn._hamming_distances_batch_xla(
                            q, rows, hash_num=32 * w)
                    else:
                        got = pallas_kernels.minhash_distances_batch(q, rows)
                        ref = knn._minhash_distances_batch_xla(q, rows)
                    got, ref = np.asarray(got), np.asarray(ref)
                    check(got.shape == (b, c) and np.array_equal(got, ref),
                          f"{mode} B={b} C={c} W={w}: pallas != xla "
                          f"(max |d| {np.abs(got - ref).max()})")
                    check(got[0, c - 1] == 0.0, f"{mode}: match not at 0")
                    cases += 1
    print(json.dumps({"cases": cases, "platform": dev.platform,
                      "kind": dev.device_kind,
                      "interpreted": pallas_kernels._interpret()}))
    return 0


def leg_kernel_parity(fleet: Fleet) -> Dict[str, Any]:
    argv = [sys.executable, os.path.abspath(__file__), "--kernel-parity-child"]
    if fleet.rehearse:
        argv.append("--rehearse-on-cpu")
    child = fleet.spawn("kernel_parity", argv)
    try:
        out, _ = child.proc.communicate(timeout=fleet.remaining())
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernel parity child ran out of time")
    check(child.proc.returncode == 0,
          f"kernel parity failed ({child.proc.returncode}):\n"
          f"{child.log_tail()}")
    doc = json.loads(out.decode().strip().splitlines()[-1])
    if not fleet.rehearse:
        check(doc["platform"] == "tpu" and not doc["interpreted"],
              f"kernel parity ran as {doc!r}")
    return doc


# -- four chips ------------------------------------------------------------------

def leg_replicas(fleet: Fleet, size: dict, n: int = 4) -> Dict[str, Any]:
    """n replicas, one chip each, mixing over the collective mixer."""
    coord_port, proxy_port, jax_port = free_port(), free_port(), free_port()
    tpu_ports = [free_port() for _ in range(n)]
    z = f"tcp://{HOST}:{coord_port}"
    cfg = write_config("replica", classifier_config(size["dim"]))
    coordd = fleet.spawn("coordd", [
        sys.executable, "-m", "jubatus_tpu.coord.server",
        "-p", str(coord_port)])
    time.sleep(1.0)
    coordd.check_alive()
    proxy = fleet.spawn("proxy", [
        sys.executable, "-m", "jubatus_tpu.server.proxy", "classifier",
        "-z", z, "-p", str(proxy_port), "-t", "600"])
    ports = [free_port() for _ in range(n)]
    servers = [fleet.spawn(f"replica{i}", [
        sys.executable, "-m", "jubatus_tpu.server", "classifier",
        "-f", cfg, "-z", z, "-n", NAME, "-p", str(ports[i]),
        "--mixer", "collective_mixer", "--jax-processes", str(n),
        "--jax-process-id", str(i),
        "--jax-coordinator", f"{HOST}:{jax_port}",
        # only the do_mix below mixes: no timer, no count trigger
        "-s", "100000", "-i", "1000000000",
        "--interconnect-timeout", "300", "-t", "600"],
        extra_env=tpu_process_env(i, tpu_ports)) for i in range(n)]
    clients = [ClassifierClient(HOST, ports[i], NAME, timeout=600)
               for i in range(n)]
    try:
        for i in range(n):
            wait_for_status(fleet, servers[i], clients[i])
        for i, c in enumerate(clients):
            # replica i alone learns labels a<i> / b<i>
            batch = []
            for r in range(40):
                s = 1.0 + 0.01 * r
                batch.append((f"a{i}", Datum({f"x{i}": s, f"y{i}": -s})))
                batch.append((f"b{i}", Datum({f"x{i}": -s, f"y{i}": s})))
            check(int(c.train(batch)) == len(batch), f"replica{i}: train")
        t0 = time.monotonic()
        check(clients[0].do_mix() is True, "do_mix returned false")
        mix_s = time.monotonic() - t0
        for child in servers:
            child.check_alive()

        time.sleep(1.2)  # the runtime sample is cached for 1 s
        sts = [only(c.get_status()) for c in clients]
        homes = []
        for i, st in enumerate(sts):
            device = check_where_it_computes(st, fleet.rehearse,
                                             f"replica{i}")
            check(st.get("mixer.fallback_rounds") == 0,
                  f"replica{i}: {st.get('mixer.fallback_rounds')} rounds "
                  "fell back to the RPC mix")
            check(st.get("mixer.mix_caps_world") == n
                  and st.get("mixer.mix_caps_distributed") is True,
                  f"replica{i}: jax world {st.get('mixer.mix_caps_world')}")
            check(st.get("mixer.mix_caps_backend") ==
                  ("cpu" if fleet.rehearse else "tpu"),
                  f"replica{i}: mix backend {st.get('mixer.mix_caps_backend')}")
            check(len(st["runtime.jax_array_devices"]) == 1,
                  f"replica{i}: model on {st['runtime.jax_array_devices']}")
            homes.append(st["runtime.jax_array_devices"][0])
        check(len(set(homes)) == n,
              f"{n} replicas on {len(set(homes))} devices: {homes}")
        check(sts[0].get("mixer.collective_rounds", 0) >= 1,
              "master ran no collective round")
        hist = clients[0].client.call("get_mix_history", NAME)
        rounds = [r for r in hist if r.get("mode") == "collective"
                  and r.get("ok")]
        check(rounds and all(k in rounds[-1].get("phases", {}) for k in
                             ("ship_ms", "reduce_ms", "readback_ms")),
              f"no collective record with phases in {hist!r}")

        # every replica now knows every label, through the proxy too
        for i, c in enumerate(clients):
            j = (i + 1) % n  # a label only replica j trained
            (scored,) = c.classify([Datum({f"x{j}": 1.0, f"y{j}": -1.0})])
            check(len(scored) == 2 * n and top_label(scored) == f"a{j}",
                  f"replica{i} on replica{j}'s label: {scored!r}")
        with ClassifierClient(HOST, proxy_port, NAME, timeout=600) as pc:
            (scored,) = pc.classify([Datum({"x2": -1.0, "y2": 1.0})])
            check(top_label(scored) == "b2", f"via proxy: {scored!r}")
            check(len(pc.get_status()) == n, "proxy does not see the fleet")
            pst = only(pc.get_proxy_status())
        check(pst.get("runtime.jax_backend_initialized") is False,
              "the proxy initialised a jax backend")
        check(pst.get("rpc.transport") == "native", "proxy transport")
    finally:
        for c in clients:
            c.close()
    # one jax world: its members leave together (the runtime's shutdown
    # is a barrier), so signal all of them before waiting for any
    for child in servers:
        child.check_alive()
        child.proc.send_signal(signal.SIGTERM)
    for child in servers:
        child.wait_clean()
    proxy.terminate_cleanly()
    coordd.terminate_cleanly()
    phases = rounds[-1]["phases"]
    return {"replicas": n, "dim": size["dim"], "devices": homes,
            "device": device,
            "collective_rounds": sts[0]["mixer.collective_rounds"],
            "fallback_rounds": [s["mixer.fallback_rounds"] for s in sts],
            "do_mix_wall_s": round(mix_s, 2),
            "mix_phases_ms": {k: phases[k] for k in
                              ("ship_ms", "reduce_ms", "readback_ms")},
            "mix_topology": sts[0].get("mixer.mix_topology"),
            "proxy_backend_initialized": False, **compile_report(sts[0])}


def leg_sharded(fleet: Fleet, size: dict, n: int = 4) -> Dict[str, Any]:
    """One process, n chips: --shard-devices n."""
    port = free_port()
    cfg = write_config("sharded", classifier_config(size["dim"]))
    extra = ({"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}"}
             if fleet.rehearse else {})
    child = fleet.spawn("sharded", [
        sys.executable, "-m", "jubatus_tpu.server", "classifier",
        "-f", cfg, "-p", str(port), "--shard-devices", str(n),
        "-c", str(2 * size["conns"]), "-t", "600"], extra_env=extra)
    with ClassifierClient(HOST, port, NAME, timeout=30) as c:
        wait_for_status(fleet, child, c)
    out = drive_classifier(fleet, child, port, size,
                           size["shard_calls_per_conn"], "sharded")
    st = out.pop("status")
    device = check_where_it_computes(st, fleet.rehearse, "sharded")
    devices = st.get("driver.shard.devices") or []
    check(st.get("driver.shard.count") == n and len(set(devices)) == n,
          f"sharded: weight table on {devices!r}")
    check(st["driver.shard.shard_shape"][1] * n == size["dim"],
          f"sharded: shard shape {st['driver.shard.shard_shape']}")
    # a device's bytes are the fullest chip's: one shard of the tables
    # and a flush's inputs, never the four chips' sum
    in_use = st.get("runtime.jax_device_bytes_in_use")
    if not fleet.rehearse:
        check(all("TPU" in d.upper() for d in devices),
              f"sharded: shards on {devices!r}")
        per_shard = st["driver.shard.bytes_per_shard"]
        check(in_use is not None and per_shard <= in_use < 2 * per_shard,
              f"sharded: {in_use} device bytes in use on the fullest chip, "
              f"{per_shard} of tables a shard")
    child.terminate_cleanly()
    out.update(dim=size["dim"], device=device, shard_devices=devices,
               shard_shape=st["driver.shard.shard_shape"],
               device_bytes_in_use=in_use,
               devices_bytes_in_use_total=st.get(
                   "runtime.jax_devices_bytes_in_use_total"),
               **compile_report(st))
    return out


# -- main ------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tiny sizes on the CPU; proves nothing about the "
                         "chip and says so in its result")
    ap.add_argument("--kernel-parity-child", action="store_true",
                    help=argparse.SUPPRESS)
    ns = ap.parse_args(argv)
    size = TINY if ns.rehearse_on_cpu else FULL
    if ns.kernel_parity_child:
        return kernel_parity_child(size, ns.rehearse_on_cpu)

    check("JUBATUS_TPU_PALLAS" not in os.environ
          and os.environ.get("JUBATUS_TPU_NATIVE_RPC", "") == ""
          and os.environ.get("JUBATUS_TPU_NATIVE_INGEST", "") == "",
          "unset JUBATUS_TPU_PALLAS / _NATIVE_RPC / _NATIVE_INGEST: the "
          "smoke runs the defaults")
    fleet = Fleet(ns.rehearse_on_cpu)
    report: Dict[str, Any] = {"chips": ns.chips}
    try:
        if ns.chips == 1:
            report["kernel_parity"] = leg_kernel_parity(fleet)
            report["classifier"] = leg_classifier(fleet, size)
            for method in ("lsh", "minhash"):
                report[f"nn_{method}"] = leg_nearest_neighbor(
                    fleet, size, method)
            device = report["classifier"]["device"]
        else:
            report["replicas"] = leg_replicas(fleet, size)
            report["sharded"] = leg_sharded(fleet, size)
            device = report["sharded"]["device"]
        check(not jax_backend_initialized(),
              "the smoke's own process initialised a jax backend")
    finally:
        fleet.stop_all()
    report["wall_s"] = round(time.monotonic() - fleet.t0, 1)
    print(json.dumps(report, sort_keys=True))
    result: Dict[str, Any] = {"ok": True}
    if ns.rehearse_on_cpu:
        result["rehearsal"] = "cpu: proves nothing about the chip"
    result["device"] = device
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
