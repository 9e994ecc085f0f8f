"""Mix-plane benchmark: mix rounds at Criteo scale and at the BASELINE.md
north star (mix round <= 1 s at D=2^24), plus a REAL multi-process
collective round.

The reference logs per-round time + bytes (linear_mixer.cpp:553-558); this
does the same for the TPU mix plane on three paths:

- ``device_round`` at D=2^20 AND D=2^24: the single-host production path
  (LocalMixGroup shape): per-replica host diffs [L, D] f32 ->
  host-to-device -> jitted reduce + apply into the master weights ->
  block_until_ready. Runs on the device bench.py runs on (the chip).
  Transfers use uncommitted ``jnp.asarray``, the shape the serving path
  feeds.
- ``allreduce8``: the multi-replica collective path (`allreduce_diffs`,
  psum over the mesh's replica axis), executed on an 8-device virtual CPU
  mesh in a subprocess — the same path `dryrun_multichip` validates. Wall
  time on virtual CPU devices is NOT an ICI number; it proves the
  collective compiles + executes and bounds the host-side orchestration.
- ``collective_nproc4``: a FULL production collective_mixer round across
  4 jax.distributed processes (prepare RPC fan-out, schema sync, GO via
  the coordinator, psum_pytree, acks) — the complete orchestration stack,
  timed on the master. Virtual CPU world: the number bounds protocol +
  host cost, not interconnect bandwidth (labeled as such).

Every path reports f32 and, where applicable, the compressed wire
variants — bf16 (half the bytes) and block-quantized int8 (~4x fewer
bytes, --mix-compress int8) — plus a multi-round drift probe proving the
int8 error-feedback residual keeps averaged weights unbiased
(``collective_round_drift_vs_f32`` vs the stateless ``_noef`` control).

Usage: python bench_mix.py        — prints one JSON dict of mix metrics.
Also importable: bench.py folds `collect(...)` into its "extra" field.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

L = 2
N_REPLICAS = 2          # device_round: reference's smallest real cluster
TRIALS = 5
NORTH_STAR_BITS = 24    # BASELINE.md: Criteo-shaped 2^24 model, round <= 1 s


def _median(xs):
    return float(np.median(np.asarray(xs)))


def scrub_child_env(env: dict) -> dict:
    """The env of a child that must stay off the chip (load generators,
    CPU worlds): the chip belongs to one process, the one being measured.
    Also puts the repo on PYTHONPATH for ``python -c`` children."""
    env = dict(env)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.abspath(__file__))
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if repo not in parts:
        parts.insert(0, repo)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    return env


def device_round(dim_bits: int, dev=None, trials: int = TRIALS,
                 tag: str = "") -> dict:
    """One full mix round, single-device reduce (replicas co-hosted).
    ``dev`` pins the default device for the round (arrays stay
    uncommitted)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    ctx = jax.default_device(dev) if dev is not None else \
        contextlib.nullcontext()
    with ctx:
        return _device_round_impl(dim_bits, trials, tag)


def _device_round_impl(dim_bits: int, trials: int, tag: str) -> dict:
    import jax
    import jax.numpy as jnp

    d = 1 << dim_bits
    rng = np.random.default_rng(0)
    diffs_host = [rng.normal(size=(L, d)).astype(np.float32)
                  for _ in range(N_REPLICAS)]
    master = jnp.zeros((L, d), jnp.float32)

    @jax.jit
    def reduce_apply(master, stacked):
        return master + jnp.sum(stacked, axis=0)

    @jax.jit
    def reduce_apply_bf16(master, stacked):
        # wire-compressed variant: replicas ship bf16 diffs (half the
        # host->device and inter-replica bytes); master stays f32
        return master + jnp.sum(stacked.astype(jnp.float32), axis=0)

    out = {}
    suffix = tag or f"d{dim_bits}"
    for name, fn, cast in (("f32", reduce_apply, np.float32),
                           ("bf16", reduce_apply_bf16, None)):
        if cast is None:
            import ml_dtypes

            ship = [x.astype(ml_dtypes.bfloat16) for x in diffs_host]
        else:
            ship = diffs_host
        # warmup (compile)
        stacked = jnp.asarray(np.stack(ship))
        master = fn(master, stacked)
        master.block_until_ready()
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            stacked = jnp.asarray(np.stack(ship))  # get_diff arrival
            master = fn(master, stacked)
            master.block_until_ready()             # put_diff barrier
            times.append(time.perf_counter() - t0)
            del stacked
        bytes_moved = sum(x.nbytes for x in ship)
        out[f"mix_round_ms_{suffix}_{name}"] = round(_median(times) * 1e3, 2)
        out[f"mix_round_mb_{suffix}_{name}"] = round(bytes_moved / 2**20, 2)
    return out


def allreduce8() -> dict:
    """allreduce_diffs on an 8-replica virtual CPU mesh (subprocess)."""
    import jax
    import jax.numpy as jnp

    from jubatus_tpu.parallel.mesh import replica_mesh
    from jubatus_tpu.parallel.mix import _psum_stacked
    from jax.sharding import NamedSharding, PartitionSpec as P

    D = 1 << 20
    mesh = replica_mesh(8)
    rng = np.random.default_rng(0)
    stacked_host = {"w": rng.normal(size=(8, L, D)).astype(np.float32)}
    sharding = NamedSharding(mesh, P("replica"))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.asarray(x), sharding), stacked_host)

    out = {}
    for name, compress in (("f32", False), ("bf16", True)):
        total = _psum_stacked(stacked, mesh=mesh, axis="replica",
                              compress=compress)
        jax.block_until_ready(total)
        times = []
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            total = _psum_stacked(stacked, mesh=mesh, axis="replica",
                                  compress=compress)
            jax.block_until_ready(total)
            times.append(time.perf_counter() - t0)
        # ring allreduce wire bytes per replica: 2*(n-1)/n of the payload
        payload = L * D * (2 if compress else 4)
        out[f"allreduce8_ms_{name}"] = round(_median(times) * 1e3, 2)
        out[f"allreduce8_wire_mb_per_replica_{name}"] = round(
            payload * 2 * 7 / 8 / 2**20, 2)
    return out


def _allreduce8_subprocess() -> dict:
    """Run allreduce8 with 8 virtual CPU devices regardless of parent env."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = scrub_child_env(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    prog = (
        "import json, bench_mix\n"
        "print('MIXBENCH=' + json.dumps(bench_mix.allreduce8()))\n"
    )
    proc = subprocess.run([sys.executable, "-c", prog], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=600)
    for line in proc.stdout.splitlines():
        if line.startswith("MIXBENCH="):
            return json.loads(line[len("MIXBENCH="):])
    return {"allreduce8_error": (proc.stderr or proc.stdout)[-300:]}


_COLLECTIVE_CHILD = r"""
import os, sys, time, json
import jax
pid = int(sys.argv[1]); n = int(sys.argv[2])
jax_port, coord_dir = sys.argv[3], sys.argv[4]
dim_bits = int(sys.argv[5]) if len(sys.argv) > 5 else 0
mode = sys.argv[6] if len(sys.argv) > 6 else "off"  # off|bf16|int8
# CPU worlds need the gloo collectives backend or every psum raises
# ("Multiprocess computations aren't implemented on the CPU backend")
from jubatus_tpu.parallel.multihost import enable_cpu_collectives
enable_cpu_collectives()
jax.distributed.initialize(f"127.0.0.1:{jax_port}", num_processes=n,
                           process_id=pid)
from jubatus_tpu.client import ClassifierClient, Datum
from jubatus_tpu.coord import membership
from jubatus_tpu.server import EngineServer
from jubatus_tpu.server.args import ServerArgs

# dim_bits > 0: the north-star-scale round — AROW (w + sigma diffs, the
# reference's confidence-weighted shape) at hash_max_size-pinned dim
if dim_bits:
    CONF = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}],
                          "hash_max_size": 1 << dim_bits}}
else:
    CONF = {"method": "PA", "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
args = ServerArgs(engine="classifier", coordinator=coord_dir, name="mb",
                  listen_addr="127.0.0.1", mixer="collective_mixer",
                  interval_sec=1e9, interval_count=1 << 30,
                  mix_compress=mode,
                  # north-star payloads (256 MB diffs) need a mixer-plane
                  # timeout matched to the transfer, like the reference's
                  # --interconnect_timeout knob for big models
                  interconnect_timeout=180.0 if dim_bits else 10.0,
                  timeout=180.0 if dim_bits else 10.0)
srv = EngineServer("classifier", CONF, args)
srv.start(0)
c = ClassifierClient("127.0.0.1", srv.args.rpc_port, "mb", timeout=300)
for _ in range(4):
    c.train([["pos", Datum({f"x{pid}": 1.0})],
             ["neg", Datum({f"x{pid}": -1.0})]])
# budget starts AFTER training: at north-star dims the d2^24 train
# compiles eat minutes of one time-sliced core, and a peer whose wait
# expires calls srv.stop() — tearing its listener down right under the
# master's mix fan-out (connection refused on every peer). The d24
# budget matches the parent's 1200 s timeout: a peer deadline SHORTER
# than the parent's lets a slow master outlive its peers and fan out
# into torn-down listeners instead of timing out cleanly at the parent
deadline = time.time() + (120 if not dim_bits else 1800)
while time.time() < deadline:
    if len(membership.get_all_nodes(srv.coord, "classifier", "mb")) == n:
        break
    time.sleep(0.2)
# the d24 world measures f32, bf16 AND int8 back to back in ONE world
# (flip compress in place between rounds — the prepare signature
# re-reads it, so all members flipping keeps the cluster matched); a
# second world boot would pay membership + d24 train compiles twice
variants = ["bf16", "int8"] if (dim_bits and mode == "off") else []
if pid == 0:
    time.sleep(1.5 if not dim_bits else 5.0)  # peers finish training
    def warmed_round():
        # warmup until the COLLECTIVE path engages (compiles the psum):
        # big models boot slowly on a time-sliced host and a transient
        # prepare failure routes one round to the RPC fallback — retry
        for attempt in range(4):
            out = srv.mixer.mix_now()
            if out and out.get("collective"):
                break
            print(f"warmup attempt {attempt}: {out!r}", flush=True)
            time.sleep(3.0)
        assert out and out.get("collective"), out
        # registry hygiene: drop the warmup rounds (compile-heavy) so the
        # mix.round histogram embedded below covers steady state only
        srv.rpc.trace.reset()
        # median of 3 measured rounds: the round is dominated by the
        # device-queue drain at the chunk-0 barrier on a time-sliced
        # host, which is noisy run to run — one sample flips mode
        # comparisons, three stabilize them
        times = []
        for _ in range(3 if dim_bits else 1):
            t0 = time.perf_counter()
            out = srv.mixer.mix_now()      # measured round
            times.append((time.perf_counter() - t0) * 1e3)
            assert out and out.get("collective"), out
        times.sort()
        return times[len(times) // 2]
    rec = {}
    plat = jax.devices()[0].platform
    def measure(tag):
        # per-phase breakdown of the measured round (VERDICT r4 #5):
        # makes the wire-bandwidth claim arithmetic from measured terms
        # instead of an assertion — cast (~0, on-device by design), ship
        # (host->device + wire prep), reduce (wire+fold as ONE fused
        # collective), readback, plus the wire bytes and quant mode the
        # flight recorder stamps per round
        ms = warmed_round()
        rec[f"collective_round_ms_nproc{n}{tag}"] = round(ms, 2)
        rec[f"collective_round{tag}_platform"] = plat
        phases = dict(getattr(srv.mixer, "last_phases", {}))
        for k, v in phases.items():
            rec[f"collective_phase_{k}{tag}"] = v
        if "wire_mb" in phases:
            rec[f"collective_wire_mb_per_round{tag}"] = phases["wire_mb"]
        # steady-state mix.round quantiles from the span histograms
        # (warmup rounds were reset away inside warmed_round)
        tr = srv.rpc.trace.trace_status()
        for q in ("p50_ms", "p99_ms", "max_ms"):
            k = f"trace.mix.round.{q}"
            if k in tr:
                rec[f"collective_mix_round_{q}{tag}"] = tr[k]
    tag = (f"_d{dim_bits}" if dim_bits else "") + \
        (f"_{mode}" if mode != "off" else "")
    measure(tag)
    diffs = {k: m.get_diff() for k, m in srv.driver.get_mixables().items()}
    import numpy as np
    nbytes = 0
    for d in diffs.values():
        leaves, _ = jax.tree_util.tree_flatten(d)
        nbytes += sum(np.asarray(x).nbytes for x in leaves)
    rec[f"collective_round{tag}_payload_mb_per_replica"] = \
        round(nbytes / 2**20, 2)
    rec[f"collective_round{tag}_note"] = (
        f"{n} jax.distributed {plat} processes; orchestration+psum "
        "cost, not interconnect bandwidth")
    flight = srv.mixer.flight.snapshot(last=1)
    if flight:
        rec[f"collective_flight_last{tag}"] = flight[-1]
    for v in variants:
        srv.mixer.compress = v
        open(coord_dir.rstrip("/") + f".flip_{v}", "w").close()
        fdeadline = time.time() + 120
        while time.time() < fdeadline:
            if all(os.path.exists(f"{coord_dir.rstrip('/')}.flipped_{v}_{p}")
                   for p in range(1, n)):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"peers never acked the {v} flip")
        measure(f"_d{dim_bits}_{v}")
    print("COLLECTIVE=" + json.dumps(rec), flush=True)
    # explicit completion marker (SIBLING of the coordinator dir — the
    # file coordinator owns everything inside): peers must NOT key off
    # model_version — failed warmup attempts still run RPC-fallback
    # rounds that bump it, and a peer leaving early tears its listener
    # down under the master's next fan-out
    open(coord_dir.rstrip("/") + ".done", "w").close()
else:
    done = coord_dir.rstrip("/") + ".done"
    pending = list(variants)
    while time.time() < deadline:
        if os.path.exists(done):
            break
        if pending and os.path.exists(
                f"{coord_dir.rstrip('/')}.flip_{pending[0]}"):
            v = pending.pop(0)
            srv.mixer.compress = v
            open(f"{coord_dir.rstrip('/')}.flipped_{v}_{pid}", "w").close()
        time.sleep(0.2)
c.close()
srv.stop()
print(f"CHILD-{pid}-DONE", flush=True)
"""


def run_jax_world(child_src: str, n: int, timeout: float = 300.0,
                  extra_args: tuple = ()):
    """Spawn ``n`` jax.distributed CPU child processes (argv: pid, n,
    jax_port, coord_dir, *extra); returns (outputs, returncodes).
    Shared by this bench and tests/test_collective_mixer.py — one
    harness owns the port pick, env scrub, CONCURRENT pipe draining
    (a child blocked writing into a full pipe while the parent reads
    siblings sequentially would deadlock a collective), kill-and-reap
    on timeout, and coordinator-dir cleanup."""
    import shutil
    import tempfile
    import threading

    repo = os.path.dirname(os.path.abspath(__file__))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    jax_port = s.getsockname()[1]
    s.close()
    coord_dir = tempfile.mkdtemp(prefix="mixbench_coord_")
    env = scrub_child_env(
        {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    procs = []
    outs = [""] * n
    threads = []
    try:
        for i in range(n):
            p = subprocess.Popen(
                [sys.executable, "-c", child_src, str(i), str(n),
                 str(jax_port), coord_dir, *map(str, extra_args)],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            procs.append(p)

            def drain(idx=i, proc=p):
                outs[idx] = proc.stdout.read()

            t = threading.Thread(target=drain, daemon=True)
            t.start()
            threads.append(t)
        deadline = time.monotonic() + timeout
        for p in procs:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        for t in threads:
            t.join(timeout=10)
        return outs, [p.returncode for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(coord_dir, ignore_errors=True)
        import glob as _glob

        for marker in _glob.glob(coord_dir.rstrip("/") + ".*"):
            try:  # the children's sibling marker files (.done, .flip_*)
                os.unlink(marker)
            except OSError:
                pass


def collective_nproc(n: int = 4, dim_bits: int = 0,
                     timeout: float = 300.0, mode: str = "off") -> dict:
    """Timed production collective round across ``n`` OS processes.
    ``dim_bits`` > 0 runs the north-star-scale variant (AROW diffs at
    D=2^dim_bits — w + sigma, 2^dim_bits * L * 2 * 4 bytes f32 per
    replica) and measures ALL THREE wire modes back to back in one
    world when ``mode`` starts at "off" (f32 → flip bf16 → flip int8);
    ``mode`` pins a single --mix-compress variant otherwise."""
    out: dict = {}
    tag = (f"_d{dim_bits}" if dim_bits else "") + \
        (f"_{mode}" if mode != "off" else "")
    err_key = f"collective_round{tag}_error"
    extra = ((str(dim_bits), mode)
             if (dim_bits or mode != "off") else ())
    try:
        outs, rcs = run_jax_world(_COLLECTIVE_CHILD, n, timeout=timeout,
                                  extra_args=extra)
    except subprocess.TimeoutExpired:
        return {err_key: "timeout"}
    if any(rc != 0 for rc in rcs):
        return {err_key: f"child exits {rcs}: {(''.join(outs))[-200:]}"}
    for text in outs:
        for line in text.splitlines():
            if line.startswith("COLLECTIVE="):
                out.update(json.loads(line[len("COLLECTIVE="):]))
    if not out:
        out[err_key] = "no master output"
    return out


_DRIFT_CHILD = r"""
import sys, json
import numpy as np
import jax
pid = int(sys.argv[1]); n = int(sys.argv[2])
jax_port = sys.argv[3]
dim_bits = int(sys.argv[5]); rounds = int(sys.argv[6])
from jubatus_tpu.parallel.multihost import enable_cpu_collectives
enable_cpu_collectives()
jax.distributed.initialize(f"127.0.0.1:{jax_port}", num_processes=n,
                           process_id=pid)
from jubatus_tpu.parallel.collective import ErrorFeedback, psum_pytree

# every process contributes fresh per-round diffs; all processes run the
# SAME sequence of collectives (f32, int8+EF, int8 stateless) so the
# streams stay in lockstep — no mixer protocol needed for a raw probe
rng = np.random.default_rng(100 + pid)
shape = (2, 1 << dim_bits)
# force the chunked (= quantized) path even at probe dims below the
# default 8 MiB chunk: ~4 chunks per leaf at any dim_bits
chunk_mb = min(8.0, max(0.25, shape[0] * shape[1] * 4 / 2**20 / 4))
ef = ErrorFeedback()
S32 = np.zeros(shape, np.float32)
S8 = np.zeros(shape, np.float32)
S8n = np.zeros(shape, np.float32)
ph = {}
d1 = None
for r in range(rounds):
    x = {"w": rng.normal(size=shape).astype(np.float32)}
    S32 += psum_pytree(x, compress="off", chunk_mb=chunk_mb)["w"]
    S8 += psum_pytree(x, compress="int8", chunk_mb=chunk_mb, phases=ph,
                      feedback=ef)["w"]
    S8n += psum_pytree(x, compress="int8", chunk_mb=chunk_mb)["w"]
    if d1 is None:
        d1 = float(np.linalg.norm(S8 - S32))
if pid == 0:
    ref = float(np.linalg.norm(S32))
    print("DRIFT=" + json.dumps({
        "collective_round_drift_vs_f32":
            float(np.linalg.norm(S8 - S32)) / ref,
        "collective_round_drift_vs_f32_noef":
            float(np.linalg.norm(S8n - S32)) / ref,
        "collective_round_drift_rounds": rounds,
        "collective_round_drift_first_round_l2": d1,
        "collective_round_drift_ef_rounds": ef.rounds,
        "collective_wire_mb_per_round": ph.get("wire_mb"),
        "collective_round_drift_note": (
            f"cumulative {rounds}-round averaged-weight drift of the "
            "int8 transport at D=2^%d across %d processes; error "
            "feedback telescopes it to ONE round's quantization error, "
            "stateless int8 random-walks" % (dim_bits, n)),
    }), flush=True)
print(f"CHILD-{pid}-DONE", flush=True)
"""


def drift_probe(n: int = 4, dim_bits: int = 22, rounds: int = 6,
                timeout: float = 600.0) -> dict:
    """Multi-round averaged-weight drift of the int8 quantized transport
    vs the exact f32 collective, measured on a REAL n-process world:
    ``collective_round_drift_vs_f32`` (error feedback carried between
    rounds — bounded, non-compounding) against the ``_noef`` control
    (stateless quantization — sqrt(rounds) random walk). The test
    suite's world-of-1 gate proves the telescoping algebra; this probe
    proves it survives the scatter/gather ring."""
    try:
        outs, rcs = run_jax_world(_DRIFT_CHILD, n, timeout=timeout,
                                  extra_args=(str(dim_bits), str(rounds)))
    except subprocess.TimeoutExpired:
        return {"collective_round_drift_error": "timeout"}
    if any(rc != 0 for rc in rcs):
        return {"collective_round_drift_error":
                f"child exits {rcs}: {(''.join(outs))[-300:]}"}
    for text in outs:
        for line in text.splitlines():
            if line.startswith("DRIFT="):
                return json.loads(line[len("DRIFT="):])
    return {"collective_round_drift_error": "no master output"}


_SCALING_CHILD = r"""
import os, sys, time, json
import numpy as np
import jax
pid = int(sys.argv[1]); n = int(sys.argv[2])
jax_port = sys.argv[3]
dim_bits = int(sys.argv[5]); topo = sys.argv[6]
from jubatus_tpu.parallel.multihost import enable_cpu_collectives
enable_cpu_collectives()
jax.distributed.initialize(f"127.0.0.1:{jax_port}", num_processes=n,
                           process_id=pid)
from jubatus_tpu.parallel.collective import psum_pytree

# raw transport probe, no servers: one f32 leaf of 2^dim_bits elements
# (the north-star model dim) through the chunked pipeline, flat vs
# hierarchical, IN THE SAME WORLD — same processes, same gloo sockets,
# and the parity check compares the exact same inputs through both
rng = np.random.default_rng(41 + pid)
x = {"w": rng.normal(size=(1 << dim_bits,)).astype(np.float32)}
rec = {}
totals = {}
trials = 2 if n >= 16 else 3
for variant, kw in (("flat", {}), ("hier", {"topology": topo})):
    ph = {}
    out = psum_pytree(x, phases=ph, **kw)   # warmup: compiles
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        out = psum_pytree(x, phases=ph, **kw)
        times.append((time.perf_counter() - t0) * 1e3)
    totals[variant] = out["w"]
    times.sort()
    rec[variant] = {"ms": times[len(times) // 2], "phases": dict(ph)}
# parity: the two paths reduce in different association orders (ring
# scatter vs two-tier tree), so multi-process totals agree to float32
# rounding, not bitwise — world-1 bitwise parity is the unit suite's
# gate (tests/test_collective_pipeline.py). Gate here on relative error
# at the noise floor of an n-way f32 sum.
scale = float(np.max(np.abs(totals["flat"]))) or 1.0
rel = float(np.max(np.abs(totals["flat"] - totals["hier"]))) / scale
parity = bool(rel < 1e-5)
if pid == 0:
    h, m = (int(s) for s in topo.split("x"))
    sfx = f"nproc{n}_d{dim_bits}"
    fp, hp = rec["flat"]["phases"], rec["hier"]["phases"]
    # flat fleets co-locate the same M processes per physical host the
    # hierarchical grouping names: a flat HOST ships M ring shares
    flat_per_host = m * fp["wire_bytes_per_host"]
    out = {
        f"collective_round_ms_{sfx}": round(rec["flat"]["ms"], 2),
        f"collective_round_ms_{sfx}_hier": round(rec["hier"]["ms"], 2),
        f"collective_scaling_topo_nproc{n}": topo,
        f"collective_wire_bytes_per_host_{sfx}": flat_per_host,
        f"collective_wire_bytes_per_host_{sfx}_hier":
            hp["wire_bytes_per_host"],
        f"collective_wire_per_host_reduction_nproc{n}": round(
            flat_per_host / max(1, hp["wire_bytes_per_host"]), 2),
        f"collective_hier_parity_nproc{n}": parity,
        f"collective_hier_max_rel_err_nproc{n}": rel,
        f"collective_phase_intra_ms_{sfx}_hier": hp["intra_ms"],
        f"collective_phase_inter_ms_{sfx}_hier": hp["inter_ms"],
        f"collective_scaling_note_nproc{n}": (
            f"{n} gloo CPU processes grouped {topo} time-slicing one "
            "core: ms bounds orchestration, wire bytes are the model"),
    }
    print("SCALING=" + json.dumps(out), flush=True)
print(f"CHILD-{pid}-DONE", flush=True)
"""

#: nproc -> the HxM grouping the scaling sweep exercises (hosts on the
#: wire x processes co-located per host)
SCALING_TOPOLOGIES = {4: "2x2", 8: "2x4", 16: "4x4"}


def scaling_sweep(nprocs=(4, 8, 16), dim_bits: int = NORTH_STAR_BITS,
                  timeout: float = 900.0) -> dict:
    """Round time + wire bytes vs nproc, flat vs hierarchical (ISSUE 9).

    The scaling gate: the flat ring's wire bytes per host grow with the
    DEVICE count (every process ships the payload's ring share; M
    co-located processes multiply it), the hierarchical reduce's stay
    proportional to HOSTS on the wire — one chunk copy per host,
    whatever M is. Each world also asserts bit-parity between the two
    paths on identical inputs. On this box the gloo 'intra' tier is
    loopback TCP, not ICI, so round-time wins only appear at nproc>=8
    where the flat ring's hop count dominates; the wire-byte keys are
    the portable claim."""
    out: dict = {}
    for n in nprocs:
        topo = SCALING_TOPOLOGIES.get(n)
        if topo is None:
            h = max(1, n // 4)
            topo = f"{h}x{n // h}"
        err_key = f"collective_scaling_error_nproc{n}"
        try:
            outs, rcs = run_jax_world(
                _SCALING_CHILD, n, timeout=timeout,
                extra_args=(str(dim_bits), topo))
        except subprocess.TimeoutExpired:
            out[err_key] = "timeout"
            continue
        if any(rc != 0 for rc in rcs):
            out[err_key] = f"child exits {rcs}: {(''.join(outs))[-300:]}"
            continue
        got = False
        for text in outs:
            for line in text.splitlines():
                if line.startswith("SCALING="):
                    out.update(json.loads(line[len("SCALING="):]))
                    got = True
        if not got:
            out[err_key] = "no master output"
    return out


def async_fold_probe(dim_bits: int = 20, members: int = 4,
                     trials: int = 5) -> dict:
    """Fold-phase cost of the async plane's bounded-staleness weights
    (ISSUE 11): a weighted host fold of ``members`` dense 2^dim_bits
    f32 diffs vs the sync plane's plain tree_sum over the same
    payloads. The weighting is one extra multiply per stale
    contribution — the probe records the measured overhead ratio so
    "staleness weights are ~free at fold time" stays a number, not a
    claim. (The round-BARRIER comparison — sync gather stalled by a
    straggler vs async cadence — is bench_serving's
    ``e2e_async_mix_straggler_cadence_x``.)"""
    import numpy as np

    from jubatus_tpu.framework.async_mixer import fold_weight, scale_tree
    from jubatus_tpu.parallel.mix import tree_sum

    rng = np.random.default_rng(11)
    d = 1 << dim_bits
    diffs = [{"w": rng.normal(size=d).astype(np.float32),
              "b": rng.normal(size=16).astype(np.float32)}
             for _ in range(members)]
    # half the members one round stale, one at the bound — the shape a
    # mildly-degraded fleet folds every tick
    stal = [0, 1] * (members // 2) + [0] * (members % 2)
    weights = [fold_weight(s, 8) for s in stal]

    def timed(fn):
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    plain_ms = timed(lambda: tree_sum(diffs))
    weighted_ms = timed(lambda: tree_sum(
        [scale_tree(df, w) for df, w in zip(diffs, weights)]))
    tag = f"d{dim_bits}_m{members}"
    out = {f"mix_async_fold_ms_{tag}": round(weighted_ms, 3),
           f"mix_sync_fold_ms_{tag}": round(plain_ms, 3)}
    if plain_ms > 0:
        out[f"mix_async_fold_weighted_overhead_ratio_{tag}"] = round(
            weighted_ms / plain_ms, 3)
    return out


def collect(dev=None) -> dict:
    import jax

    out = device_round(20, dev, tag="d20")
    out.update(device_round(NORTH_STAR_BITS, dev, trials=3, tag="d24"))
    # the platform the single-device rounds ran on
    out["mix_platform"] = (dev.platform if dev is not None
                           else jax.devices()[0].platform)
    out.update(_allreduce8_subprocess())
    out.update(collective_nproc(4))
    # multi-round drift of the quantized transport vs f32 on a real
    # 4-process world: error feedback bounded vs stateless random walk
    out.update(drift_probe())
    # the d24 world measures f32, bf16 AND int8 rounds back to back (one
    # boot, one membership, flip-in-place): per-phase keys for all three
    # variants let the --mix-compress tradeoff be audited per term
    # (on-device cast/quant cost vs 2x/4x fewer wire bytes) instead of
    # as one opaque total (VERDICT r4 #5)
    out.update(collective_nproc(4, dim_bits=NORTH_STAR_BITS, timeout=1800))
    # nproc scaling curve, flat vs hierarchical (ISSUE 9): wire bytes
    # per host must track hosts-on-the-wire, not total processes
    out.update(scaling_sweep())
    # async mix (ISSUE 11): staleness-weighted fold cost vs plain sum
    out.update(async_fold_probe())
    # wire-reduction ratio the int8 mode actually achieved at d24, and
    # the round-time comparison against the bf16 baseline (on CPU
    # loopback the quantization compute competes with the saved memcpy
    # on the SAME starved core — the wire win is the ICI story, see
    # docs/PERF_NOTES.md "Quantized mix")
    w_f32 = out.get(f"collective_wire_mb_per_round_d{NORTH_STAR_BITS}")
    w_int8 = out.get(f"collective_wire_mb_per_round_d{NORTH_STAR_BITS}_int8")
    if w_f32 and w_int8:
        out["collective_wire_reduction_int8_vs_f32"] = round(
            w_f32 / w_int8, 2)
    ms_bf16 = out.get(f"collective_round_ms_nproc4_d{NORTH_STAR_BITS}_bf16")
    ms_int8 = out.get(f"collective_round_ms_nproc4_d{NORTH_STAR_BITS}_int8")
    if ms_bf16 and ms_int8:
        out["collective_round_int8_vs_bf16_ratio"] = round(
            ms_int8 / ms_bf16, 3)
    gates = [v for k, v in out.items() if k.startswith("mix_round_ms_d24_")]
    if gates:
        out["mix_round_worst_ms"] = max(gates)
    # the north-star flag (BASELINE.md: mix round <= 1 s at D=2^24) is
    # computed ONLY from the measurement that includes BOTH the scale and
    # the multi-process transport: the nproc4 collective round shipping
    # d24 AROW diffs, labeled with the platform that ran it (VERDICT r3:
    # a single-device psum on the CPU fallback checks no box).
    ns_key = f"collective_round_ms_nproc4_d{NORTH_STAR_BITS}"
    if ns_key in out:
        ms = out[ns_key]
        plat = out.get(f"collective_round_d{NORTH_STAR_BITS}_platform",
                       "cpu")
        out["mix_under_1s_target"] = bool(ms < 1000.0)
        out["mix_under_1s_platform"] = plat
        if plat == "cpu" and ms >= 1000.0:
            payload = out.get(
                f"collective_round_d{NORTH_STAR_BITS}"
                "_payload_mb_per_replica", 0.0)
            wire = payload * 2 * 3 / 4  # ring allreduce, n=4
            out["mix_under_1s_note"] = (
                f"fails on cpu orchestration (4 processes time-slicing one "
                f"core, loopback transport); passing needs real chips: "
                f"~{wire:.0f} MB/replica on the wire per round, i.e. ICI "
                f"must sustain >= {wire / 1000:.1f} GB/s per link with "
                f"host orchestration off the critical path")
    return out


_SHARDED_CHILD = r"""
import json, os, sys, time
import numpy as np
import jax, jax.numpy as jnp

dim_bits = int(sys.argv[1]); shards = int(sys.argv[2])
method = sys.argv[3] if len(sys.argv) > 3 else "AROW"
B, K, L = 2048, 32, 2
D = 1 << dim_bits
from jubatus_tpu.ops import classifier as ops
from jubatus_tpu.parallel import sharded_model as sm

conf = method in ops.CONFIDENCE_METHODS
rng = np.random.default_rng(0)
idx = jnp.asarray(rng.integers(0, D, (B, K)).astype(np.int32))
val = jnp.asarray(rng.normal(size=(B, K)).astype(np.float32))
labels = jnp.asarray(rng.integers(0, L, B).astype(np.int32))
mask = jnp.asarray(np.ones(L, bool))
qi = jnp.asarray(rng.integers(0, D, (256, K)).astype(np.int32))
qv = jnp.asarray(rng.normal(size=(256, K)).astype(np.float32))

if shards > 1:
    mesh = sm.feature_shard_mesh(shards)
    st = sm.place_state(mesh, ops.init_state(L, D, conf), D)
    # routed by column range on the host, as the driver's stage does
    ridx, rval, _ = sm.route_rows(np.asarray(idx), np.asarray(val), shards,
                                  D // shards)
    ridx, rval = jax.device_put((ridx, rval), sm.flush_sharding(mesh))
    train = lambda s: sm.train_batch(mesh, s, ridx, rval, labels, mask,
                                     1.0, method=method)
    classify = lambda s: sm.scores(mesh, s, qi, qv, mask)
else:
    st = ops.init_state(L, D, conf)
    train = lambda s: ops.train_batch(s, idx, val, labels, mask, 1.0,
                                      method=method)
    classify = lambda s: ops.scores(s, qi, qv, mask)

# per-device weight-state footprint: the acceptance criterion's shape
per_dev = {}
for leaf in st:
    for sh in leaf.addressable_shards:
        per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + \
            int(np.prod(sh.data.shape)) * leaf.dtype.itemsize
total_bytes = sum(int(leaf.nbytes) for leaf in st)

st = train(st); jax.block_until_ready(st)         # compile
t_train = []
for _ in range(5):
    t0 = time.perf_counter()
    st = train(st); jax.block_until_ready(st)
    t_train.append(time.perf_counter() - t0)
sc = classify(st); jax.block_until_ready(sc)      # compile
t_cls = []
for _ in range(15):
    t0 = time.perf_counter()
    jax.block_until_ready(classify(st))
    t_cls.append(time.perf_counter() - t0)
out = {
    "samples_per_sec": round(B / float(np.median(t_train)), 1),
    "classify_p99_ms": round(
        float(np.percentile(np.asarray(t_cls) * 1e3, 99)), 2),
    "state_bytes_total": total_bytes,
    "state_bytes_per_device_max": max(per_dev.values()),
    "devices": len(per_dev),
}
print(json.dumps(out))
"""


def run_sharded_model(dim_bits: int = 26, shard_counts=(1, 8),
                      method: str = "AROW",
                      timeout: float = 1800.0) -> dict:
    """Feature-sharded linear model bench (ISSUE 13): train samples/s
    and classify p99 at D=2^dim_bits, single- vs multi-shard, each in a
    subprocess with that many virtual devices. Emits
    ``sharded_train_samples_per_sec_d{bits}_{s}shard`` (up-good) and
    ``sharded_classify_p99_ms_d{bits}_{s}shard`` (down-good), plus the
    per-device weight-state footprint that IS the HBM-capacity win —
    virtual CPU devices share one core, so multi-shard WALL numbers
    bound orchestration + psum cost, not chip throughput (same caveat
    as allreduce8)."""
    import jax

    out: dict = {"sharded_model_platform": jax.devices()[0].platform}
    for s in shard_counts:
        env = scrub_child_env(dict(os.environ))
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={max(s, 1)}"])
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _SHARDED_CHILD, str(dim_bits),
                 str(s), method],
                capture_output=True, text=True, timeout=timeout, env=env)
            doc = json.loads(proc.stdout.strip().splitlines()[-1])
        except Exception as e:  # noqa: BLE001 — partial results beat a dead bench
            out[f"sharded_model_error_{s}shard"] = repr(e)[:200]
            continue
        tag = f"d{dim_bits}_{s}shard"
        out[f"sharded_train_samples_per_sec_{tag}"] = doc["samples_per_sec"]
        out[f"sharded_classify_p99_ms_{tag}"] = doc["classify_p99_ms"]
        out[f"sharded_state_mb_per_device_{tag}"] = round(
            doc["state_bytes_per_device_max"] / 2 ** 20, 1)
        out[f"sharded_state_mb_total_{tag}"] = round(
            doc["state_bytes_total"] / 2 ** 20, 1)
    # the acceptance shape: per-device footprint <= total / n_shards
    # (+ O(1) replicated leaves) — recorded as a boolean gate
    for s in shard_counts:
        if s <= 1:
            continue
        tag = f"d{dim_bits}_{s}shard"
        per = out.get(f"sharded_state_mb_per_device_{tag}")
        tot = out.get(f"sharded_state_mb_total_{tag}")
        if per is not None and tot is not None:
            out[f"sharded_footprint_sliced_{tag}_ok"] = \
                bool(per <= tot / s + 1.0)
    return out


if __name__ == "__main__":
    from jubatus_tpu.utils.compile_cache import configure as _configure_cache

    _configure_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "sharded":
        # the ISSUE 13 slice on its own: feature-sharded train/classify
        # at D=2^bits (default 26), single- vs N-shard
        bits = int(sys.argv[2]) if len(sys.argv) > 2 else 26
        shards = int(sys.argv[3]) if len(sys.argv) > 3 else 8
        print(json.dumps(run_sharded_model(bits, (1, shards)), indent=1))
    else:
        print(json.dumps(collect(), indent=1))
