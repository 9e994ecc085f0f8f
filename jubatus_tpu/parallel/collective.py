"""Cross-process diff reduction — the mix's data plane as an XLA collective.

``psum_pytree`` reduces one pytree of arrays across every process in the
``jax.distributed`` world: each process contributes its local replica's
diff, the reduction runs as jitted shard_map psums over a
one-device-per-process 'replica' mesh (ICI/DCN, not TCP fan-out), and
every process reads back the identical total. This is SURVEY.md §7 step
3's north-star shape: the reference's get_diff → pairwise fold →
put_diff (linear_mixer.cpp:437-559) collapses into AllReduces whose
combiner IS the fold.

The data plane is PIPELINED (docs/PERF_NOTES.md "Mix data plane"):

- Leaves at or above the chunk size are split into fixed-size 1-D chunks
  and streamed with a double buffer, so the host→device ship of chunk
  k+1 overlaps the psum of chunk k and the device→host readback of
  chunk k−1 — instead of the old serial cast-all/ship-all/reduce-all/
  readback-all ("Exploring the limits of Concurrency in ML Training on
  Google TPUs", arxiv 2011.03641: transfer/compute overlap is where TPU
  pipelines recover wall clock). Chunk psums are separate collectives,
  so every process MUST build the identical stream: the plan is a pure
  function of (shapes, dtypes, chunk_bytes, compress) — which the
  collective mixer folds into its prepare signature — never of where a
  leaf happens to live.
- ``compress`` is a three-state wire mode, ``off | bf16 | int8`` (the
  historical bool still resolves: True == "bf16").

  * ``bf16`` casts f32 chunks to bf16 ON DEVICE in the ship stage (a
    tiny jitted cast right after placement), so the psum's wire sees
    half the bytes, the collective body stays a pure reduce, and the
    host never stages an astype copy (EQuARX, arxiv 2506.17615: a
    compressed AllReduce only wins when the cast is fused off the host).
  * ``int8`` is the EQuARX shape proper: per-block scales computed on
    device, quantize-on-device BEFORE the ship (the collective stages
    int8 + one f32 scale per QUANT_BLOCK elements — ~3.94x fewer bytes
    per chunk), scatter-reduce where receivers DEQUANTIZE and
    accumulate in f32, the segment owner REQUANTIZES the reduced
    total, the int8 representation all-gathers around the ring, and
    readback dequantizes.
    Quantization is biased, and an online learner's weight averages
    feed the next round — so a per-replica ``ErrorFeedback`` residual
    (quantization error added back into the next round's diff) keeps
    the averaged weights unbiased: the shipped sums telescope to the
    true sums minus ONE bounded residual, for any number of rounds.
    Small leaves and non-f32 dtypes stay exact (counts must not drift).
- Leaves that are already device-resident ``jax.Array``s (the models in
  models/ are JAX — their diffs need not round-trip through numpy) take
  a zero-staging path: no host cast, no ``device_put`` from numpy, and
  with ``prefer_device=True`` no readback either — the totals are handed
  back as device arrays for the jitted put_diff to consume directly.
- ``topology`` switches the chunked pipeline into HIERARCHICAL mode
  over the two-tier ``(host, local)`` mesh (parallel/mesh.py
  ``host_topology``): each chunk is first psum'd over the ``local``
  axis (intra-host — ICI/loopback, not the wire), each local lane then
  carries only its 1/M segment of the host total into the inter-host
  reduce over the ``host`` axis, and an intra-host all-gather (a psum
  of lane-placed segments) rebuilds the full chunk. The inter-host
  wire therefore ships ONE copy of the chunk per host — wire bytes per
  host stay proportional to hosts, not total devices (the MLPerf-on-
  TPU-pods / "limits of Concurrency" hierarchical-reduction shape;
  flat all-reduce ships the chunk once per *device*). Wire modes
  compose: bf16 casts and int8 quantizes AFTER the intra-host reduce
  (the intra tier stays exact f32 — its bandwidth is free by
  assumption), so int8 error-feedback residuals correct the HOST sum
  and live one per host, not one per device.

Requirements: every process calls with the SAME treedef/shapes/dtypes in
the same order and the same ``compress``/``chunk_bytes`` (the collective
mixer's prepare phase verifies this before anyone enters), and the jax
runtime must be initialized across the world (jax.distributed.initialize
— parallel/multihost.py). Works single-process too (world of 1: psum
degenerates to identity and the int8 path to one quantize round trip —
which is exactly what the error-feedback drift gates exercise).
"""

from __future__ import annotations

import functools
import math
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jubatus_tpu.parallel.mesh import HostTopology, host_mesh, host_topology
from jubatus_tpu.utils import faults


class ChunkIntegrityError(RuntimeError):
    """A wire chunk failed its integrity screen (ISSUE 15): ``kind`` is
    ``"crc"`` (a staged chunk's CRC32 no longer matches — corruption in
    the host staging window) or ``"nonfinite"`` (the reduced total
    carries NaN/Inf — some contributor shipped poison, or the fold
    overflowed). The collective mixer catches this, counts it, and
    routes the next round to the RPC mix instead of applying garbage."""

    def __init__(self, kind: str, detail: str = "") -> None:
        super().__init__(f"chunk integrity failure ({kind}): {detail}")
        self.kind = kind

#: pipeline chunk size in MiB (uncompressed leaf bytes). Leaves at or
#: above this split into chunks and double-buffer; smaller leaves batch
#: into one collective call. 8 MiB won the sweep recorded in
#: docs/PERF_NOTES.md ("Mix data plane"): big enough that per-chunk
#: dispatch overhead (~0.1 ms) is noise against the chunk's transfer,
#: small enough that three in-flight buffers overlap rather than
#: serialize. Override per deployment with JUBATUS_TPU_MIX_CHUNK_MB —
#: every process in a cluster must agree (the prepare signature checks).
DEFAULT_CHUNK_MB = float(os.environ.get("JUBATUS_TPU_MIX_CHUNK_MB", "8"))

#: in-flight chunks beyond the one being collected: 2 = classic double
#: buffer (ship k+1 while chunk k reduces and chunk k−1 reads back)
_PIPELINE_DEPTH = 2

#: wire-compression modes psum_pytree understands; the collective
#: mixer's --mix-compress flag and prepare signature speak the same enum
COMPRESS_MODES = ("off", "bf16", "int8")

#: elements per quantization block in int8 mode: one f32 scale (absmax /
#: 127) per QUANT_BLOCK elements, so the wire overhead is 4/QUANT_BLOCK
#: bytes per element (~1.6% at 256 — 3.94x total reduction vs f32).
#: Every process in a cluster must agree (rides the prepare signature).
QUANT_BLOCK = int(os.environ.get("JUBATUS_TPU_MIX_QUANT_BLOCK", "256"))

_64BIT = (np.dtype(np.float64), np.dtype(np.int64), np.dtype(np.uint64))

#: process-wide collective dispatch gate (ISSUE 11). Chunk psums are a
#: SEQUENCE of separate collectives, and XLA matches collectives across
#: processes by dispatch order — two rounds interleaving their dispatch
#: in one process would wedge the world. The gate serializes DISPATCH
#: only: it is released the moment a round's last chunk has been handed
#: to the runtime, before the reader thread drains the readback. Round
#: N+1's early chunk ship/reduce therefore overlaps round N's readback
#: (the ``psum_pytree_start`` streaming shape), while the collective
#: order every process sees stays total.
_DISPATCH_GATE = threading.Lock()


class _Gate:
    """One round's hold on the dispatch gate; release is idempotent so
    the early release at dispatch-complete and the outer safety-net
    finally compose."""

    def __init__(self) -> None:
        self._held = False

    def acquire(self) -> float:
        t0 = time.perf_counter()
        _DISPATCH_GATE.acquire()
        self._held = True
        return time.perf_counter() - t0

    def release(self) -> None:
        if self._held:
            self._held = False
            _DISPATCH_GATE.release()


class PendingReduce:
    """Handle for a streaming round started with ``psum_pytree_start``:
    the reduce is dispatching/draining on a worker thread; ``result()``
    joins it and returns the totals (re-raising any failure). While one
    round's readback drains, the NEXT ``psum_pytree_start`` call's ship
    and reduce dispatch may already run — the dispatch gate keeps the
    collective order total across rounds, which is what makes the
    overlap safe."""

    def __init__(self) -> None:
        self._thread: Optional[threading.Thread] = None
        self._box: Dict[str, Any] = {}

    def done(self) -> bool:
        return self._thread is not None and not self._thread.is_alive()

    def result(self) -> Any:
        self._thread.join()
        if "err" in self._box:
            raise self._box["err"]
        return self._box["out"]


def psum_pytree_start(diff: Any, **kwargs) -> PendingReduce:
    """Begin one AllReduce round on a worker thread and return a
    ``PendingReduce`` immediately. Back-to-back rounds stream: round
    N+1's early chunk ship/reduce overlaps round N's readback, because
    the dispatch gate serializes only the DISPATCH of collectives (a
    hard ordering requirement), never the device→host drain. Callers
    must still collect rounds in the order they started them (every
    process must run rounds in the same order)."""
    pending = PendingReduce()

    def work() -> None:
        try:
            pending._box["out"] = psum_pytree(diff, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised in result()
            pending._box["err"] = e

    t = threading.Thread(target=work, name="mix-round-reduce", daemon=True)
    pending._thread = t
    t.start()
    return pending


def _norm_compress(compress: Any) -> str:
    """Resolve the wire mode: the ``off|bf16|int8`` enum, or the
    historical bool (True meant "ship f32 as bf16") every pre-enum
    caller still passes."""
    if isinstance(compress, str):
        mode = compress.lower() or "off"
        if mode not in COMPRESS_MODES:
            raise ValueError(f"unknown mix compress mode {compress!r}; "
                             f"expected one of {COMPRESS_MODES}")
        return mode
    return "bf16" if compress else "off"


def _norm_topology(topology: Any) -> Optional[HostTopology]:
    """Resolve the hierarchical-mode switch: None/"" / "flat" keep the
    flat single-tier pipeline; a HostTopology rides as-is; an "HxM"
    string (the --mix-topology override) resolves against the runtime's
    devices. Every process in a cluster must resolve the SAME topology —
    the collective mixer signs its prepare with it."""
    if topology is None or topology == "" or topology == "flat":
        return None
    if isinstance(topology, HostTopology):
        return topology
    if topology == "auto":
        return host_topology()
    return host_topology(override=topology)


#: per-(device, shape, dtype) zero staging buffers for the hierarchical
#: path's non-representative lanes and fresh residual chains. Bounded;
#: safe to reuse because device arrays are immutable and the hier
#: programs never donate them.
_ZEROS_CACHE: Dict[Tuple, Any] = {}


def _dev_zeros(dev, shape: Tuple[int, ...], dtype_str: str):
    key = (dev, shape, dtype_str)
    z = _ZEROS_CACHE.get(key)
    if z is None:
        if len(_ZEROS_CACHE) > 64:
            _ZEROS_CACHE.clear()
        z = jax.device_put(np.zeros(shape, np.dtype(dtype_str)), dev)
        _ZEROS_CACHE[key] = z
    return z


class ErrorFeedback:
    """Per-replica error-feedback residual state for the int8 transport.

    Block quantization is biased, and the mix averages weights round
    over round — without correction the per-round bias compounds into a
    random walk on the averaged model. Carrying the residual
    ``e_r = (x_r + e_{r-1}) - dequant(quant(x_r + e_{r-1}))`` between
    rounds telescopes it away: the sum of shipped contributions equals
    the sum of true diffs minus ONE bounded residual, for any number of
    rounds (the drift gate in tests/test_collective_pipeline.py proves
    both directions).

    Two chains per replica, matching the two quantization events in the
    chunk collective: ``contrib`` (this replica's own diff segments,
    quantized once for the scatter) and ``total`` (the requant of the
    reduced segments this replica owns and broadcasts). Residuals stay
    device-resident between rounds, keyed by (leaf index, chunk start),
    and are committed only after the WHOLE collective entry succeeds —
    an aborted, degraded, or mid-psum-failed round leaves the state of
    the last successful round intact."""

    def __init__(self) -> None:
        self.key: Optional[Tuple] = None
        self.contrib: Dict[Tuple[int, int], Any] = {}
        self.total: Dict[Tuple[int, int], Any] = {}
        self.rounds = 0

    def reset(self) -> None:
        self.key = None
        self.contrib.clear()
        self.total.clear()

    def stats(self) -> Dict[str, int]:
        return {"rounds": self.rounds, "chunks": len(self.contrib)}

    def norms(self) -> Dict[str, float]:
        """L2 norm of each residual chain — the model-health plane's
        drift signal (ISSUE 7): a residual norm that GROWS round over
        round means quantization error is being deferred faster than
        the telescoping cancels it. One device reduction per residual
        chunk, so call this once per round (the mixer caches it for
        get_status), not per scrape."""
        out: Dict[str, float] = {}
        for name, chain in (("contrib", self.contrib),
                            ("total", self.total)):
            s = 0.0
            for v in chain.values():
                d = v * 1.0  # promote without a host copy; jnp or numpy
                s += float((d * d).sum())
            out[f"{name}_residual_norm"] = float(math.sqrt(s))
        return out


def _world_mesh() -> Mesh:
    """1-D 'replica' mesh with exactly one device per process (the first
    local device of each), in process order — every process builds the
    identical mesh."""
    per_process: Dict[int, Any] = {}
    for d in jax.devices():
        p = d.process_index
        if p not in per_process or d.id < per_process[p].id:
            per_process[p] = d
    devs = [per_process[p] for p in sorted(per_process)]
    return Mesh(np.array(devs), axis_names=("replica",))


def _donate() -> Tuple[int, ...]:
    # donating the stacked input lets XLA reuse its buffer for the
    # on-device bf16 cast; the CPU backend can't honor donation and
    # would warn on every compile
    return () if jax.default_backend() == "cpu" else (0,)


def _psum_body(x, compress: bool):
    y = jnp.squeeze(x, 0)
    if compress and y.dtype == jnp.float32:
        # cast fused into the collective: the wire sees bf16 (half the
        # ICI/DCN bytes), the caller gets f32 back — the EQuARX-style
        # tradeoff without the old host-side astype copy
        y = y.astype(jnp.bfloat16)
        return jax.lax.psum(y, "replica").astype(jnp.float32)
    total = jax.lax.psum(y, "replica")
    if compress and total.dtype == jnp.bfloat16:
        # pre-cast bf16 input under compress keeps the old contract:
        # hand back f32 for the f32 master
        total = total.astype(jnp.float32)
    return total


@functools.lru_cache(maxsize=32)
def _reduce_tree_fn(mesh: Mesh, treedef, shapes: Tuple, dtypes: Tuple,
                    compress: bool):
    """Batched psum of one pytree of small leaves (single collective
    program, like the pre-pipeline engine)."""

    def body(stacked):
        return jax.tree_util.tree_map(
            lambda x: _psum_body(x, compress), stacked)

    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("replica"), out_specs=P()),
        out_shardings=NamedSharding(mesh, P()),
        donate_argnums=_donate(),
    )


@functools.lru_cache(maxsize=32)
def _reduce_chunk_fn(mesh: Mesh, elems: int, dtype_str: str, compress: bool):
    """psum of one [world, elems] chunk. All full chunks of a dtype share
    this one compiled program; ragged tails are zero-padded up to it
    (psum of zeros is zeros, sliced off at collection)."""

    def body(x):
        return _psum_body(x, compress)

    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("replica"), out_specs=P()),
        out_shardings=NamedSharding(mesh, P()),
        donate_argnums=_donate(),
    )


@functools.lru_cache(maxsize=8)
def _cast_fn(dtype_str: str):
    """On-device dtype cast for the ship stage (bf16 mode). The wire
    prep must never be a host astype — at the d24 bench shape that copy
    alone cost ~740 ms per round (the codestyle host-cast gate keeps it
    from coming back)."""
    return jax.jit(lambda x: x.astype(jnp.dtype(dtype_str)))


def _block_quant(y, block: int):
    """[m] f32 -> ([m] int8, [m/block] f32 scales); m % block == 0.
    Symmetric per-block absmax scaling (EQuARX's block-wise design: one
    outlier only poisons its own 256 elements, not the tensor)."""
    b = y.reshape(-1, block)
    amax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, jnp.float32(1.0))
    q = jnp.clip(jnp.round(b / scale), -127.0, 127.0).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def _block_dequant(q, scale, block: int):
    return (q.reshape(-1, block).astype(jnp.float32)
            * scale[:, None]).reshape(-1)


def _quant_ring_reduce(q, scales, res_t, axis: str, n: int, block: int):
    """The quantized scatter-reduce + all-gather ring over ``axis``
    (n members), shared by the flat transport (axis="replica", the
    whole world) and the hierarchical inter-host tier (axis="host",
    one lane-segment per host group). ``q`` [m] int8 + ``scales``
    [m/block] f32 are the caller's pre-quantized copy of the full ring
    payload (m divisible by n*block); ``res_t`` [m/n] is the carried
    requant residual of the segment this member owns. Returns the
    dequantized total [m] f32 — bit-identical on every member, because
    everyone dequantizes the same all-gathered int8+scale bits — and
    the new owned-segment residual. n == 1 degenerates to the pure
    dequant → +res → requant round trip the world-1 drift gates ride."""
    m = q.shape[0]
    seg = m // n
    sb = (m // block) // n  # scale blocks per segment
    r = jax.lax.axis_index(axis)
    qsegs = q.reshape(n, seg)
    ssegs = scales.reshape(n, sb)
    acc = _block_dequant(
        jax.lax.dynamic_index_in_dim(qsegs, r, 0, keepdims=False),
        jax.lax.dynamic_index_in_dim(ssegs, r, 0, keepdims=False),
        block)
    for k in range(1, n):
        perm = [(i, (i + k) % n) for i in range(n)]
        sq = jax.lax.dynamic_index_in_dim(
            qsegs, (r + k) % n, 0, keepdims=False)
        ss = jax.lax.dynamic_index_in_dim(
            ssegs, (r + k) % n, 0, keepdims=False)
        acc = acc + _block_dequant(
            jax.lax.ppermute(sq, axis, perm),
            jax.lax.ppermute(ss, axis, perm), block)
    tot = acc + res_t
    tq, ts = _block_quant(tot, block)
    new_res_t = tot - _block_dequant(tq, ts, block)
    out = jnp.zeros((n, seg), jnp.float32)
    out = out.at[r].set(_block_dequant(tq, ts, block))
    cq, cs, idx = tq, ts, r
    fwd = [(i, (i + 1) % n) for i in range(n)]
    for _ in range(n - 1):
        cq = jax.lax.ppermute(cq, axis, fwd)
        cs = jax.lax.ppermute(cs, axis, fwd)
        idx = (idx - 1) % n
        out = out.at[idx].set(_block_dequant(cq, cs, block))
    return out.reshape(m), new_res_t


@functools.lru_cache(maxsize=32)
def _quant_ship_fn(celems: int, block: int):
    """LOCAL (non-collective) per-chunk quantizer for the ship stage:
    ``(x [1, celems] f32, res [1, celems] f32) -> (q int8, scales f32,
    new_res f32)``. Quantize-on-device BEFORE the ship — the collective's
    input arrays are int8 + per-block scales (4x smaller staging than an
    f32 chunk), the error-feedback residual of this replica's own
    contribution is computed here (and never enters the collective), and
    the host never stages a cast."""

    def body(x, res):
        y = jnp.squeeze(x, 0) + jnp.squeeze(res, 0)
        q, scales = _block_quant(y, block)
        new_res = y - _block_dequant(q, scales, block)
        return q[None], scales[None], new_res[None]

    return jax.jit(body)


@functools.lru_cache(maxsize=32)
def _quant_reduce_fn(mesh: Mesh, celems: int, block: int):
    """Quantized all-reduce of one pre-quantized [world, celems] chunk —
    dequant → sum → requant, one jitted program per chunk size:

    - scatter-reduce: for each ring shift k, every replica forwards its
      (already ship-quantized) int8 copy of the RECEIVER's segment; the
      receiver dequantizes and accumulates in f32 on device — the wire
      never sees anything wider than int8 + per-block f32 scales.
    - the segment owner requantizes the reduced total (the second
      error-feedback chain, carried via ``res_t``) and the int8 bits
      all-gather around the ring; EVERY replica — owner included —
      dequantizes the same int8+scale representation on readback, so
      the output is bit-identical everywhere (shard_map cannot prove
      that: check_vma=False).

    World of 1 degenerates to the pure quantize round trip (ship quant
    → dequant → total requant) with both residual chains active — the
    single-process drift gates ride that."""
    n = mesh.shape["replica"]

    def body(q, scales, res_t):
        out, new_res_t = _quant_ring_reduce(
            jnp.squeeze(q, 0), jnp.squeeze(scales, 0),
            jnp.squeeze(res_t, 0), "replica", n, block)
        return out, new_res_t[None]

    return jax.jit(
        shard_map(body, mesh=mesh,
                  in_specs=(P("replica"), P("replica"), P("replica")),
                  out_specs=(P(), P("replica")),
                  check_vma=False),
        out_shardings=(NamedSharding(mesh, P()),
                       NamedSharding(mesh, P("replica"))),
        # only the quantized buffer is donated: the residual input must
        # survive a failed round (feedback commits on success)
        donate_argnums=_donate(),
    )


_SPEC2 = P("host", "local")


@functools.lru_cache(maxsize=32)
def _hier_fns(mesh: Mesh, celems: int, dtype_str: str, mode: str):
    """The two-tier reduce of one [hosts, locals, celems] chunk as TWO
    jitted programs (separately dispatched so the mix can time the
    tiers apart — ``intra_ms`` vs ``inter_ms``):

    - intra: reduce-scatter over ``local`` — each lane receives ONLY
      its 1/M segment of the host sum, (M-1)/M of the chunk on the
      intra wire (a full psum would ship 2(M-1)/M and broadcast a sum
      we immediately discard M-1 of). The bf16 cast happens here, after
      the exact intra fold, when the wire mode asks — the inter tier's
      input is one chunk copy per host, spread over the lanes.
    - inter: psum over ``host`` reduces each lane's segment across
      hosts (M parallel rings, each carrying a DISTINCT segment — the
      per-host wire is the chunk once, not once per device), then an
      intra-host all-gather of the lane segments (lane-order concat)
      rebuilds the full chunk on every device.

    A 1x1 topology degenerates to the identity pipeline — bit-identical
    to the flat path, which the world-1 parity gates assert."""
    compress = mode == "bf16" and dtype_str == "float32"

    def intra(x):
        y = jnp.squeeze(x, (0, 1))
        s = jax.lax.psum_scatter(y, "local", scatter_dimension=0,
                                 tiled=True)
        if compress:
            s = s.astype(jnp.bfloat16)
        return s[None, None]

    def inter(s):
        y = jnp.squeeze(s, (0, 1))
        tot = jax.lax.psum(y, "host")
        if compress:
            tot = tot.astype(jnp.float32)
        return jax.lax.all_gather(tot, "local", tiled=True)

    # no donation on the intra input: its zero lanes come from the
    # shared _dev_zeros cache and must survive the call
    intra_j = jax.jit(
        shard_map(intra, mesh=mesh, in_specs=_SPEC2, out_specs=_SPEC2,
                  check_vma=False),
        out_shardings=NamedSharding(mesh, _SPEC2))
    inter_j = jax.jit(
        shard_map(inter, mesh=mesh, in_specs=_SPEC2, out_specs=P(),
                  check_vma=False),
        out_shardings=NamedSharding(mesh, P()),
        donate_argnums=_donate())
    return intra_j, inter_j


@functools.lru_cache(maxsize=32)
def _hier_quant_fns(mesh: Mesh, celems: int, block: int):
    """int8 over the two-tier mesh. Quantization happens AFTER the
    intra-host reduce (the intra tier is exact f32 — quantizing the
    wire you are not constrained by would only add error), so the
    error-feedback residuals correct the HOST sum: one ``contrib``
    chain entry per (host, lane) segment — per host, not per
    contributing device — and the ring's requant chain per owned
    sub-segment, exactly like the flat transport one tier down."""
    n_host = mesh.shape["host"]

    def intra(x, res_c):
        y = jnp.squeeze(x, (0, 1))
        s = jax.lax.psum_scatter(y, "local", scatter_dimension=0,
                                 tiled=True)
        s = s + jnp.squeeze(res_c, (0, 1))
        q, scales = _block_quant(s, block)
        new_res = s - _block_dequant(q, scales, block)
        return q[None, None], scales[None, None], new_res[None, None]

    def inter(q, scales, res_t):
        out_seg, new_rt = _quant_ring_reduce(
            jnp.squeeze(q, (0, 1)), jnp.squeeze(scales, (0, 1)),
            jnp.squeeze(res_t, (0, 1)), "host", n_host, block)
        return (jax.lax.all_gather(out_seg, "local", tiled=True),
                new_rt[None, None])

    # no donation on intra (zero lanes + residual come from shared /
    # carried buffers); inter donates only the fresh quantized buffer —
    # the residual input must survive a failed round
    intra_j = jax.jit(
        shard_map(intra, mesh=mesh, in_specs=(_SPEC2, _SPEC2),
                  out_specs=(_SPEC2, _SPEC2, _SPEC2), check_vma=False),
        out_shardings=(NamedSharding(mesh, _SPEC2),) * 3)
    inter_j = jax.jit(
        shard_map(inter, mesh=mesh, in_specs=(_SPEC2, _SPEC2, _SPEC2),
                  out_specs=(P(), _SPEC2), check_vma=False),
        out_shardings=(NamedSharding(mesh, P()),
                       NamedSharding(mesh, _SPEC2)),
        donate_argnums=_donate())
    return intra_j, inter_j


@functools.lru_cache(maxsize=8)
def _finite_all_fn():
    """On-device isfinite reduction of one reduced chunk — the
    collective path's half of the fold-time finite screen (the RPC mix
    screens payloads on the host; device-resident totals must be
    screened where they live). Returns a device scalar so the pipeline
    never blocks per chunk; the flags fold into one host readback at
    round end."""
    return jax.jit(lambda x: jnp.isfinite(x).all())


def _finite_flag(arr):
    """Device bool scalar (or None for non-float dtypes, which cannot
    carry NaN/Inf)."""
    if not np.issubdtype(np.dtype(arr.dtype), np.floating):
        return None
    return _finite_all_fn()(arr)


def _crc_stage(chunk: np.ndarray, state: Dict[str, int],
               guard: str) -> np.ndarray:
    """CRC32-bracketed staging of one host wire chunk (ISSUE 15): stamp
    the contribution's checksum, pass through the ``mix.wire.corrupt``
    chaos window (bitflip models transport/DMA corruption), and verify
    before the bytes reach the device. The bracket covers the host
    staging window — device-side transport integrity is the runtime's
    job, and the reduced total's finite screen is the cross-member
    backstop. The chunk also gets the CONTRIBUTION-side finite screen
    here: the int8 transport's requant LAUNDERS a NaN/Inf block into
    zeros (NaN fails the ``amax > 0`` scale test), so poison must be
    caught before it quantizes, not after it reduces. ``quarantine``
    raises (the round dies instead of shipping garbage); ``warn``
    counts and ships."""
    from jubatus_tpu import native

    if np.issubdtype(chunk.dtype, np.floating) and \
            not np.isfinite(chunk).all():
        state["nonfinite"] += 1
        if guard == "quarantine":
            raise ChunkIntegrityError(
                "nonfinite", "staged contribution chunk carries NaN/Inf")
    buf = chunk.tobytes()
    crc0 = native.crc32(buf)
    if faults.is_armed():
        mut = faults.fire_mutate("mix.wire.corrupt")
        if mut is not None and mut[0] == "bitflip":
            buf = faults.flip_byte(buf)
    if native.crc32(buf) != crc0:
        state["crc"] += 1
        if guard == "quarantine":
            raise ChunkIntegrityError(
                "crc", f"staged chunk of {len(buf)} bytes")
        return np.frombuffer(buf, dtype=chunk.dtype)
    return chunk


def _leaf_meta(leaf) -> Tuple[Any, np.dtype, Tuple[int, ...]]:
    """(leaf, dtype, shape) WITHOUT materializing device arrays on the
    host (np.asarray on a jax.Array is a full device→host copy)."""
    dtype = getattr(leaf, "dtype", None)
    shape = getattr(leaf, "shape", None)
    if dtype is None or shape is None:
        leaf = np.asarray(leaf)  # python scalar / list leaf
        dtype, shape = leaf.dtype, leaf.shape
    return leaf, np.dtype(dtype), tuple(shape)


def psum_pytree(diff: Any, compress: Any = False,
                phases: dict = None,  # type: ignore[assignment]
                chunk_mb: Optional[float] = None,
                prefer_device: bool = False,
                feedback: Optional[ErrorFeedback] = None,
                topology: Any = None,
                guard: str = "off") -> Any:
    """AllReduce ``diff`` (pytree of arrays/scalars) across the process
    world. Every process must call this with an identically-shaped
    pytree and the same ``compress`` and ``chunk_mb`` (both ride the
    collective mixer's prepare signature).

    ``compress`` picks the wire mode (``off | bf16 | int8``; the
    historical bool still works, True == "bf16"). ``bf16`` ships f32
    leaves as bf16 — half the wire bytes per round at ~3 decimal digits
    of diff precision; additive diffs tolerate it because put_diff folds
    into an f32 master. The cast runs ON DEVICE in the ship stage (a
    host astype here once cost ~740 ms per d24 round). ``int8`` runs
    chunked f32 leaves through the block-quantized all-reduce
    (``_quant_chunk_fn``): ~3.94x fewer wire bytes; pass a persistent
    ``feedback`` (ErrorFeedback) so the quantization error is carried
    into the next round's diff and the averaged model stays unbiased —
    without it every round's bias walks the weights. Small leaves and
    non-f32 dtypes stay exact under int8.

    ``prefer_device=True`` returns totals as device ``jax.Array``s
    (no readback) — callers whose put_diff is jitted consume them
    directly; the default returns host numpy arrays.

    ``phases`` (optional dict) is filled with this call's per-phase wall
    times so mix rounds log like the reference's per-round time+bytes
    (linear_mixer.cpp:553-558): ``cast_ms`` (host cast — held at ~0 by
    design: compress casts/quantization run on device), ``ship_ms``
    (host→device placement + the on-device wire prep; the first chunk is
    measured with an explicit completion barrier so async dispatch
    cannot leak transfer time into ``reduce_ms``), ``reduce_ms`` (the
    jitted collectives — wire and fold are ONE fused program, unlike the
    reference's get_diff/fold/put_diff), ``readback_ms`` (device→host;
    in the pipelined stream this is the time BLOCKED on arrival, i.e.
    whatever the overlap didn't hide), ``payload_mb`` (post-compress
    wire bytes this replica contributes, including quantization scales
    and block padding under int8), ``wire_mb`` ==
    ``wire_mb_ring_model`` (2(n-1)/n × payload — ring-allreduce bytes
    per replica; exact for the int8 scatter+gather this module
    implements, a model for the runtime-picked psum), ``quant`` (the
    resolved wire mode, stamped into flight-recorder round records),
    plus the pipeline accounting: ``chunks``, ``chunk_mb``, and
    ``overlap_ms_saved`` — a DIRECT measurement of the overlap win:
    the reader thread's readback blocking that elapsed while the main
    thread was still shipping/reducing later chunks (minus the tail it
    did wait for) — wait the serial path would have eaten inline.

    ``topology`` (None | HostTopology | "auto" | "HxM") switches the
    CHUNKED stream into the two-tier hierarchical reduce over the
    (host, local) mesh: intra-host psum first, one chunk copy per host
    on the inter-host wire (see the module docstring). Small leaves
    keep the flat batched collective — their wire share is noise and
    the stream shape must stay a pure function of the plan inputs.
    Hierarchical phases additionally report ``intra_ms``/``inter_ms``
    (per-tier; barriered exactly for chunk 0, dispatch-side for the
    pipelined remainder, like ``reduce_ms``), ``topo`` (the NxM
    signature, "flat" otherwise) and ``wire_bytes_per_host`` (ring-
    model inter-host bytes one HOST ships per round — the scaling
    gate's key: flat grows it with devices, hierarchical holds it at
    the host count)."""
    # model-integrity screens (ISSUE 15; ``guard`` mirrors the owning
    # mixer's --mix-guard): when not "off", every host-staged wire
    # chunk is CRC32-bracketed through the ``mix.wire.corrupt`` chaos
    # window (_crc_stage) and every reduced total gets a finite screen
    # (on device for prefer_device consumers — flags fold into ONE
    # scalar readback at round end, so the pipeline never stalls per
    # chunk). ``quarantine`` raises ChunkIntegrityError — BEFORE the
    # feedback commit, so a poisoned round leaves the EF residuals of
    # the last good round intact; ``warn`` stamps ``finite_ok`` /
    # ``crc_mismatch_chunks`` / ``nonfinite_chunks`` into ``phases``
    # and proceeds.
    guard = (guard or "off").lower() if isinstance(guard, str) else \
        ("quarantine" if guard else "off")
    if guard not in ("off", "warn", "quarantine"):
        raise ValueError(f"unknown guard mode {guard!r}")
    mode = _norm_compress(compress)
    # a 1x1 (trivial) topology still rides the hier code path — the
    # world-1 parity gates prove that path bit-identical to flat
    topo = _norm_topology(topology)
    mesh = _world_mesh()
    n = mesh.shape["replica"]
    me = jax.local_devices()[0]
    sharding = NamedSharding(mesh, P("replica"))
    hier = topo is not None
    if hier:
        mesh2 = host_mesh(topo)
        sharding2 = NamedSharding(mesh2, _SPEC2)
        my_devs = [d for row in topo.grid for d in row
                   if d.process_index == me.process_index]
        if not my_devs:
            raise ValueError(
                f"topology {topo.signature} includes no device of "
                f"process {me.process_index}")
    if chunk_mb is None:
        chunk_mb = DEFAULT_CHUNK_MB
    chunk_bytes = max(1, int(chunk_mb * 2**20))
    block = QUANT_BLOCK

    leaves, treedef = jax.tree_util.tree_flatten(diff)
    if phases is not None:
        phases.update(cast_ms=0.0, ship_ms=0.0, reduce_ms=0.0,
                      readback_ms=0.0, intra_ms=0.0, inter_ms=0.0,
                      payload_mb=0.0,
                      wire_mb=0.0, wire_mb_ring_model=0.0,
                      wire_bytes_per_host=0, chunks=0,
                      chunk_mb=round(chunk_bytes / 2**20, 2),
                      overlap_ms_saved=0.0, dispatch_gate_ms=0.0,
                      quant=mode,
                      guard=guard, finite_ok=True,
                      crc_mismatch_chunks=0, nonfinite_chunks=0,
                      topo=topo.signature if hier else "flat")
    if not leaves:
        return diff

    metas = []
    for leaf in leaves:
        leaf, dtype, shape = _leaf_meta(leaf)
        if dtype in _64BIT:
            # a silent downcast would make the collective path less exact
            # than the RPC fold; callers gate these to the fallback
            # (collective_mixer._signature marks them unsupported)
            raise ValueError(
                f"64-bit leaf dtype {dtype} cannot ride the "
                "collective exactly; use the RPC mix path")
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        metas.append((leaf, dtype, shape, size))

    # the collective sequence must be identical on every process, so the
    # small/chunked split keys on (size, chunk_bytes) alone — where a
    # leaf lives only changes local staging, never the stream shape
    small_idx = [i for i, (_, dt, _, s) in enumerate(metas)
                 if s * dt.itemsize < chunk_bytes]
    big_idx = [i for i, (_, dt, _, s) in enumerate(metas)
               if s * dt.itemsize >= chunk_bytes]
    big_set = set(big_idx)

    def _chunk_elems(dtype: np.dtype) -> int:
        ce = max(1, chunk_bytes // dtype.itemsize)
        if hier:
            # every lane owns a 1/M segment of the chunk; int8
            # additionally block-quantizes per host-ring sub-segment
            quantum = topo.locals
            if mode == "int8" and dtype == np.float32:
                quantum = topo.locals * topo.hosts * block
        elif mode == "int8" and dtype == np.float32:
            # every replica-owned segment must block-quantize: pad the
            # chunk up to a multiple of world * QUANT_BLOCK (zeros
            # quantize to zeros; sliced off at collection)
            quantum = n * block
        else:
            quantum = 1
        return ((ce + quantum - 1) // quantum) * quantum

    # wire accounting per leaf: bf16 halves every f32 leaf; int8
    # quantizes only the CHUNKED f32 leaves (small leaves and non-f32
    # dtypes ship exact) at 1 byte/elem + one f32 scale per block,
    # counting the block padding the stream actually ships. Chunked
    # and small bytes are tracked apart: in hierarchical mode only the
    # chunked stream rides the two-tier reduce (small leaves stay on
    # the flat world ring), so their ring models differ.
    nbytes = big_bytes = small_bytes = 0
    for i, (_, dtype, _, size) in enumerate(metas):
        wire = size * dtype.itemsize
        if dtype == np.float32:
            if mode == "bf16":
                wire //= 2
            elif mode == "int8" and i in big_set:
                ce = _chunk_elems(dtype)
                shipped = ((size + ce - 1) // ce) * ce
                wire = shipped + (shipped // block) * 4
        nbytes += wire
        if i in big_set:
            big_bytes += wire
        else:
            small_bytes += wire

    out: List[Any] = [None] * len(metas)
    t_ship = t_reduce = t_readback = t_cast = 0.0

    # dispatch gate (ISSUE 11): held from the first collective dispatch
    # of this round to the last — released BEFORE the readback drain so
    # a back-to-back round (psum_pytree_start) ships/reduces its early
    # chunks while this round's device→host traffic completes. The wait
    # itself is reported as dispatch_gate_ms.
    gate = _Gate()
    gate_wait = gate.acquire()
    try:
        return _reduce_under_gate(
            gate, gate_wait, metas, small_idx, big_idx, big_set, out,
            treedef, mesh, n, me, sharding, hier, topo, chunk_bytes,
            block, mode, prefer_device, feedback, phases,
            _chunk_elems, nbytes, big_bytes, small_bytes,
            t_ship, t_reduce, t_readback, t_cast, guard)
    finally:
        gate.release()


def _reduce_under_gate(gate, gate_wait, metas, small_idx, big_idx,
                       big_set, out, treedef, mesh, n, me, sharding,
                       hier, topo, chunk_bytes, block, mode,
                       prefer_device, feedback, phases, _chunk_elems,
                       nbytes, big_bytes, small_bytes,
                       t_ship, t_reduce, t_readback, t_cast,
                       guard="off"):
    """The collective body of one round, entered with the dispatch gate
    held (see psum_pytree). Split out so the gate's safety-net release
    wraps every exit path without re-indenting the stream logic."""
    if hier:
        mesh2 = host_mesh(topo)
        sharding2 = NamedSharding(mesh2, _SPEC2)
        my_devs = [d for row in topo.grid for d in row
                   if d.process_index == me.process_index]

    # integrity state (ISSUE 15): per-round CRC/finite tallies, plus
    # the deferred on-device finite flags (one readback at round end)
    integ = {"crc": 0, "nonfinite": 0}
    finite_flags: List[Any] = []

    def _screen_total(arr, on_device: bool) -> None:
        """Queue (device) or run (host) the finite screen of one
        reduced total; tallies fold in _finite_verdict."""
        if guard == "off":
            return
        if on_device:
            f = _finite_flag(arr)
            if f is not None:
                finite_flags.append(f)
        elif np.issubdtype(np.dtype(arr.dtype), np.floating) and \
                not np.isfinite(arr).all():
            integ["nonfinite"] += 1

    def _finite_verdict() -> None:
        """Fold the deferred device flags (one blocking readback for
        the whole round), stamp the phases, and — in quarantine mode —
        refuse a poisoned round before anything consumes it (and, for
        int8, before the error-feedback residuals commit)."""
        if guard == "off":
            return
        if finite_flags:
            integ["nonfinite"] += sum(
                0 if bool(f) else 1 for f in finite_flags)
            finite_flags.clear()
        if phases is not None:
            phases.update(crc_mismatch_chunks=integ["crc"],
                          nonfinite_chunks=integ["nonfinite"],
                          finite_ok=not (integ["crc"]
                                         or integ["nonfinite"]))
        if guard == "quarantine" and integ["nonfinite"]:
            raise ChunkIntegrityError(
                "nonfinite", f"{integ['nonfinite']} reduced chunk(s) "
                "carry NaN/Inf")

    # -- small leaves: one batched collective (the pre-pipeline shape) --
    if small_idx:
        t0 = time.perf_counter()
        arrs = []
        for i in small_idx:
            leaf, dtype, shape, _ = metas[i]
            if isinstance(leaf, jax.Array):
                shard = jax.device_put(leaf[None, ...], me)
            else:
                shard = jax.device_put(np.asarray(leaf)[None, ...], me)
            arrs.append(jax.make_array_from_single_device_arrays(
                (n,) + shape, sharding, [shard]))
        # device_put is async: block before timestamping so transfer
        # cost does not leak into reduce_ms
        jax.block_until_ready(arrs)
        t1 = time.perf_counter()
        stacked = tuple(arrs)
        shapes = tuple(a.shape for a in arrs)
        dtypes = tuple(str(a.dtype) for a in arrs)
        s_treedef = jax.tree_util.tree_structure(stacked)
        total = _reduce_tree_fn(mesh, s_treedef, shapes, dtypes,
                                mode == "bf16")(stacked)
        total = jax.block_until_ready(total)
        t2 = time.perf_counter()
        for i, tot in zip(small_idx, total):
            local = tot.addressable_shards[0].data
            out[i] = local if prefer_device else np.asarray(local)
            _screen_total(out[i], on_device=prefer_device)
        t3 = time.perf_counter()
        t_ship += t1 - t0
        t_reduce += t2 - t1
        t_readback += t3 - t2
    if not big_idx:
        # small-only round: every collective completed above — the next
        # round may dispatch while we assemble/return
        gate.release()
        _finite_verdict()

    # -- big leaves: chunked double-buffered stream ---------------------
    n_chunks = 0
    overlap_saved = 0.0
    quant_rounds = 0
    if big_idx:
        stream: List[Tuple[int, int, int]] = []  # (leaf idx, start, stop)
        flats: Dict[int, Any] = {}
        chunks_out: Dict[int, List[Any]] = {}
        for i in big_idx:
            leaf, dtype, shape, size = metas[i]
            celems = _chunk_elems(dtype)
            if isinstance(leaf, jax.Array):
                flats[i] = leaf.reshape(-1)  # device op, zero staging
            else:
                flats[i] = np.ascontiguousarray(
                    np.asarray(leaf)).reshape(-1)
            chunks_out[i] = []
            for start in range(0, size, celems):
                stream.append((i, start, min(start + celems, size)))
        n_chunks = len(stream)

        # error-feedback state: reset on any plan change (shape, chunk,
        # world, topology, or block skew would misalign the carried
        # residuals); fresh residuals commit only after the whole
        # stream succeeds
        plan_key = (str(treedef),
                    tuple((str(m[1]), m[2]) for m in metas),
                    chunk_bytes, n, block,
                    topo.signature if hier else "flat")
        if feedback is not None and feedback.key != plan_key:
            feedback.reset()
        pending_c: Dict[Tuple[int, int], Any] = {}
        pending_t: Dict[Tuple[int, int], Any] = {}
        tiers = {"intra": 0.0, "inter": 0.0}

        def _quantized(i: int) -> bool:
            return mode == "int8" and metas[i][1] == np.float32

        def _hier_global(per_dev_shape, dtype_str, data=None):
            """A (hosts, locals, *per_dev_shape) global array from this
            process's addressable lanes: ``data`` on its FIRST grid
            device (a process contributes its chunk exactly once),
            cached zeros on the rest — the intra psum folds every
            host's real lanes and ignores the zero ones."""
            shards = []
            for j, d in enumerate(my_devs):
                if j == 0 and data is not None:
                    shards.append(jax.device_put(data[None, None], d))
                else:
                    shards.append(
                        _dev_zeros(d, (1, 1) + per_dev_shape, dtype_str))
            return jax.make_array_from_single_device_arrays(
                (topo.hosts, topo.locals) + per_dev_shape, sharding2,
                shards)

        def ship(entry):
            i, start, stop = entry
            dtype = metas[i][1]
            celems = _chunk_elems(dtype)
            flat = flats[i]
            chunk = flat[start:stop]
            pad = celems - (stop - start)
            if isinstance(flat, jax.Array):
                # device-resident leaf: zero host staging, so there is
                # no host window to checksum — the runtime owns the
                # buffer end to end; the contribution's finite screen
                # runs ON DEVICE instead (deferred flag, one readback
                # per round)
                if pad:
                    chunk = jnp.concatenate(
                        [chunk, jnp.zeros(pad, chunk.dtype)])
                if guard != "off":
                    f = _finite_flag(chunk)
                    if f is not None:
                        finite_flags.append(f)
            else:
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros(pad, chunk.dtype)])
                if guard != "off":
                    # CRC-bracketed staging + the mix.wire.corrupt
                    # chaos window (ISSUE 15)
                    chunk = _crc_stage(chunk, integ, guard)
            if hier:
                # the wire prep (bf16 cast / int8 quantization) happens
                # INSIDE the collective, after the exact intra-host
                # fold — the ship stage only places this process's
                # contribution on its representative lane
                return _hier_global((celems,), str(chunk.dtype),
                                    data=chunk), celems
            shard = jax.device_put(chunk[None, :], me)
            if mode == "bf16" and dtype == np.float32:
                # the wire prep IS the ship path: cast on device right
                # after placement, so the collective body reduces
                # pre-cast bf16 and the host never stages an astype
                shard = _cast_fn("bfloat16")(shard)
            elif _quantized(i):
                # quantize-on-device before the ship: the collective's
                # staged inputs are int8 + per-block scales (4x less),
                # and this replica's contribution residual (error
                # feedback chain 1) is computed here, locally — it
                # never enters the collective
                key = (i, start)
                rc = feedback.contrib.get(key) \
                    if feedback is not None else None
                if rc is None:
                    rc = jax.device_put(
                        np.zeros((1, celems), np.float32), me)
                q, scales, new_rc = _quant_ship_fn(celems, block)(shard, rc)
                pending_c[key] = new_rc
                gq = jax.make_array_from_single_device_arrays(
                    (n, celems), sharding, [q])
                gs = jax.make_array_from_single_device_arrays(
                    (n, celems // block), sharding, [scales])
                return (gq, gs), celems
            return jax.make_array_from_single_device_arrays(
                (n, celems), sharding, [shard]), celems

        def _total_residual(entry, celems):
            """The owned-segment requant residual (error feedback chain
            2) as a [world, seg] array — zeros on the first round /
            after a plan change. Stored globals are reused as-is: their
            sharding matches the freshly built (equal) mesh."""
            rt = feedback.total.get((entry[0], entry[1])) \
                if feedback is not None else None
            if rt is None:
                seg = celems // n
                rt = jax.make_array_from_single_device_arrays(
                    (n, seg), sharding,
                    [jax.device_put(np.zeros((1, seg), np.float32), me)])
            return rt

        def reduce_chunk(entry, stacked, celems, barrier=False):
            i = entry[0]
            dtype = metas[i][1]
            if hier:
                return _reduce_chunk_hier(entry, stacked, celems, barrier)
            if _quantized(i):
                gq, gs = stacked
                rt = _total_residual(entry, celems)
                reduced, new_rt = _quant_reduce_fn(
                    mesh, celems, block)(gq, gs, rt)
                pending_t[(i, entry[1])] = new_rt
                return reduced
            dt = ("bfloat16" if mode == "bf16" and dtype == np.float32
                  else str(dtype))
            return _reduce_chunk_fn(mesh, celems, dt,
                                    mode == "bf16")(stacked)

        def _reduce_chunk_hier(entry, stacked, celems, barrier):
            """Two dispatches per chunk — intra-host fold, then the
            inter-host ring + rebuild — so the tiers are timed apart.
            Chunk 0 (``barrier``) blocks between them: its ``intra_ms``
            / ``inter_ms`` are real wall splits; the pipelined
            remainder adds dispatch-side time only (same honesty
            contract as ``reduce_ms``)."""
            i, start, _stop = entry
            key = (i, start)
            t0 = time.perf_counter()
            if _quantized(i):
                seg = celems // topo.locals
                rc = feedback.contrib.get(key) \
                    if feedback is not None else None
                if rc is None:
                    rc = _hier_global((seg,), "float32")
                intra_fn, inter_fn = _hier_quant_fns(mesh2, celems, block)
                q, scales, new_rc = intra_fn(stacked, rc)
                if barrier:
                    jax.block_until_ready((q, scales))
                t1 = time.perf_counter()
                pending_c[key] = new_rc
                rt = feedback.total.get(key) \
                    if feedback is not None else None
                if rt is None:
                    rt = _hier_global((seg // topo.hosts,), "float32")
                reduced, new_rt = inter_fn(q, scales, rt)
                if barrier:
                    jax.block_until_ready(reduced)
                t2 = time.perf_counter()
                pending_t[key] = new_rt
            else:
                dtype = metas[i][1]
                intra_fn, inter_fn = _hier_fns(mesh2, celems,
                                               str(dtype), mode)
                segs = intra_fn(stacked)
                if barrier:
                    jax.block_until_ready(segs)
                t1 = time.perf_counter()
                reduced = inter_fn(segs)
                if barrier:
                    jax.block_until_ready(reduced)
                t2 = time.perf_counter()
            tiers["intra"] += t1 - t0
            tiers["inter"] += t2 - t1
            return reduced

        def collect(entry, reduced):
            i, start, stop = entry
            if prefer_device:
                local = reduced.addressable_shards[0].data
                _screen_total(local, on_device=True)
                chunks_out[i].append(
                    local[: stop - start] if stop - start != local.shape[0]
                    else local)
            else:
                # fully replicated → np.asarray is legal and reuses the
                # copy_to_host_async started right after dispatch
                host = np.asarray(reduced)
                _screen_total(host, on_device=False)
                chunks_out[i].append(host[: stop - start])

        # chunk 0 runs serially with explicit barriers: the block after
        # ship keeps transfer cost out of reduce_ms (the old path's
        # async device_put leaked it there), and its psum doubles as the
        # round's entry barrier — it completes only once EVERY process
        # has entered, so cross-process entry skew lands here, visibly,
        # instead of smearing over the stream
        tp0 = time.perf_counter()
        stacked, celems = ship(stream[0])
        jax.block_until_ready(stacked)
        tp1 = time.perf_counter()
        reduced = reduce_chunk(stream[0], stacked, celems, barrier=True)
        reduced = jax.block_until_ready(reduced)
        tp2 = time.perf_counter()
        collect(stream[0], reduced)
        tp3 = time.perf_counter()
        t_ship += tp1 - tp0
        t_reduce += tp2 - tp1
        t_readback += tp3 - tp2
        pipelined = stream[1:]

        # pipelined remainder. The main thread only DISPATCHES ship +
        # psum; a dedicated reader thread blocks on each chunk's arrival
        # and collects it, so D2H(k−1) genuinely overlaps H2D(k+1) and
        # psum(k) — both sides spend their time in GIL-releasing runtime
        # calls. A semaphore bounds chunks in flight to the double
        # buffer; the reader's blocked time that elapsed WHILE the main
        # thread was still streaming is readback latency the serial path
        # would have eaten inline — that measured quantity (minus the
        # tail the main thread did wait for at join) is overlap_ms_saved.
        import threading

        slots = threading.Semaphore(_PIPELINE_DEPTH + 1)
        handoff: deque = deque()
        ready = threading.Semaphore(0)
        state = {"blocked": 0.0, "error": None}

        def _reader():
            while True:
                ready.acquire()
                item = handoff.popleft()
                if item is None:
                    return
                tb = time.perf_counter()
                try:
                    collect(*item)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    state["error"] = e
                state["blocked"] += time.perf_counter() - tb
                slots.release()

        tpipe0 = time.perf_counter()
        reader = threading.Thread(target=_reader, name="mix-readback",
                                  daemon=True)
        reader.start()
        try:
            for entry in pipelined:
                slots.acquire()
                if state["error"] is not None:
                    break
                t0 = time.perf_counter()
                stacked, celems = ship(entry)
                t1 = time.perf_counter()
                reduced = reduce_chunk(entry, stacked, celems)
                if not prefer_device:
                    try:
                        reduced.copy_to_host_async()
                    except Exception:  # noqa: BLE001 — no async D2H here
                        pass
                t2 = time.perf_counter()
                t_ship += t1 - t0
                t_reduce += t2 - t1
                handoff.append((entry, reduced))
                ready.release()
        finally:
            dispatch_done = time.perf_counter()
            handoff.append(None)
            ready.release()
            # every collective of this round is dispatched (or the
            # round is dead): open the gate BEFORE draining readback so
            # the next round's ship/reduce overlaps it
            gate.release()
            reader.join()
        if state["error"] is not None:
            raise state["error"]
        t_join = time.perf_counter() - dispatch_done
        t_readback += t_join
        pipe_wall = time.perf_counter() - tpipe0
        # measured, not modeled: readback blocking that ran concurrently
        # with the main thread's ship/reduce stream (clamped at 0 for
        # the degenerate no-pipelined-chunks case)
        overlap_saved = max(0.0, state["blocked"] - t_join)

        # integrity verdict BEFORE the residual commit: a poisoned
        # round must leave the EF state of the last good round intact
        # (quarantine raises here; warn stamps and proceeds)
        _finite_verdict()

        # the whole stream completed: NOW the carried residuals advance
        # (an exception above leaves the last successful round's state)
        if feedback is not None and (pending_c or pending_t):
            feedback.contrib.update(pending_c)
            feedback.total.update(pending_t)
            feedback.key = plan_key
            feedback.rounds += 1
            quant_rounds = 1

        for i in big_idx:
            _, dtype, shape, size = metas[i]
            t3 = time.perf_counter()
            parts = chunks_out[i]
            if prefer_device:
                total = parts[0] if len(parts) == 1 else \
                    jnp.concatenate(parts)
                out[i] = total.reshape(shape)
            else:
                total = parts[0] if len(parts) == 1 else \
                    np.concatenate(parts)
                out[i] = total.reshape(shape)
            t_readback += time.perf_counter() - t3

    # ring-model wire accounting. Flat: every process ships the full
    # post-compress payload around the world ring — bytes per host grow
    # with the device count. Hierarchical: the chunked stream crosses
    # the inter-host wire ONCE per host (2(H-1)/H of the chunked
    # payload, spread over the M lanes), small leaves stay on the world
    # ring — bytes per host stay proportional to hosts.
    if hier:
        h_ring = 2 * (topo.hosts - 1) / topo.hosts
        w_ring = 2 * (n - 1) / n
        wire_per_host = big_bytes * h_ring + \
            topo.locals * small_bytes * w_ring
        wire_mb = (big_bytes * h_ring / topo.locals +
                   small_bytes * w_ring) / 2**20
    else:
        wire_mb = nbytes * 2 * (n - 1) / n / 2**20
        wire_per_host = nbytes * 2 * (n - 1) / n
    if phases is not None:
        # per-tier split: in flat mode EVERY reduced byte crosses the
        # process boundary, so the whole reduce is the inter tier
        intra_s = tiers["intra"] if big_idx and hier else 0.0
        inter_s = tiers["inter"] if big_idx and hier else t_reduce
        phases.update(
            cast_ms=round(t_cast * 1e3, 2),
            ship_ms=round(t_ship * 1e3, 2),
            reduce_ms=round(t_reduce * 1e3, 2),
            readback_ms=round(t_readback * 1e3, 2),
            intra_ms=round(intra_s * 1e3, 2),
            inter_ms=round(inter_s * 1e3, 2),
            payload_mb=round(nbytes / 2**20, 2),
            wire_mb=round(wire_mb, 2),
            wire_mb_ring_model=round(wire_mb, 2),
            wire_bytes_per_host=int(wire_per_host),
            chunks=n_chunks,
            chunk_mb=round(chunk_bytes / 2**20, 2),
            overlap_ms_saved=round(overlap_saved * 1e3, 2),
            dispatch_gate_ms=round(gate_wait * 1e3, 2),
            quant=mode,
            topo=topo.signature if hier else "flat",
        )
        if quant_rounds:
            phases["ef_rounds"] = feedback.rounds
    return jax.tree_util.tree_unflatten(treedef, out)


def world_size() -> int:
    return jax.process_count()
