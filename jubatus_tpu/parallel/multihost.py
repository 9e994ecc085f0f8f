"""Multi-host runtime init — the DCN half of the communication backend
(SURVEY.md §5 "distributed communication backend": control/ingest stays
RPC; the mix plane is XLA collectives over ICI within a slice and DCN
across slices/hosts).

``initialize()`` wraps ``jax.distributed.initialize`` with the
framework's conventions: the coordinator address can come from the same
``-z`` locator servers already carry (the coordination service stores
the JAX coordinator endpoint under /jubatus/jax_coordinator, so only
process 0 needs static config). After init, ``jax.devices()`` spans all
hosts and the existing mesh builders (parallel/mesh.py) and SPMD steps
(parallel/spmd.py) work unchanged — collectives ride ICI within a slice
and DCN across.

Single-host (or already-initialized) calls are no-ops, so servers can
call this unconditionally at boot.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

from jubatus_tpu.coord.base import Coordinator

log = logging.getLogger(__name__)

JAX_COORD_PATH = "/jubatus/jax_coordinator"


def enable_cpu_collectives() -> None:
    """Select the gloo cross-process collectives backend for CPU worlds.

    The CPU backend refuses multiprocess computations outright
    ("Multiprocess computations aren't implemented on the CPU backend")
    unless ``jax_cpu_collectives_implementation`` is switched to gloo
    BEFORE the backend initializes — without it, every CPU-world psum
    raises, members ack failure, and the collective mix silently degrades
    to broken rounds. gloo also carries the collective_permute the int8
    quantized transport's scatter/gather ring rides
    (parallel/collective._quant_chunk_fn), so one switch covers every
    wire mode. Only the CPU backend reads the option: a TPU world's
    collectives ride the interconnect whatever it says."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def collective_capabilities() -> dict:
    """What the initialized runtime can carry for the mix plane — the
    ops-facing answer to "can this member ride --mix-compress int8?".
    Keys: ``backend`` (cpu/tpu/...), ``distributed`` (one jax world
    spans the fleet), ``world`` (process count), ``local_devices``
    (devices THIS process contributes — the intra-host tier the
    hierarchical mix folds before the wire), ``topology`` (the derived
    ``NxM`` two-tier shape, processes x local devices — `jubactl -c
    status`/`watch` show it per member, so a fleet whose tier shapes
    disagree is diagnosable BEFORE its rounds mismatch into the RPC
    fallback), ``quantized_transport`` (the int8 ring's requirements
    are met: every backend this repo targets carries psum +
    collective_permute once the world is up — CPU via gloo, TPU
    natively — so this tracks ``distributed`` or a world of one).
    Surfaced in the collective mixer's get_status."""
    init = jax.distributed.is_initialized()
    world = jax.process_count() if init else 1
    local = len(jax.local_devices())
    backend = jax.default_backend()
    quantized = True
    if backend == "cpu" and world > 1:
        # a CPU world that skipped enable_cpu_collectives() has no
        # cross-process collectives AT ALL — psum and the int8 ring's
        # collective_permute both raise at dispatch
        quantized = jax.config.jax_cpu_collectives_implementation == "gloo"
    return {
        "backend": backend,
        "distributed": init,
        "world": world,
        "local_devices": local,
        "topology": f"{world}x{local}",
        "quantized_transport": quantized,
    }


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    coord: Optional[Coordinator] = None,
    resolve_timeout: float = 60.0,
) -> bool:
    """Join the multi-host JAX runtime. Returns True if distributed init
    ran, False when single-host / already initialized.

    Endpoint resolution order: explicit ``coordinator_address``, then the
    coordination store (process 0 publishes, others poll until
    ``resolve_timeout``), then give up (single-host).

    NOTE: must run before anything initializes the XLA backend — even
    ``jax.process_count()``/``jax.devices()`` would do that, which is why
    the already-initialized check uses ``jax.distributed.is_initialized``.
    """
    if jax.distributed.is_initialized():
        return False
    if not num_processes or num_processes <= 1:
        return False  # single-host: never poll or raise
    if coord is not None:
        if process_id == 0:
            if not coordinator_address:
                raise ValueError("process 0 must pass coordinator_address "
                                 "(its own reachable host:port) to publish")
            publish_endpoint(coord, coordinator_address)  # BEFORE peers join
        elif coordinator_address is None:
            # fleets boot unordered: poll until process 0 publishes.
            # Timing out RAISES — a silent single-host fallback would
            # leave the rest of the fleet hanging in the init barrier.
            import time

            deadline = time.monotonic() + resolve_timeout
            while True:
                raw = coord.read(JAX_COORD_PATH)
                if raw:
                    coordinator_address = raw.decode()
                    break
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"no JAX coordinator endpoint published within "
                        f"{resolve_timeout:.0f}s (is process 0 up?)")
                time.sleep(0.5)
    if not coordinator_address:
        return False
    enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    log.info("joined multi-host runtime: process %d/%d via %s",
             jax.process_index(), jax.process_count(), coordinator_address)
    return True


def publish_endpoint(coord: Coordinator, address: str) -> None:
    """Process 0 publishes the JAX coordinator endpoint for the fleet.
    The node is EPHEMERAL (owned by process 0's coordinator session): a
    crashed fleet's endpoint disappears instead of pointing late-booting
    workers at a dead coordinator from the previous incarnation."""
    coord.remove(JAX_COORD_PATH)
    if not coord.create(JAX_COORD_PATH, address.encode(), ephemeral=True):
        # a silent publish failure would surface as timeouts on every
        # OTHER host — fail here, where the cause is
        raise RuntimeError(
            f"cannot publish JAX coordinator endpoint at {JAX_COORD_PATH} "
            "(stale node owned by another session, or session closed)")
