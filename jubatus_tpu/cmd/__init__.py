"""Ops CLIs (≙ jubatus/server/cmd/ + jubavisor/, SURVEY.md §2.6).

- ``jubactl``    — cluster control: start/stop via supervisors, save/load/
                   status via the servers themselves (cmd/jubactl.cpp).
- ``jubaconfig`` — validate + write/read/delete/list engine configs in the
                   coordination store (cmd/jubaconfig.cpp).
- ``jubaconv``   — offline json→datum→fv conversion debugger
                   (cmd/jubaconv.cpp).
- ``jubavisor``  — per-host process supervisor daemon, RPC-controlled
                   (jubavisor/jubavisor.{hpp,cpp}).

Each module exposes ``main(argv)`` and runs via
``python -m jubatus_tpu.cmd.<tool>``. The coordinator location comes from
``-z`` or the ``ZK``/``JUBATUS_COORDINATOR`` environment variables (the
reference honors ``ZK``, jubactl.cpp:121-127).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

#: TPU_PROCESS_BOUNDS of a host whose chips are split one to a process
_HOST_BOUNDS = {1: "1,1,1", 4: "2,2,1", 8: "2,4,1"}


def resolve_coordinator(flag: str) -> Optional[str]:
    """-z flag, else $JUBATUS_COORDINATOR, else $ZK (reference order)."""
    return flag or os.environ.get("JUBATUS_COORDINATOR") or os.environ.get("ZK")


def compute_on_cpu() -> None:
    """For a CLI that builds a driver or opens a checkpoint only to
    validate or inspect it (jubaconfig, jubadump): a chip belongs to one
    process, and that process is the server, so this one computes on the
    CPU. Call before anything initialises a jax backend."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def tpu_process_env(index: int, ports: Sequence[int]) -> Dict[str, str]:
    """The variables that make libtpu give process ``index`` of
    ``len(ports)`` on one host exactly one chip (chip ``index``) while
    the processes still form one TPU topology, so collectives between
    them cross the interconnect. ``ports`` are free local ports, one per
    process, for the TPU runtime's own mesh (not the RPC ports). Set the
    result in the child's environment before it imports jax. A TPU
    host's own environment describes ONE process that owns every chip,
    under libtpu's older names (``TPU_HOST_BOUNDS``,
    ``TPU_CHIPS_PER_HOST_BOUNDS``, ``TPU_WORKER_ID``), so both spellings
    are set. Established on a v5e 2x2 host (CHANGES.md, PR 21)."""
    count = len(ports)
    if count not in _HOST_BOUNDS:
        raise ValueError(f"no one-chip-per-process layout known for "
                         f"{count} processes on a host "
                         f"(known: {sorted(_HOST_BOUNDS)})")
    return {
        "TPU_VISIBLE_DEVICES": str(index),
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _HOST_BOUNDS[count],
        "TPU_HOST_BOUNDS": _HOST_BOUNDS[count],
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[index]),
        "CLOUD_TPU_TASK_ID": str(index),
        "TPU_WORKER_ID": str(index),
    }
