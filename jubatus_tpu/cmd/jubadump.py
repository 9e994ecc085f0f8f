"""jubadump — convert saved model files to JSON (≙ the reference's
jubadump tool, man/en/jubadump.1: "a tool to convert Jubatus model files
saved using save RPC to JSON").

Reads the checkpoint envelope (framework/save_load.py — same layout as
the reference's 48-byte header + system container + versioned user data,
save_load.cpp:45-158) WITHOUT constructing a driver, so any model file
can be inspected offline:

    python -m jubatus_tpu.cmd.jubadump -i /tmp/model.jubatus
    python -m jubatus_tpu.cmd.jubadump -i model.jubatus --summary

The reference supports a subset of engines; this version dumps every
engine's file because all drivers share one envelope + msgpack pytree
layout. ``--summary`` replaces large arrays with shape/dtype/stat
digests (the full dump of a 2^20-feature table is rarely what you want
in a terminal).
"""

from __future__ import annotations

import argparse
import json
import sys
import zlib
from typing import Any

import numpy as np

from jubatus_tpu.framework.save_load import (
    _HEADER,
    FORMAT_VERSION,
    MAGIC,
    SaveLoadError,
)
from jubatus_tpu.utils.serialization import unpack_obj

SUMMARY_ARRAY_LIMIT = 64  # arrays up to this many elements dump in full


def _jsonable(obj: Any, summary: bool) -> Any:
    if isinstance(obj, np.ndarray):
        if summary and obj.size > SUMMARY_ARRAY_LIMIT:
            finite = obj[np.isfinite(obj)] if obj.dtype.kind == "f" else obj
            stats = {}
            if finite.size and obj.dtype.kind in "fiu":
                stats = {
                    "min": float(np.min(finite)),
                    "max": float(np.max(finite)),
                    "nonzero": int(np.count_nonzero(obj)),
                }
            return {"__array__": {"dtype": obj.dtype.str,
                                  "shape": list(obj.shape), **stats}}
        return obj.tolist()
    if isinstance(obj, bytes):
        try:
            return obj.decode("utf-8")
        except UnicodeDecodeError:
            return {"__bytes__": obj.hex()}
    if isinstance(obj, dict):
        return {str(k): _jsonable(v, summary) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v, summary) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


def dump_file(path: str, *, summary: bool = False,
              skip_user_data: bool = False) -> dict:
    """Parse + validate one model file into a JSON-ready dict. A directory
    is treated as a sharded checkpoint (framework/sharded_checkpoint.py):
    the system sidecar plus per-array shape/dtype/partition metadata —
    array bytes are never read (they may span a pod's worth of hosts)."""
    import os

    if os.path.isdir(path):
        # offline metadata inspection needs no accelerator, but orbax
        # queries jax's default backend — and on a serving host that
        # would open the chip the server holds
        from jubatus_tpu.cmd import compute_on_cpu

        compute_on_cpu()
        from jubatus_tpu.framework.sharded_checkpoint import (
            checkpoint_metadata,
        )

        out = checkpoint_metadata(path)
        system = out.get("system")
        if isinstance(system, dict) and isinstance(system.get("config"), str):
            try:
                out["system"] = dict(system,
                                     config=json.loads(system["config"]))
            except json.JSONDecodeError:
                pass
        return _jsonable(out, summary)

    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < _HEADER.size:
        raise ValueError(f"{path}: truncated header ({len(raw)} bytes)")
    magic, fmt, vmaj, vmin, vmaint, crc, ssize, usize = \
        _HEADER.unpack_from(raw)
    if magic != MAGIC:
        raise ValueError(f"{path}: bad magic {magic!r} (not a model file)")
    body = raw[_HEADER.size:]
    crc_actual = zlib.crc32(body) & 0xFFFFFFFF
    out = {
        "header": {
            "format_version": fmt,
            "jubatus_version": f"{vmaj}.{vmin}.{vmaint}",
            "crc32": f"{crc:08x}",
            "crc32_ok": crc_actual == crc,
            "system_data_size": ssize,
            "user_data_size": usize,
        },
    }
    if fmt != FORMAT_VERSION:
        out["header"]["warning"] = f"unsupported format version {fmt}"
        return out
    if len(body) != ssize + usize:
        out["header"]["warning"] = (
            f"size mismatch: header says {ssize}+{usize}, file has {len(body)}")
        return out
    # corrupt bodies (the very case crc32_ok flags) must never lose the
    # header report to an unpack traceback
    try:
        system = unpack_obj(body[:ssize])
    except Exception as e:  # noqa: BLE001 — msgpack raises various types
        out["system_error"] = f"cannot decode system container: {e}"
        return out
    if isinstance(system, dict) and isinstance(system.get("config"), str):
        try:  # present the config as structured JSON, not an escaped string
            system = dict(system, config=json.loads(system["config"]))
        except json.JSONDecodeError:
            pass
    out["system"] = _jsonable(system, summary)
    if usize == 0:
        # sharded-checkpoint sidecars (system.jubatus) carry no user data;
        # the model lives in the orbax state/ tree next to them
        out["user_data"] = None
    elif not skip_user_data:
        try:
            user_version, user_data = unpack_obj(body[ssize:ssize + usize])
        except Exception as e:  # noqa: BLE001
            out["user_data_error"] = f"cannot decode user data: {e}"
            return out
        out["user_data_version"] = user_version
        out["user_data"] = _jsonable(user_data, summary)
    return out


def _live_call(target: str, method: str, flag: str, name: str,
               *extra: Any, timeout: float = 10.0) -> Any:
    """One RPC against a live HOST:PORT target (the --mix-history /
    --slow-log live-dump paths share the parse + call shape)."""
    from jubatus_tpu.rpc.client import RpcClient

    host, _, port = target.rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"{flag} wants HOST:PORT, got {target!r}")
    with RpcClient(host, int(port), timeout=timeout) as c:
        return _jsonable(c.call(method, name, *extra), False)


def dump_mix_history(target: str, name: str = "",
                     timeout: float = 10.0) -> list:
    """Pull a live server's mix-round flight records (``get_mix_history``
    RPC — the bounded ring framework/mixer.py keeps per mixer)."""
    return _live_call(target, "get_mix_history", "--mix-history", name,
                      timeout=timeout)


def dump_slow_log(target: str, name: str = "",
                  timeout: float = 10.0) -> dict:
    """Pull a live server's (or proxy's) slow-request ring — the
    tail-based capture of utils/slowlog.py, keyed by node name. Against
    a proxy the reply also folds in every backend's ring."""
    return _live_call(target, "get_slow_log", "--slow-log", name,
                      timeout=timeout)


def dump_profile(target: str, name: str = "", seconds: float = 0.0,
                 timeout: float = 10.0) -> dict:
    """Pull a live server's (or proxy's) folded stack profile — the
    always-on sampler of utils/profiler.py (collapsed stacks + sampler
    stats + tail-triggered snapshots), keyed by node name. Against a
    proxy the reply also folds in every backend's samples. ``seconds``
    bounds the window (0 = every retained bucket)."""
    return _live_call(target, "get_profile", "--profile", name,
                      float(seconds), timeout=timeout)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="jubadump",
        description="convert saved jubatus_tpu model files to JSON, or "
                    "dump a live server's mix-round flight records")
    p.add_argument("-i", "--input", metavar="FILE")
    p.add_argument("--summary", action="store_true",
                   help="digest large arrays instead of dumping them")
    p.add_argument("--no-user-data", action="store_true",
                   help="header + system container only")
    p.add_argument("--mix-history", metavar="HOST:PORT",
                   help="dump the mix flight recorder of a LIVE server "
                        "(get_mix_history RPC) instead of reading a file")
    p.add_argument("--slow-log", metavar="HOST:PORT", dest="slow_log",
                   help="dump the slow-request ring of a LIVE server or "
                        "proxy (get_slow_log RPC): tail-based capture of "
                        "requests at/above the --slowlog-quantile of "
                        "their own latency histogram")
    p.add_argument("--profile", metavar="HOST:PORT", dest="profile",
                   help="dump the folded stack profile of a LIVE server "
                        "or proxy (get_profile RPC): collapsed stacks "
                        "from the always-on sampler, sampler stats, and "
                        "tail-triggered snapshots")
    p.add_argument("--seconds", type=float, default=0.0,
                   help="[--profile] window to fold (seconds; 0 = every "
                        "retained bucket)")
    p.add_argument("-n", "--name", default="",
                   help="[--mix-history/--slow-log/--profile] cluster "
                        "name to pass the RPC")
    ns = p.parse_args(argv)
    if sum(map(bool, (ns.input, ns.mix_history, ns.slow_log,
                      ns.profile))) != 1:
        print("exactly one of -i FILE, --mix-history HOST:PORT, "
              "--slow-log HOST:PORT, or --profile HOST:PORT required",
              file=sys.stderr)
        return 1
    try:
        if ns.mix_history:
            out: Any = dump_mix_history(ns.mix_history, ns.name)
        elif ns.slow_log:
            out = dump_slow_log(ns.slow_log, ns.name)
        elif ns.profile:
            out = dump_profile(ns.profile, ns.name, ns.seconds)
        else:
            out = dump_file(ns.input, summary=ns.summary,
                            skip_user_data=ns.no_user_data)
    except (OSError, ValueError, SaveLoadError) as e:
        print(str(e), file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 — RPC failures print, not raise
        print(f"live dump failed: {e}", file=sys.stderr)
        return 1
    json.dump(out, sys.stdout, indent=2)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
