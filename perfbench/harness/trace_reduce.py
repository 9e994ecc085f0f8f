"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
union and idle share, device time per compiled program, the operations
that took most time, and the longest idle gaps by what the host was
doing. Run as a child process pinned to the CPU (it imports jax only for
``jax.profiler.ProfileData``):

    python3 trace_reduce.py <dir or file> [<program-name-substring> ...]

prints one JSON object. :func:`reduce_planes` is the arithmetic, on plain
lists, so that tests can check it by hand."""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 1e5                        # 0.1 ms
TOP = 10


def union_ns(intervals: Iterable[Tuple[float, float]]
             ) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def self_ns(events: Sequence[Event]) -> List[float]:
    """Each event's own time on one line: its duration less that of the
    events nested directly inside it (a ``while`` holds its body's
    operations), in the order given."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: List[int] = []
    for i in order:
        _n, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack and start + dur <= events[stack[-1]][1] + events[stack[-1]][2]:
            own[stack[-1]] -= min(dur, own[stack[-1]])
        stack.append(i)
    return own


class _Host:
    """The host plane's events, indexed for "what was open at ``t``"."""

    def __init__(self, events: Sequence[Event]) -> None:
        events = [e for e in events if e[2] > 0]
        self.names = [e[0] for e in events]
        self.start = np.array([e[1] for e in events], np.float64)
        self.dur = np.array([e[2] for e in events], np.float64)

    def covering(self, t: float) -> str:
        """The innermost (shortest) host event open at ``t``."""
        if not self.names:
            return "no_host_event"
        open_at = (self.start <= t) & (t < self.start + self.dur)
        if not open_at.any():
            return "no_host_event"
        return self.names[int(np.where(open_at, self.dur, np.inf).argmin())]


def reduce_planes(device: Dict[str, Dict[str, List[Event]]],
                  host: Sequence[Event], programs: Sequence[str]
                  ) -> Dict[str, Any]:
    """``device``: {plane: {line: events}} of the device planes; ``host``:
    the host plane's events. The window of a device is from its first
    operation's start to its last one's end."""
    host_index = _Host(host)
    per_device = []
    op_seconds: Dict[str, float] = {}
    gap_seconds: Dict[str, float] = {}
    prog = {p: {"events": 0, "seconds": 0.0} for p in programs}
    modules: Dict[str, Dict[str, float]] = {}
    for plane, lines in sorted(device.items()):
        ops = [e for e in lines.get(OPS_LINE, []) if e[2] > 0]
        if not ops:
            continue
        busy, merged = union_ns((s, s + d) for _n, s, d in ops)
        t0, t1 = merged[0][0], merged[-1][1]
        per_device.append({"plane": plane, "busy_s": busy / 1e9,
                           "window_s": (t1 - t0) / 1e9, "ops": len(ops)})
        for (name, _s, _d), own in zip(ops, self_ns(ops)):
            op_seconds[name] = op_seconds.get(name, 0.0) + own / 1e9
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
        short = sum(g for g, _s, _e in gaps if g < SHORT_GAP_NS)
        if short:
            gap_seconds["gaps_under_0.1_ms"] = \
                gap_seconds.get("gaps_under_0.1_ms", 0.0) + short / 1e9
        long_gaps = sorted((g for g in gaps if g[0] >= SHORT_GAP_NS),
                           reverse=True)
        for g, s, e in long_gaps[:200]:
            what = host_index.covering((s + e) / 2.0)
            gap_seconds[what] = gap_seconds.get(what, 0.0) + g / 1e9
        rest = sum(g for g, _s, _e in long_gaps[200:])
        if rest:
            gap_seconds["further_gaps"] = \
                gap_seconds.get("further_gaps", 0.0) + rest / 1e9
        for name, _s, d in lines.get(MODULES_LINE, []):
            m = modules.setdefault(name.split("(")[0],
                                   {"events": 0, "seconds": 0.0})
            m["events"] += 1
            m["seconds"] += d / 1e9
            for p in programs:
                if p in name:
                    prog[p]["events"] += 1
                    prog[p]["seconds"] += d / 1e9
    n = len(per_device)
    top = lambda m: [[k[:64], v] for k, v in  # noqa: E731
                     sorted(m.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device) / n if n else 0.0,
        "window_s": sum(d["window_s"] for d in per_device) / n if n else 0.0,
        "programs": prog,
        "modules": modules,
        "device_ops": top(op_seconds),
        "idle_gaps": top(gap_seconds),
    }


def read_xplane(path: str) -> Tuple[Dict[str, Dict[str, List[Event]]],
                                    List[Event], List[str]]:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    names: List[str] = []
    for plane in data.planes:
        names.append(plane.name)
        if plane.name.startswith("/device:"):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events)
    return device, host, names


def find_xplanes(path: str) -> List[str]:
    if os.path.isfile(path):
        return [path]
    return sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_path(path: str, programs: Sequence[str]) -> Dict[str, Any]:
    """All the traces under ``path`` (one per server) reduced together:
    busy and window are means over the devices, the rest are sums."""
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    planes: List[str] = []
    files = find_xplanes(path)
    for i, f in enumerate(files):
        d, h, names = read_xplane(f)
        planes.extend(names)
        # host gaps are attributed within one server's trace only when it
        # is the only one; several servers' clocks are not aligned here
        if i == 0:
            host = h
        for plane, lines in d.items():
            device[f"{i}:{plane}"] = lines
    out = reduce_planes(device, host if len(files) == 1 else [], programs)
    out["planes"] = sorted(set(planes))
    out["lines"] = sorted({ln for lines in device.values() for ln in lines})
    return out


if __name__ == "__main__":
    print(json.dumps(reduce_path(sys.argv[1], sys.argv[2:])))
