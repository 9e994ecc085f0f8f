"""The arithmetic of the end-to-end metrics, kept with the benchmark.

A rate is all the work answered in the window over all the window's
seconds; a tail is the tail of every call answered in the window."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between order statistics;
    None for no values."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def in_window(records: Sequence[tuple], t0: float, t1: float,
              group: Optional[str] = None) -> List[tuple]:
    """The records whose answer landed in ``[t0, t1)``."""
    return [r for r in records
            if t0 <= r[5] < t1 and (group is None or r[0] == group)]


def rate(work: float, t0: float, t1: float) -> float:
    """Work answered in the window over the window's length: a stall
    inside the window lowers it, since the seconds still count."""
    return work / (t1 - t0)


def latencies_ms(records: Sequence[tuple]) -> List[float]:
    """Answer time minus the time the call was due (for a closed loop,
    the time it was sent)."""
    return [(r[5] - r[3]) * 1e3 for r in records]


def clusters(times: Sequence[float], gap_s: float) -> List[List[int]]:
    """Indices of ``times`` grouped so that a group's members each lie
    within ``gap_s`` of the one before, in order of time. Answers of one
    flush are released together, so a group is a flush."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    out: List[List[int]] = []
    for i in order:
        if out and times[i] - times[out[-1][-1]] <= gap_s:
            out[-1].append(i)
        else:
            out.append([i])
    return out


def flush_histogram(records: Sequence[tuple], rows_per_call: int,
                    gap_s: float) -> Dict[int, int]:
    """{rows: flushes} as the generator sees them (:func:`clusters` of
    the answers' arrival). For printing; the exact mean comes from the
    server's own counters."""
    hist: Dict[int, int] = {}
    for group in clusters([r[5] for r in records], gap_s):
        rows = len(group) * rows_per_call
        hist[rows] = hist.get(rows, 0) + 1
    return dict(sorted(hist.items()))


def span_delta(before: Dict[str, Any], after: Dict[str, Any], span: str
               ) -> Tuple[int, float]:
    """(count, total milliseconds) a span gained between two ``get_status``
    maps, from its ``count`` and ``mean_ms``."""
    def total(st: Dict[str, Any]) -> Tuple[int, float]:
        n = int(st.get(f"trace.{span}.count", 0) or 0)
        return n, n * float(st.get(f"trace.{span}.mean_ms", 0.0) or 0.0)

    n0, ms0 = total(before)
    n1, ms1 = total(after)
    return n1 - n0, ms1 - ms0


def counter_delta(before: Dict[str, Any], after: Dict[str, Any], key: str
                  ) -> float:
    return float(after.get(key, 0) or 0) - float(before.get(key, 0) or 0)
