"""What several per-layer readers share: differences of the servers'
counters and spans over the window, a compiled program's device time in
the traced interval, the table of peaks. A
reader that finds nothing to read gets ``None`` and returns it."""

from __future__ import annotations

from typing import Any, Optional, Tuple

from . import needed, stats


def counter(run: Any, key: str) -> float:
    """A ``get_status`` counter's gain over the window, all servers."""
    return sum(stats.counter_delta(s0, s1, key)
               for s0, s1 in zip(run.status0, run.status1))


def span(run: Any, name: str) -> Tuple[float, float]:
    """(count, total ms) a span gained over the window, all servers."""
    n = ms = 0.0
    for s0, s1 in zip(run.status0, run.status1):
        dn, dms = stats.span_delta(s0, s1, name)
        n += dn
        ms += dms
    return n, ms


def rows_per_flush(run: Any, queue: str) -> Optional[float]:
    """Rows per flush of one coalescer over the window, from its own
    ``item_count`` and ``flush_count``."""
    flushes = counter(run, f"microbatch.{queue}.flush_count")
    if flushes <= 0:
        return None
    return counter(run, f"microbatch.{queue}.item_count") / flushes


def outside_server_ms(run: Any, method: str) -> Optional[float]:
    """Client mean minus the server's ``trace.rpc.<method>`` mean over the
    window: time on the wire, in the transport and in the generator."""
    lat = stats.latencies_ms(run.window(method))
    n, ms = span(run, f"rpc.{method}")
    if not lat or n <= 0:
        return None
    return sum(lat) / len(lat) - ms / n


def program_ms(run: Any, which: str) -> Optional[float]:
    """Device milliseconds per execution of the configuration's ``which``
    program (``train``, ``classify``) in the traced interval."""
    if run.trace is None or run.rehearsal:
        return None
    p = run.trace["programs"].get(run.config["programs"][which])
    return p["seconds"] * 1e3 / p["events"] if p and p["events"] else None


def hbm_roofline_pct(run: Any, which: str, queue: str, needed_bytes: Any
                     ) -> Optional[float]:
    """The bytes one flush of ``queue`` needs (``needed_bytes(rows, feats,
    labels)``) over the chip's peak HBM rate, over the program's time."""
    ms = program_ms(run, which)
    rows = rows_per_flush(run, queue)
    if ms is None or rows is None:
        return None
    peak = run.peaks["by_device_kind"][run.device["kind"]]["hbm_bytes_per_s"]
    need = needed_bytes(rows, run.config["features_per_row"],
                        run.config["live_labels"])
    return needed.roofline_share_pct(need, ms / 1e3, peak)


def idle_share_pct(run: Any) -> Optional[float]:
    """1 minus busy over the traced interval, mean over the chips."""
    if run.trace is None or run.rehearsal:
        return None
    shares = [100.0 * (1.0 - d["busy_s"] / d["window_s"])
              for d in run.trace["devices"] if d["window_s"] > 0]
    return sum(shares) / len(shares) if shares else None
