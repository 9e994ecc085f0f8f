#!/usr/bin/env python3
"""Diff two BENCH_*.json rounds and gate key-metric regressions (ISSUE 8).

Every perf PR so far proved its win by hand-reading two JSON files; this
is the mechanical version — the perf trajectory's regression gate:

    python tools/bench_compare.py BENCH_r04.json BENCH_r05.json
    python tools/bench_compare.py --glob 'BENCH_r*.json'   # latest two
    python tools/bench_compare.py old.json new.json \
        --tolerance 0.05 --key-tolerance collective_round_ms_nproc4_d24=0.15

Inputs may be any of the repo's bench shapes: the round envelope
(``{"parsed": {"extra": {...}}}``), the full capture
(``{"extra": {...}, "value": ...}``), or a flat ``{key: number}`` dict
(bench_serving output) — numeric keys are flattened out of
all of them.

Regression direction is inferred per key:

- **higher is better** — throughput (``*_per_sec``, ``*samples_per_sec``),
  ``*_speedup``, engagement ``*_fraction``s;
- **lower is better** — latencies (``*_ms``/``*_ms_*``), overhead/cost
  ``*_ratio``s, ``*_wire_mb*``, ``*drift*``, error/timeout counts;
- **boolean gates** — ``*_ok`` / ``*_target`` flipping true→false is a
  regression regardless of tolerance;
- keys matching neither pattern are reported informationally and never
  gate (a new key or a removed key is also information, not a failure).

A key regresses when it moves beyond its tolerance (default
``--tolerance 0.05`` = 5%, overridable per key) in the bad direction.
Exit status: 0 clean, 1 regressions found, 2 usage/input error.
"""

from __future__ import annotations

import argparse
import glob as globlib
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_TOLERANCE = 0.05

#: key patterns whose larger values are better (checked before _LOWER:
#: a wire REDUCTION factor beats the _per_host substring it contains).
#: ``_capacity_per_replica`` covers the autoscaling plane (ISSUE 12):
#: steady-state examples/s each serving replica absorbs — shrinkage
#: means the fleet needs more replicas for the same traffic.
#: ``_quarantined`` covers the model-integrity plane (ISSUE 15): the
#: poison drill arms a known poisoner, so quarantined counts falling
#: means the guard stopped catching it — a regression exactly like a
#: throughput drop (its companion drift/recovery keys are down-good
#: via the _LOWER patterns).
#: ``_recall_at_`` covers the ANN tier (ISSUE 16): recall@k of the IVF
#: approximate top-k against the exact scan — any fall means the index
#: started returning wrong neighbors, the one regression an ANN tier
#: must never trade for speed. Its build throughput rides the existing
#: ``_per_sec`` pattern (``ann_build_rows_per_sec``).
#: ``_accuracy`` / ``_recall`` cover the data-quality plane (ISSUE 17):
#: prequential accuracy and shadow recall — model quality going DOWN is
#: the regression the whole plane exists to catch.
#: ``_headroom`` covers the usage-attribution plane (ISSUE 19):
#: ``capacity.headroom`` (spare capacity after per-tenant demand) —
#: shrinking headroom at the same offered load means the replica got
#: more expensive to run.
_HIGHER = re.compile(
    r"(_per_sec($|_)|samples_per_sec|_speedup($|_)|_fraction($|_)"
    r"|_reduction($|_)|_capacity_per_replica($|_)|_quarantined($|_)"
    r"|_recall_at_|_accuracy($|_)|_recall($|_)|_headroom($|_))")
#: key patterns whose smaller values are better. ``_per_host`` covers
#: the hierarchical-mix scaling plane (ISSUE 9): wire bytes each host
#: ships per round — the quantity the two-tier reduce holds down, so
#: growth is a regression exactly like a latency
#: ``rows_lost`` covers the elastic-membership plane (ISSUE 10): rows
#: missing after a join/migrate/drain cycle — any growth is data loss.
#: ``_stall_ms`` / ``_lag_rounds`` cover the async mix plane (ISSUE
#: 11): model-lock stall on the serving path and rounds-behind-master
#: — both down-good (`_stall_ms` already matches `_ms`, listed for the
#: record; `_lag_rounds` needs its own pattern)
#: ``_recovery_s`` / ``_violation_s`` cover the autoscaling plane
#: (ISSUE 12): flash-onset-to-recovered wall time and seconds spent in
#: SLO violation — growth in either means the control loop got slower
#: at absorbing a traffic step.
#: ``_us`` covers the event plane (ISSUE 14): per-emit microseconds
#: (``e2e_event_emit_us``) — a hot-path cost, down-good like any
#: latency.
#: ``_drift_score`` / ``_psi`` cover the data-quality plane (ISSUE 17):
#: PSI drift between reference and live windows — on an unshifted
#: stream any growth means a false drift alarm (the bare ``drift``
#: pattern already matches ``_drift_score``; ``_psi`` needs its own).
#: ``_coldstart_to_serving_s`` / ``_model_loss_rows`` cover the durable
#: model plane (ISSUE 18): fleet wall time from first boot to first
#: served answer, and rows the killall drill lost BEYOND the diff-chain
#: tail — growth in the former means recovery got slower, any growth in
#: the latter is durability loss (the contract is zero). The warm-boot
#: wall time rides the existing ``_recovery_s`` pattern
#: (``e2e_warmboot_recovery_s``) and the warm-beats-cold verdict rides
#: ``_ok`` (``e2e_warmboot_beats_cold_ok``).
#: ``_err_frac`` covers the usage-attribution plane (ISSUE 19): the
#: conservation gap between the ledger's accounted CPU/device time and
#: the span plane's process totals
#: (``e2e_usage_attribution_err_frac``) — growth means requests are
#: escaping attribution. The overhead verdicts ride the existing
#: ``_ratio`` pattern (``e2e_usage_overhead_mean_ratio``).
#: ``_converge_rounds`` covers the self-tuning plane (ISSUE 20): mix
#: rounds the perf tuner burned before landing within the regret band
#: of the hand-tuned optimum (``e2e_tune_converge_rounds``) — growth
#: means the search got slower; the regret itself rides ``_ratio``
#: (``e2e_tune_regret_ratio``).
_LOWER = re.compile(
    r"(_ms($|_)|_ratio($|_)|_us($|_)|wire_mb|_per_host($|_)|drift"
    r"|_error(s)?($|_)|_timeouts|_errors_total|_denials|rows_lost"
    r"|_stall_ms($|_)|_lag_rounds($|_)"
    r"|_recovery_s($|_)|_violation_s($|_)|_psi($|_)"
    r"|_coldstart_to_serving_s($|_)|_model_loss_rows($|_)"
    r"|_err_frac($|_)|_converge_rounds($|_))")

#: built-in per-key tolerance defaults (explicit --key-tolerance wins):
#: the nproc16 sweep time-slices 16 gloo processes over however few
#: cores the box has, so its WALL times swing far beyond the 5% default
#: on pure scheduler noise — its wire-byte keys are arithmetic and keep
#: the tight gate
_DEFAULT_KEY_TOL: List[Tuple[re.Pattern, float]] = [
    (re.compile(r"_ms_nproc16($|_)"), 0.30),
    # churn-window quantiles ride kill/boot timing on a shared core:
    # the GATES of record are the error fractions and rows_lost (tight);
    # the churn latency/throughput keys get a loose band
    (re.compile(r"_churn_(p99_inflation_ratio|rpc_.*_ms"
                r"|mixed_samples_per_sec)"), 0.50),
]


def default_tolerance_for(key: str, fallback: float) -> float:
    for pat, tol in _DEFAULT_KEY_TOL:
        if pat.search(key):
            return tol
    return fallback
#: boolean gates: True -> False is a regression
_BOOL_GATE = re.compile(r"(_ok($|_)|_target($|_))")


def flatten(doc: Any, prefix: str = "") -> Dict[str, Any]:
    """Numeric/bool leaves of a bench JSON, flattened. The round
    envelope's ``parsed``/``extra`` nesting collapses WITHOUT a prefix —
    ``extra.e2e_x`` and a flat ``e2e_x`` must compare as the same key
    across bench shapes."""
    out: Dict[str, Any] = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            k = str(k)
            if k in ("parsed", "extra"):
                out.update(flatten(v, prefix))
            elif isinstance(v, dict):
                out.update(flatten(v, f"{prefix}{k}."))
            elif isinstance(v, bool) or isinstance(v, (int, float)):
                out[f"{prefix}{k}"] = v
    return out


def direction(key: str) -> Optional[str]:
    """'higher' | 'lower' | 'bool' | None (ungated)."""
    if _BOOL_GATE.search(key):
        return "bool"
    if _HIGHER.search(key):
        return "higher"
    if _LOWER.search(key):
        return "lower"
    return None


def compare(old: Dict[str, Any], new: Dict[str, Any],
            tolerance: float = DEFAULT_TOLERANCE,
            key_tolerance: Optional[Dict[str, float]] = None
            ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """Diff two flat metric maps; returns (rows, regressions). Each row:
    {key, old, new, change, direction, verdict} — verdict in
    {"ok", "improved", "REGRESSED", "info", "added", "removed"}."""
    key_tolerance = key_tolerance or {}
    rows: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    for key in sorted(set(old) | set(new)):
        o, n = old.get(key), new.get(key)
        if o is None or n is None:
            rows.append({"key": key, "old": o, "new": n, "change": None,
                         "direction": direction(key),
                         "verdict": "added" if o is None else "removed"})
            continue
        d = direction(key)
        tol = key_tolerance.get(key)
        if tol is None:
            tol = default_tolerance_for(key, tolerance)
        if not isinstance(o, (bool, int, float)) \
                or not isinstance(n, (bool, int, float)):
            # defensive: callers may pass unflattened maps with string
            # leaves — those are information, never a gate
            rows.append({"key": key, "old": o, "new": n, "change": None,
                         "direction": None, "verdict": "info"})
            continue
        if d == "bool" or isinstance(o, bool) or isinstance(n, bool):
            verdict = "ok"
            if bool(o) and not bool(n):
                verdict = "REGRESSED"
            elif not bool(o) and bool(n):
                verdict = "improved"
            row = {"key": key, "old": bool(o), "new": bool(n),
                   "change": None, "direction": "bool", "verdict": verdict}
        else:
            o, n = float(o), float(n)
            change = (n - o) / abs(o) if o else (0.0 if n == o else None)
            verdict = "info"
            if change is None and d in ("higher", "lower"):
                # zero baseline, nonzero now: relative change is
                # unbounded, which is the OPPOSITE of ungateable — a
                # loss counter (rows_lost, _model_loss_rows) whose
                # contract is exactly zero must trip on ANY growth
                grew = n > o
                verdict = "REGRESSED" if grew == (d == "lower") \
                    else "improved"
            elif d == "higher":
                verdict = "REGRESSED" if (change is not None
                                          and change < -tol) else \
                    ("improved" if change is not None and change > tol
                     else "ok")
            elif d == "lower":
                verdict = "REGRESSED" if (change is not None
                                          and change > tol) else \
                    ("improved" if change is not None and change < -tol
                     else "ok")
            row = {"key": key, "old": o, "new": n, "change": change,
                   "direction": d, "verdict": verdict}
        rows.append(row)
        if row["verdict"] == "REGRESSED":
            regressions.append(row)
    return rows, regressions


def load_metrics(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return flatten(json.load(f))


def pick_latest_two(pattern: str) -> Tuple[str, str]:
    """(older, newer) by name sort — the repo's rounds are numbered
    (BENCH_r01..), so lexical order IS chronological order; ties or
    exotic names fall back to mtime."""
    paths = sorted(globlib.glob(pattern))
    if len(paths) < 2:
        raise ValueError(
            f"--glob {pattern!r} matched {len(paths)} file(s); need >= 2")
    paths.sort(key=lambda p: (os.path.basename(p), os.path.getmtime(p)))
    return paths[-2], paths[-1]


def _fmt(v: Any) -> str:
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render(rows: List[Dict[str, Any]], old_path: str, new_path: str,
           show_all: bool = False) -> str:
    lines = [f"bench_compare: {old_path} -> {new_path}"]
    shown = 0
    for r in rows:
        if not show_all and r["verdict"] in ("ok", "added", "removed",
                                             "info"):
            continue
        shown += 1
        chg = (f"{r['change'] * 100:+.1f}%" if isinstance(r["change"], float)
               else "-")
        lines.append(f"  {r['verdict']:<10} {r['key']:<52} "
                     f"{_fmt(r['old']):>12} -> {_fmt(r['new']):>12}  {chg}")
    gated = sum(1 for r in rows if r["direction"] is not None
                and r["verdict"] not in ("added", "removed"))
    regressed = sum(1 for r in rows if r["verdict"] == "REGRESSED")
    improved = sum(1 for r in rows if r["verdict"] == "improved")
    lines.append(f"  {gated} gated key(s): {regressed} regressed, "
                 f"{improved} improved, "
                 f"{gated - regressed - improved} within tolerance")
    if not shown and not show_all:
        lines.insert(1, "  (no keys moved beyond tolerance; --all to "
                     "list everything)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_compare",
        description="diff two BENCH_*.json rounds; exit 1 on key-metric "
                    "regressions beyond tolerance")
    p.add_argument("old", nargs="?", help="older round JSON")
    p.add_argument("new", nargs="?", help="newer round JSON")
    p.add_argument("--glob", dest="glob_pat", default="",
                   help="pick the latest two files matching this glob "
                        "instead of naming them (lexical order = round "
                        "order for BENCH_rNN names)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative change beyond which a gated key "
                        "regresses (default 0.05 = 5%%)")
    p.add_argument("--key-tolerance", action="append", default=[],
                   metavar="KEY=FRAC",
                   help="per-key tolerance override (repeatable), e.g. "
                        "collective_round_ms_nproc4_d24=0.15 for a "
                        "noisy key")
    p.add_argument("--all", action="store_true",
                   help="print every compared key, not just movers")
    ns = p.parse_args(argv)
    try:
        if ns.glob_pat:
            old_path, new_path = pick_latest_two(ns.glob_pat)
        elif ns.old and ns.new:
            old_path, new_path = ns.old, ns.new
        else:
            print("need OLD NEW paths or --glob", file=sys.stderr)
            return 2
        key_tol: Dict[str, float] = {}
        for spec in ns.key_tolerance:
            key, _, frac = spec.partition("=")
            if not key or not frac:
                print(f"bad --key-tolerance {spec!r} (want KEY=FRAC)",
                      file=sys.stderr)
                return 2
            key_tol[key] = float(frac)
        old = load_metrics(old_path)
        new = load_metrics(new_path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2
    rows, regressions = compare(old, new, tolerance=ns.tolerance,
                                key_tolerance=key_tol)
    print(render(rows, old_path, new_path, show_all=ns.all))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
