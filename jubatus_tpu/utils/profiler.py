"""Continuous profiling plane (ISSUE 8).

The observability stack so far answers *which* RPC, trace, or mix round
was slow (PRs 2/4/7); this module answers *where the time went inside
the process*. Three capture modes:

- **Always-on sampling profiler** (:class:`SamplingProfiler`): one
  daemon thread per process samples every thread's stack via
  ``sys._current_frames()`` at ``--profile-hz`` (default ~67 Hz, a
  deliberately non-round rate so the sampler never phase-locks with
  periodic work; 0 = fully off, no thread). Samples fold into
  collapsed-stack keys (``root;caller;...;leaf``, one
  ``file.py:function`` token per frame — no line numbers, so hot
  functions aggregate instead of exploding key cardinality) in a
  BOUNDED store: at most ``max_stacks`` distinct keys per bucket,
  overflow folding into ``(other)`` so counts stay honest under churn.
  The store is windowed like utils/timeseries.py — the live bucket
  rotates into a bounded ring every ``bucket_s`` seconds, so
  ``profile(seconds=N)`` is an exact fold of the last N seconds, not a
  process-lifetime smear. Served by the ``get_profile`` RPC (proxies
  broadcast + fold backends with their own samples), rendered by
  ``jubactl -c profile`` (top-N self/cumulative table, or ``--folded``
  collapsed-stack output consumable by flamegraph.pl / speedscope) and
  dumped by ``jubadump --profile``.
- **On-demand device capture** (:class:`DeviceCapture`): the
  ``profile_device`` RPC wraps ``jax.profiler.trace()`` for a bounded
  duration into a capped artifacts directory (``--profile-dir``), so
  XLA compile/execute/HBM time on a real TPU is one
  ``jubactl -c profile --device`` away. The Python tracer is off in a
  capture: its host plane holds the program's own spans (the tracing
  Registry opens a profiler annotation per span) and the runtime's
  events, on the device's clock. Old captures are pruned — the
  artifacts dir can never grow without bound.
- **Tail-triggered snapshots**: when utils/slowlog.py sees K breaches
  of the same span inside a window (``--profile-trigger-*``), it calls
  :meth:`SamplingProfiler.tail_snapshot`, which folds the last few
  seconds of samples into a bounded snapshot ring stamped with the
  offending trace_ids — closing the loop from PR 4's "this request was
  slow" to "this stack made it slow".

Overhead is a first-class number: the sampler accounts its own wall
time (``profiler.overhead_ms_per_s`` gauge) and bench_serving.py's
``run_profiling_overhead`` A/B holds the e2e cost under the
observability plane's <2% budget.
"""

from __future__ import annotations

import logging
import os
import shutil
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from jubatus_tpu.utils.tracing import Registry

log = logging.getLogger(__name__)

#: default sampling rate; ~67 Hz ≈ 15 ms period — coarse enough to stay
#: invisible next to a multi-ms RPC, fine enough that a 1-second stall
#: lands ~67 samples
DEFAULT_HZ = 67.0
#: distinct collapsed-stack keys retained per bucket before overflow
#: folds into ``(other)``
DEFAULT_MAX_STACKS = 512
#: live-bucket rotation period (the window resolution of ``profile()``)
DEFAULT_BUCKET_S = 5.0
#: ring depth: 10 minutes of history at the 5 s bucket
DEFAULT_RING = 120
#: tail-triggered snapshot ring depth
DEFAULT_SNAPSHOTS = 16
#: seconds of samples a tail-triggered snapshot folds
SNAPSHOT_WINDOW_S = 5.0
#: frames deeper than this truncate (a runaway recursion must not mint
#: unbounded keys)
MAX_DEPTH = 64

#: overflow key for stacks beyond the per-bucket bound
OTHER_KEY = "(other)"


#: code object -> "file.py:func" token. Memoized because the token is
#: rebuilt for EVERY frame of EVERY thread at the sampling rate — the
#: basename+format work dominated the raw sample cost. Keyed by the
#: code object itself (keeps it alive; the population is bounded by the
#: program's code, and the overflow clear below backstops pathological
#: dynamic-code generators). Plain dict: GIL-atomic get/set.
_CODE_TOKENS: Dict[Any, str] = {}
_CODE_TOKENS_CAP = 8192


def _code_token(co: Any) -> str:
    tok = _CODE_TOKENS.get(co)
    if tok is None:
        if len(_CODE_TOKENS) >= _CODE_TOKENS_CAP:
            _CODE_TOKENS.clear()
        tok = _CODE_TOKENS[co] = \
            f"{os.path.basename(co.co_filename)}:{co.co_name}"
    return tok


def collapse_frame(frame: Any, thread_name: str = "") -> str:
    """One thread's stack as a collapsed key: ``root;...;leaf`` with
    ``file.py:function`` tokens (basename only, NO line numbers — hot
    functions aggregate; the key space stays bounded by the code, not
    the data). The thread name roots the stack so worker pools and the
    accept loop separate in a flamegraph."""
    parts: List[str] = []
    f = frame
    while f is not None and len(parts) < MAX_DEPTH:
        parts.append(_code_token(f.f_code))
        f = f.f_back
    parts.reverse()
    if thread_name:
        parts.insert(0, f"thread:{thread_name}")
    return ";".join(parts) if parts else "(empty)"


class SamplingProfiler:
    """Per-process always-on stack sampler with a bounded, windowed
    aggregate store. One instance per server/proxy, bound to its tracing
    Registry (gauges/counters land there)."""

    def __init__(self, registry: Optional[Registry] = None,
                 hz: float = DEFAULT_HZ,
                 max_stacks: int = DEFAULT_MAX_STACKS,
                 bucket_s: float = DEFAULT_BUCKET_S,
                 ring_capacity: int = DEFAULT_RING,
                 snapshot_capacity: int = DEFAULT_SNAPSHOTS) -> None:
        self.registry = registry
        self.hz = max(0.0, float(hz))
        self.max_stacks = max(8, int(max_stacks))
        self.bucket_s = max(0.5, float(bucket_s))
        self._lock = threading.Lock()
        #: live bucket: collapsed key -> sample count
        self._current: Dict[str, int] = {}
        self._current_start = time.time()  # wall-clock
        #: rotated buckets, oldest-first: (t_start, t_end, {key: count})
        self._ring: deque = deque(maxlen=max(2, int(ring_capacity)))
        #: tail-triggered snapshots (see tail_snapshot)
        self._snapshots: deque = deque(maxlen=max(1, int(snapshot_capacity)))
        self._samples = 0
        self._truncated = 0
        self._snapshots_taken = 0
        self._sample_s = 0.0          # cumulative wall time spent sampling
        self._bucket_samples = 0      # since last rotation (for gauges)
        self._bucket_sample_s = 0.0
        self._thread_names: Dict[int, str] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def enabled(self) -> bool:
        return self.hz > 0

    # -- sampling ------------------------------------------------------------
    def sample_once(self) -> int:
        """Take one sample of every live thread (except the sampler
        itself); returns the number of stacks folded. Opens a profiler
        annotation (no histogram), so that its holds on the GIL stay
        visible in a device capture."""
        if self.registry is None:
            return self._sample()
        with self.registry.annotation("profiler.sample_once"):
            return self._sample()

    def _sample(self) -> int:
        t0 = time.perf_counter()
        me = threading.get_ident()
        frames = sys._current_frames()
        keys: List[str] = []
        fresh_names = None
        for ident, frame in frames.items():
            if ident == me:
                continue
            name = self._thread_names.get(ident)
            if name is None:
                if fresh_names is None:
                    fresh_names = {t.ident: t.name
                                   for t in threading.enumerate()}
                    self._thread_names = fresh_names
                name = fresh_names.get(ident, "?")
            keys.append(collapse_frame(frame, name))
        del frames
        now = time.time()  # wall-clock: windows compare across nodes
        cost = time.perf_counter() - t0
        with self._lock:
            self._samples += 1
            self._bucket_samples += 1
            self._sample_s += cost
            self._bucket_sample_s += cost
            for k in keys:
                self._ingest_locked(k)
            rotated = None
            if now - self._current_start >= self.bucket_s:
                rotated = self._rotate_locked(now)
        if rotated is not None:
            self._publish(rotated)
        return len(keys)

    def _ingest_locked(self, key: str) -> None:
        cur = self._current
        n = cur.get(key)
        if n is not None:
            cur[key] = n + 1
        elif len(cur) < self.max_stacks:
            cur[key] = 1
        else:
            cur[OTHER_KEY] = cur.get(OTHER_KEY, 0) + 1
            self._truncated += 1

    def _rotate_locked(self, now: float) -> Dict[str, Any]:
        """Push the live bucket into the ring; returns the gauge doc the
        caller publishes OUTSIDE the lock."""
        self._ring.append((self._current_start, now, self._current))
        doc = {
            "stacks": len(self._current),
            "samples": self._bucket_samples,
            "wall_s": max(now - self._current_start, 1e-9),
            "sample_s": self._bucket_sample_s,
        }
        self._current = {}
        self._current_start = now
        self._bucket_samples = 0
        self._bucket_sample_s = 0.0
        return doc

    def _publish(self, doc: Dict[str, Any]) -> None:
        reg = self.registry
        if reg is None:
            return
        reg.count("profiler.samples", int(doc["samples"]))
        reg.gauge("profiler.hz", self.hz)
        reg.gauge("profiler.stacks", doc["stacks"])
        reg.gauge("profiler.overhead_ms_per_s",
                  round(doc["sample_s"] / doc["wall_s"] * 1e3, 3))

    # -- views ---------------------------------------------------------------
    def profile(self, seconds: float = 0.0) -> Dict[str, Any]:
        """Wire-safe folded view over the last ``seconds`` (0 = every
        retained bucket): collapsed stacks, sampler stats, and the
        tail-triggered snapshot ring."""
        now = time.time()  # wall-clock
        with self._lock:
            entries: List[Tuple[float, float, Dict[str, int]]] = \
                list(self._ring)
            entries.append((self._current_start, now, dict(self._current)))
            snapshots = [dict(s) for s in self._snapshots]
            stats = self._stats_locked()
        start = now - float(seconds) if seconds and seconds > 0 else 0.0
        folded: Dict[str, int] = {}
        t_oldest = now
        for t0, t1, bucket in entries:
            if t1 < start:
                continue
            t_oldest = min(t_oldest, t0)
            for k, v in bucket.items():
                folded[k] = folded.get(k, 0) + v
        return {"folded": folded,
                "ts_start": round(max(start, t_oldest), 3),
                "ts_end": round(now, 3),
                "stats": stats,
                "snapshots": snapshots}

    def tail_snapshot(self, span: str,
                      trace_ids: Optional[List[str]] = None
                      ) -> Optional[Dict[str, Any]]:
        """Fold the last ``SNAPSHOT_WINDOW_S`` seconds of samples into a
        snapshot stamped with the breaching span + trace_ids and ring
        it (utils/slowlog.py's breach trigger calls this). No-op when
        the sampler is off — there is nothing to snapshot."""
        if not self.enabled:
            return None
        doc = self.profile(SNAPSHOT_WINDOW_S)
        rec = {"span": str(span),
               "trace_ids": [str(t) for t in (trace_ids or []) if t][:8],
               "ts": round(time.time(), 3),  # wall-clock
               "window_s": SNAPSHOT_WINDOW_S,
               "samples": sum(doc["folded"].values()),
               "folded": doc["folded"]}
        with self._lock:
            self._snapshots.append(rec)
            self._snapshots_taken += 1
        if self.registry is not None:
            self.registry.count("profiler.snapshots")
        return rec

    def snapshots(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(s) for s in self._snapshots]

    def _stats_locked(self) -> Dict[str, Any]:
        return {"enabled": self.enabled,
                "hz": self.hz,
                "samples": self._samples,
                "truncated": self._truncated,
                "ring_buckets": len(self._ring),
                "bucket_s": self.bucket_s,
                "current_stacks": len(self._current),
                "max_stacks": self.max_stacks,
                "snapshots_taken": self._snapshots_taken,
                "sample_ms_total": round(self._sample_s * 1e3, 3)}

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return self._stats_locked()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="stack-profiler")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    def _loop(self) -> None:
        period = 1.0 / self.hz
        while not self._stop.wait(period):
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 — the sampler must survive
                log.debug("stack sample failed", exc_info=True)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._snapshots.clear()
            self._current = {}
            self._current_start = time.time()  # wall-clock
            self._samples = self._truncated = self._snapshots_taken = 0
            self._sample_s = 0.0
            self._bucket_samples = 0
            self._bucket_sample_s = 0.0


# -- cross-node folding + rendering -------------------------------------------


def fold_profiles(docs) -> Dict[str, int]:
    """Fold N ``profile()`` docs (or bare folded dicts) into one
    collapsed-stack map — bucket-wise sums, deterministic regardless of
    input order (same property as tracing.merge_snapshots)."""
    out: Dict[str, int] = {}
    for doc in docs:
        folded = doc.get("folded", doc) if isinstance(doc, dict) else {}
        for k, v in (folded or {}).items():
            out[str(k)] = out.get(str(k), 0) + int(v)
    return out


def folded_lines(folded: Dict[str, int]) -> List[str]:
    """flamegraph.pl / speedscope input: one ``stack count`` line per
    collapsed key, sorted for determinism."""
    return [f"{k} {v}" for k, v in sorted(folded.items())]


def top_table(folded: Dict[str, int]) -> List[Dict[str, Any]]:
    """Per-frame self/cumulative sample counts from a folded map,
    hottest-self first. ``cum`` counts each stack once per frame even
    under recursion (set-dedup within the stack)."""
    self_c: Dict[str, int] = {}
    cum: Dict[str, int] = {}
    total = 0
    for stack, n in folded.items():
        n = int(n)
        total += n
        frames = stack.split(";")
        leaf = frames[-1]
        self_c[leaf] = self_c.get(leaf, 0) + n
        for f in set(frames):
            cum[f] = cum.get(f, 0) + n
    rows = []
    for frame in cum:
        s = self_c.get(frame, 0)
        rows.append({
            "frame": frame,
            "self": s,
            "cum": cum[frame],
            "self_pct": round(s / total * 100, 2) if total else 0.0,
            "cum_pct": round(cum[frame] / total * 100, 2) if total else 0.0,
        })
    rows.sort(key=lambda r: (-r["self"], -r["cum"], r["frame"]))
    return rows


def render_top(folded: Dict[str, int], top: int = 30) -> str:
    """The ``jubactl -c profile`` table: top-N frames by self time."""
    total = sum(int(v) for v in folded.values())
    lines = [f"{'self%':>7} {'cum%':>7} {'self':>8} {'cum':>8}  frame"]
    for row in top_table(folded)[:max(1, int(top))]:
        lines.append(f"{row['self_pct']:>6.2f}% {row['cum_pct']:>6.2f}% "
                     f"{row['self']:>8} {row['cum']:>8}  {row['frame']}")
    lines.append(f"total: {total} sample(s), "
                 f"{len(folded)} distinct stack(s)")
    return "\n".join(lines)


# -- on-demand device capture -------------------------------------------------


class DeviceCapture:
    """Bounded jax.profiler capture directory: ``capture(seconds)``
    traces XLA compile/execute (TensorBoard-viewable; on TPU: HBM +
    per-op device time) into a fresh subdirectory, pruning the oldest
    captures past ``max_captures`` so the artifacts dir is capped."""

    def __init__(self, base_dir: str, max_captures: int = 8) -> None:
        self.base_dir = str(base_dir)
        self.max_captures = max(1, int(max_captures))
        self._lock = threading.Lock()
        self._captures = 0

    #: a single capture may not run longer than this (the RPC blocks
    #: one worker for the duration)
    MAX_SECONDS = 60.0

    def capture(self, seconds: float) -> Dict[str, Any]:
        """Trace the device for ``seconds`` (clamped to
        [0.05, MAX_SECONDS]); returns {"artifact": path, ...} or
        {"error": ...} — a missing/broken profiler backend degrades to
        a structured error, never an exception on the RPC plane."""
        seconds = min(max(float(seconds), 0.05), self.MAX_SECONDS)
        if not self._lock.acquire(blocking=False):
            return {"error": "capture already in progress",
                    "dir": self.base_dir}
        try:
            self._captures += 1
            stamp = time.strftime("%Y%m%d-%H%M%S")  # wall-clock
            path = os.path.join(self.base_dir,
                                f"device-{stamp}-{self._captures:03d}")
            try:
                os.makedirs(path, exist_ok=True)
                import jax

                # no hook on every Python call of every thread: the host
                # plane holds the program's own annotations (Registry.span)
                # and the runtime's events; Python-frame questions are the
                # sampling profiler's (get_profile)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                with jax.profiler.trace(path, profiler_options=options):
                    time.sleep(seconds)
            except Exception as e:  # noqa: BLE001 — backend quirks degrade
                log.warning("device capture failed", exc_info=True)
                shutil.rmtree(path, ignore_errors=True)
                return {"error": f"{type(e).__name__}: {e}",
                        "dir": self.base_dir}
            self._prune()
            return {"artifact": path, "seconds": seconds,
                    "bytes": _tree_bytes(path)}
        finally:
            self._lock.release()

    def list(self) -> Dict[str, Any]:
        """Existing capture artifacts, oldest-first."""
        arts = []
        try:
            names = sorted(os.listdir(self.base_dir))
        except OSError:
            names = []
        for name in names:
            p = os.path.join(self.base_dir, name)
            if os.path.isdir(p):
                arts.append({"name": name, "path": p,
                             "bytes": _tree_bytes(p)})
        return {"dir": self.base_dir, "artifacts": arts,
                "max_captures": self.max_captures}

    def _prune(self) -> None:
        try:
            names = sorted(n for n in os.listdir(self.base_dir)
                           if os.path.isdir(os.path.join(self.base_dir, n)))
        except OSError:
            return
        for name in names[:-self.max_captures]:
            shutil.rmtree(os.path.join(self.base_dir, name),
                          ignore_errors=True)


def _tree_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total
