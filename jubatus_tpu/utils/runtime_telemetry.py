"""Per-process runtime/XLA telemetry sampler (ISSUE 4).

TensorFlow's production experience (Abadi et al., arxiv 1605.08695)
taught that runtime telemetry — memory, compilation — must be
first-class or regressions hide until they page someone. This module is
one daemon thread per process that periodically samples:

- **process**: RSS/VIRT (``/proc/self/statm``), open FDs, thread count,
  GC generation counts + total collections;
- **JAX/XLA signals**: cumulative jit compile count and wall-ms and the
  persistent compilation cache's hits and misses (via ``jax.monitoring``
  listeners — the runtime's own instrumentation, zero polling cost);
  and, only in a process that has initialised a backend of its own
  accord: platform, device kind and count, the devices holding live
  arrays, jit cache size (pjit C++ caches), live ``jax.Array`` count,
  and live and peak device memory when the backend reports them
  (``Device.memory_stats`` — TPU/GPU; CPU returns nothing);
- **forensics depth**: the owning registry's slow-log ring depth.

Every sample lands as gauges in the owning tracing ``Registry``
(``jubatus_runtime_gauge{key=...}`` on ``/metrics``) and in
``status()`` (merged as ``runtime.*`` keys into ``get_status`` and
summarized in ``/healthz``). Sampling never raises: a missing /proc or
an import-less jax just drops keys.

jax.monitoring listeners are registered once per process (they cannot be
unregistered individually) and accumulate into module-level counters, so
any number of samplers/servers in one process read one consistent view.
"""

from __future__ import annotations

import gc
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, Optional

from jubatus_tpu.utils.tracing import Registry

log = logging.getLogger(__name__)

DEFAULT_INTERVAL_SEC = 10.0

# -- jax.monitoring hooks (process-wide, install-once) ------------------------

_jax_lock = threading.Lock()
_jax_hooked = False
_jax_stats: Dict[str, float] = {
    "compile_count": 0.0,   # backend_compile events (actual XLA compiles)
    "compile_ms": 0.0,      # cumulative backend compile wall-ms
    "trace_ms": 0.0,        # cumulative jaxpr trace wall-ms
    "lower_ms": 0.0,        # cumulative jaxpr->MLIR lowering wall-ms
    "cache_hits": 0.0,      # persistent compilation cache: loaded from disk
    "cache_misses": 0.0,    # ... compiled, then written to disk
}

#: jax.monitoring event suffixes -> stat keys (duration events)
_DURATION_EVENTS = {
    "/jax/core/compile/backend_compile_duration": ("compile_ms",
                                                   "compile_count"),
    "/jax/core/compile/jaxpr_trace_duration": ("trace_ms", None),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_ms", None),
}


#: jax.monitoring plain events -> stat keys
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _on_event(event: str, **_kw: Any) -> None:
    key = _COUNT_EVENTS.get(event)
    if key is not None:
        with _jax_lock:
            _jax_stats[key] += 1


def _on_duration(event: str, duration_secs: float, **_kw: Any) -> None:
    keys = _DURATION_EVENTS.get(event)
    if keys is None:
        return
    ms_key, count_key = keys
    with _jax_lock:
        _jax_stats[ms_key] += duration_secs * 1e3
        if count_key is not None:
            _jax_stats[count_key] += 1


def install_jax_hooks() -> bool:
    """Register the jax.monitoring listeners (idempotent). Returns True
    when hooks are active, False when jax/monitoring is unavailable."""
    global _jax_hooked
    with _jax_lock:
        if _jax_hooked:
            return True
    try:
        import jax.monitoring as monitoring
    except Exception:  # noqa: BLE001 — no jax: sampler still serves /proc
        return False
    with _jax_lock:
        if _jax_hooked:
            return True
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _jax_hooked = True
    return True


def jax_compile_stats() -> Dict[str, float]:
    with _jax_lock:
        return dict(_jax_stats)


# -- sample collection --------------------------------------------------------


def _proc_sample() -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    try:
        with open("/proc/self/statm") as f:
            pages = f.read().split()
        page = os.sysconf("SC_PAGE_SIZE")
        out["vms_bytes"] = int(pages[0]) * page
        out["rss_bytes"] = int(pages[1]) * page
    except (OSError, IndexError, ValueError):
        pass
    try:
        out["open_fds"] = len(os.listdir("/proc/self/fd"))
    except OSError:
        pass
    out["threads"] = threading.active_count()
    gen = gc.get_count()
    for i, n in enumerate(gen):
        out[f"gc_gen{i}"] = n
    try:
        out["gc_collections"] = sum(
            s.get("collections", 0) for s in gc.get_stats())
    except Exception:  # noqa: BLE001 — telemetry must never raise
        pass
    return out


def jax_backend_initialized() -> bool:
    """True once THIS process has initialised a jax backend of its own
    accord. Never imports jax and never initialises one: a chip belongs
    to one process, and a proxy or a CLI that opened it by asking would
    take it from the server."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is not None and bridge.backends_are_initialized()


def _jax_sample() -> Dict[str, Any]:
    """JAX signals. The listener counters are always safe to read; the
    device, array and memory figures are read only when
    :func:`jax_backend_initialized` — ``jax.live_arrays()`` and
    ``jax.local_devices()`` would otherwise initialise a backend in a
    process that never computes (proxy, jubactl, jubadump, coordd)."""
    out: Dict[str, Any] = {
        "jax_backend_initialized": jax_backend_initialized()}
    if "jax" not in sys.modules:
        return out
    import jax  # already imported: costs nothing, initialises nothing

    for k, v in jax_compile_stats().items():
        out[f"jax_{k}"] = round(v, 3) if k.endswith("_ms") else int(v)
    out["jax_compilation_cache_dir"] = \
        jax.config.jax_compilation_cache_dir or ""
    if not out["jax_backend_initialized"]:
        return out
    try:
        devs = jax.devices()
        out["jax_platform"] = devs[0].platform
        out["jax_device_kind"] = devs[0].device_kind
        out["jax_device_count"] = len(devs)
        live = jax.live_arrays()
        out["jax_live_arrays"] = len(live)
        # in a server the live arrays are the model state (plus a few
        # temporaries), so this is where the model lives; a collective's
        # global arrays span other processes' devices and are left out
        out["jax_array_devices"] = sorted(
            {str(d) for a in live if a.is_fully_addressable
             for d in a.devices()})
        # live bytes now, and the allocator's high-water mark since the
        # process started: what it handed out between two samples (a
        # flush's inputs, a mix round's buffers). A compiled step's
        # temporaries are in neither: memory_stats() leaves them out.
        # "A device's" bytes are the fullest local device's, which is
        # what fits or does not; a model over several chips also reads
        # their sum. On one chip the two are one number
        stats = [d.memory_stats() or {} for d in jax.local_devices()
                 if hasattr(d, "memory_stats")]
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            per_device = [int(ms.get(key, 0)) for ms in stats if key in ms]
            if per_device:
                out[f"jax_device_{key}"] = max(per_device)
                out[f"jax_devices_{key}_total"] = sum(per_device)
    except Exception:  # noqa: BLE001 — backend quirks must not kill sampling
        pass
    try:  # pjit C++ jit caches (internal API — best-effort by design)
        from jax._src import pjit as _pjit

        out["jax_jit_cache_size"] = (
            _pjit._cpp_pjit_cache_fun_only.size()
            + _pjit._cpp_pjit_cache_explicit_attributes.size())
    except Exception:  # noqa: BLE001
        pass
    return out


class RuntimeTelemetry:
    """One process's sampler thread bound to one tracing Registry."""

    def __init__(self, registry: Registry,
                 interval_sec: float = DEFAULT_INTERVAL_SEC) -> None:
        self.registry = registry
        self.interval_sec = float(interval_sec)
        self._last: Dict[str, Any] = {}
        self._last_at = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._samples = 0
        #: per-tick callbacks (ISSUE 7): the owning server appends its
        #: model-health tick here — time-series ring sampling + SLO
        #: evaluation ride the existing sampler thread instead of
        #: spawning their own. Hooks run AFTER the runtime gauges are
        #: published (so the tick's ring point sees them) and must never
        #: raise (guarded anyway).
        self.hooks: list = []
        install_jax_hooks()

    def sample(self) -> Dict[str, Any]:
        """Collect one sample now; publishes gauges into the registry and
        returns the sample dict (unprefixed keys)."""
        s = _proc_sample()
        s.update(_jax_sample())
        try:
            s["slowlog_depth"] = self.registry.slowlog.stats()["retained"]
        except Exception:  # noqa: BLE001
            pass
        with self._lock:
            self._samples += 1
            s["samples"] = self._samples
            self._last = s
            self._last_at = time.monotonic()
        for k, v in s.items():
            if isinstance(v, (int, float)):
                self.registry.gauge(k, v)
        for hook in list(self.hooks):
            try:
                hook()
            except Exception:  # noqa: BLE001 — a tick must never raise
                log.debug("telemetry tick hook failed", exc_info=True)
        return s

    def status(self) -> Dict[str, Any]:
        """Most recent sample, refreshed on demand when stale (> 1 s):
        get_status and /healthz readers see live numbers without paying a
        sample per call under scrape load."""
        with self._lock:
            fresh = (time.monotonic() - self._last_at) <= 1.0
            last = dict(self._last)
        if last and fresh:
            return last
        return self.sample()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None or self.interval_sec <= 0:
            return
        self.sample()  # get_status must have runtime keys immediately
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="runtime-telemetry")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_sec):
            try:
                self.sample()
            except Exception:  # noqa: BLE001 — the sampler must survive
                log.debug("runtime telemetry sample failed", exc_info=True)
