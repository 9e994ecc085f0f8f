"""Driver base class (≙ core::driver::driver_base, SURVEY.md §2.9).

A driver owns one engine's model state + fv_converter and exposes:
- the engine's business API (train/classify/... defined by subclasses),
- the mixable protocol for the mix engine (get_mixables),
- pack/unpack for checkpointing (framework/save_load.py),
- clear and schema sync.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, ContextManager, Dict, List

from jubatus_tpu.parallel.mix import Mixable
from jubatus_tpu.utils.tracing import span_in


def locked(fn):
    """Method decorator: hold the driver's model lock (the reference's
    JRLOCK_/JWLOCK_ decorators collapsed to one reentrant lock — snapshot
    reads of JAX arrays make a reader/writer split unnecessary for now)."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self.lock:
            return fn(self, *args, **kwargs)

    return wrapper


class DriverBase:
    #: engine type name, e.g. "classifier" — matches the reference's server
    #: type strings used in model filenames and RPC registration.
    TYPE: str = "base"

    #: bumped when a driver's pack() layout changes (reference
    #: user_data_version, server_base.hpp:41-109)
    USER_DATA_VERSION: int = 1

    def __init__(self) -> None:
        self.update_count = 0
        #: model lock (the reference's rw_mutex, server_base.hpp:70-72):
        #: drivers hold it in their public methods; the mix engine holds every
        #: participant's lock for the round (parallel/mix.py), so a background
        #: mix can never interleave with train/classify on the same model.
        self.lock = threading.RLock()
        #: tracing Registry the owning server hands over (its own): the
        #: step's phase spans and counters go there. A driver used
        #: without a server (bench.py, unit tests) records nothing.
        self.trace: Any = None

    def _span(self, name: str) -> ContextManager:
        return span_in(self.trace, name)

    # -- mix plane ----------------------------------------------------------
    def get_mixables(self) -> Dict[str, Mixable]:
        return {}

    def get_schema(self) -> List[str]:
        """Row-vocabulary schema for pre-mix alignment (default: none)."""
        return []

    def sync_schema(self, union_schema: List[str]) -> None:
        pass

    # -- persistence --------------------------------------------------------
    def pack(self) -> Any:
        raise NotImplementedError

    def unpack(self, obj: Any) -> None:
        raise NotImplementedError

    def clear(self) -> None:
        raise NotImplementedError

    # -- bookkeeping ---------------------------------------------------------
    def event_model_updated(self, n: int = 1) -> None:
        """Reference server_base::event_model_updated (server_base.cpp:214-219):
        bump the update counter; the mixer watches it."""
        self.update_count += n

    def get_status(self) -> Dict[str, Any]:
        return {"type": self.TYPE, "update_count": self.update_count}
