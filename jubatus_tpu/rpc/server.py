"""Threaded MessagePack-RPC server (≙ mprpc/rpc_server.{hpp,cpp}).

The reference dispatches by a name→invoker hash (rpc_server.cpp:44-82) with
typed sync invokers (rpc_server.hpp:109-240) on an mpio event loop with N
worker threads. Here: a TCP accept loop + per-connection reader threads over a
shared bounded worker pool — Python-idiomatic, same semantics (N concurrent
in-flight calls, per-connection response ordering is NOT guaranteed, matching
msgpack-rpc's msgid-correlated pipelining).

Arity checking reproduces the typed-invoker behavior: a call with the wrong
number of params gets ARGUMENT_ERROR, an unknown method NO_METHOD_ERROR.
"""

from __future__ import annotations

import inspect
import logging
import socket
import struct
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional

import msgpack

from jubatus_tpu.rpc import deadline as deadlines
from jubatus_tpu.rpc import principal as principals
from jubatus_tpu.rpc.errors import (
    DeadlineExceeded,
    RpcMethodNotFound,
    error_to_wire,
)
from jubatus_tpu.utils import faults, tracing
from jubatus_tpu.utils.tracing import Registry

log = logging.getLogger(__name__)

REQUEST, RESPONSE, NOTIFY = 0, 1, 2

#: sentinel a raw-registered handler returns to decline the fast path —
#: the request is then decoded generically and served by the normal handler
RAW_FALLBACK = object()


def wire_is_legacy(raw: bytes) -> bool:
    """Fingerprint one request: True when it contains NO post-2013 msgpack
    type bytes — i.e. the reference's vendored-msgpack client could have
    produced it. Such connections are answered in the legacy raw format,
    which modern unpackers also accept (old raw16/raw32 are modern
    str16/str32), so a false positive only costs the str/bytes distinction
    a modern client never relied on for the jubatus API. A single modern
    type byte (str8/bin/ext) proves a modern client and pins the
    connection to the modern format. Skip-style scan — no values are
    built, and the walk is budget-capped (scan_is_legacy), so a
    provisionally-legacy connection pays a small bounded cost per
    request, not an O(elements) walk of every bulk train call."""
    from jubatus_tpu.rpc import legacy as _legacy

    return _legacy.scan_is_legacy(raw)


#: payload widths of fixed-size msgpack types (modern family included)
_MP_SCALAR_WIDTH = {0xC0: 0, 0xC2: 0, 0xC3: 0, 0xCA: 4, 0xCB: 8, 0xCC: 1,
                    0xCD: 2, 0xCE: 4, 0xCF: 8, 0xD0: 1, 0xD1: 2, 0xD2: 4,
                    0xD3: 8, 0xD4: 2, 0xD5: 3, 0xD6: 5, 0xD7: 9, 0xD8: 17}


def msgpack_span_end(buf: bytes, i: int = 0) -> int:
    """End offset of the msgpack object starting at ``buf[i]`` — a type-
    byte walk that builds no values (the raw relay path needs to split an
    envelope into spans without decoding multi-megabyte payloads).
    Raises ValueError on truncated/unknown bytes."""
    n = len(buf)
    remaining = 1
    while remaining:
        if i >= n:
            raise ValueError("truncated msgpack object")
        t = buf[i]
        i += 1
        remaining -= 1
        if t <= 0x7F or t >= 0xE0:
            continue
        if 0x80 <= t <= 0x8F:
            remaining += (t & 0x0F) * 2
        elif 0x90 <= t <= 0x9F:
            remaining += t & 0x0F
        elif 0xA0 <= t <= 0xBF:
            i += t & 0x1F
        elif t in _MP_SCALAR_WIDTH:
            i += _MP_SCALAR_WIDTH[t]
        elif t in (0xC4, 0xC7, 0xD9):     # bin8/ext8/str8
            if i >= n:
                raise ValueError("truncated msgpack object")
            i += 1 + buf[i] + (1 if t == 0xC7 else 0)
        elif t in (0xC5, 0xC8, 0xDA):     # bin16/ext16/str16
            if i + 2 > n:
                raise ValueError("truncated msgpack object")
            i += 2 + int.from_bytes(buf[i:i + 2], "big") + \
                (1 if t == 0xC8 else 0)
        elif t in (0xC6, 0xC9, 0xDB):     # bin32/ext32/str32
            if i + 4 > n:
                raise ValueError("truncated msgpack object")
            i += 4 + int.from_bytes(buf[i:i + 4], "big") + \
                (1 if t == 0xC9 else 0)
        elif t in (0xDC, 0xDD, 0xDE, 0xDF):
            w = 2 if t in (0xDC, 0xDE) else 4
            if i + w > n:
                raise ValueError("truncated msgpack object")
            count = int.from_bytes(buf[i:i + w], "big")
            if count > n - i:
                raise ValueError("impossible msgpack length")
            i += w
            remaining += count * (2 if t in (0xDE, 0xDF) else 1)
        else:
            raise ValueError(f"unknown msgpack type byte 0x{t:02x}")
    if i > n:
        raise ValueError("truncated msgpack object")
    return i


class RawResult:
    """A handler result that is ALREADY msgpack-encoded (a relayed
    backend response span): build_response splices it into the response
    frame without a decode/encode round trip."""

    __slots__ = ("span",)

    def __init__(self, span: bytes) -> None:
        self.span = span


def _parse_response_envelope(raw: bytes) -> int:
    """Offset of the ERROR object in a response frame
    ``[1, msgid, error, result]``; ValueError on anything else."""
    if len(raw) < 3 or raw[0] != 0x94 or raw[1] != 0x01:
        raise ValueError("not a msgpack-rpc response frame")
    t = raw[2]
    if t <= 0x7F:
        return 3
    if t == 0xCC:
        return 4
    if t == 0xCD:
        return 5
    if t == 0xCE:
        return 7
    raise ValueError("unexpected msgid encoding")


def _parse_envelope(raw: bytes):
    """Request envelope without decoding params: ``[0, msgid, method,
    params]``, the traced 5-element variant ``[..., trace]``, the
    deadline-bearing 6-element variant ``[..., trace, deadline]``, or
    the principal-bearing 7-element variant ``[..., trace, deadline,
    principal]`` -> (msgid, method, params_offset, n_extra), or None
    for anything else (notify, malformed, exotic headers) — those take
    the generic decode path."""
    try:
        if raw[0] not in (0x94, 0x95, 0x96, 0x97) or raw[1] != 0x00:  # REQUEST
            return None
        n_extra = raw[0] - 0x94
        i = 2
        t = raw[i]
        if t <= 0x7F:
            msgid, i = t, i + 1
        elif t == 0xCC:
            msgid, i = raw[i + 1], i + 2
        elif t == 0xCD:
            msgid, i = int.from_bytes(raw[i + 1:i + 3], "big"), i + 3
        elif t == 0xCE:
            msgid, i = int.from_bytes(raw[i + 1:i + 5], "big"), i + 5
        else:
            return None
        t = raw[i]
        if 0xA0 <= t <= 0xBF:  # fixstr/fixraw
            n, i = t & 0x1F, i + 1
        elif t == 0xD9:        # str8
            n, i = raw[i + 1], i + 2
        elif t == 0xDA:        # raw16/str16
            n, i = int.from_bytes(raw[i + 1:i + 3], "big"), i + 3
        else:
            return None
        method = raw[i:i + n].decode("utf-8", "surrogateescape")
        return msgid, method, i + n, n_extra
    except IndexError:
        return None


def split_extras(raw: bytes, off: int):
    """Split a request's params span from its OPTIONAL trailing envelope
    elements (trace, then deadline, then principal) — shared by both
    transports. Returns (params_span, trace_wire, deadline_wire,
    principal_wire); a malformed tail degrades to (everything, None,
    None, None) — a bad extra element must not 500 the request."""
    try:
        pend = msgpack_span_end(raw, off)
        trace_w = dl_w = pr_w = None
        if pend < len(raw):
            tend = msgpack_span_end(raw, pend)
            trace_w = msgpack.unpackb(raw[pend:tend], raw=False)
            if tend < len(raw):
                dend = msgpack_span_end(raw, tend)
                dl_w = msgpack.unpackb(raw[tend:dend], raw=False)
                if dend < len(raw):
                    pr_w = msgpack.unpackb(raw[dend:], raw=False)
        return raw[off:pend], trace_w, dl_w, pr_w
    except Exception:  # broad-ok — a bad trailing element must not 500
        return raw[off:], None, None, None


class RpcServer:
    """Dispatcher + listener. register() then listen() then start().

    Lifecycle mirrors the reference (listen → start(nthreads) → join → end,
    rpc_server.hpp): ``serve_background()`` is listen+start, ``stop()`` is end.
    """

    #: which transport this is, as get_status / get_proxy_status report it
    transport = "python"

    def __init__(self, timeout: float = 10.0,
                 trace: Optional[Registry] = None,
                 legacy_wire: bool = False,
                 wire_detect: bool = False) -> None:
        self._methods: Dict[str, Callable[..., Any]] = {}
        self._arity: Dict[str, Optional[int]] = {}
        #: pack responses in the pre-str8/bin msgpack format old jubatus
        #: clients understand (--legacy-wire; see rpc/legacy.py). Methods
        #: registered with binary=True (mixer internals shipping packed
        #: model bytes) keep the modern format — legacy clients never call
        #: them, and old-raw would lose the str/bytes distinction for our
        #: own peers.
        self.legacy_wire = legacy_wire
        #: per-connection autodetection: fingerprint each connection's
        #: FIRST request (wire_is_legacy) and answer legacy-format when it
        #: carries no post-2013 type bytes — an unmodified deployed
        #: jubatus client works against a server started with NO flags
        #: (the reference speaks old-format on every connection,
        #: client/common/client.hpp:30-87). Engine servers and proxies
        #: enable this; internal planes (coordd) stay modern-only so bytes
        #: payloads keep their type.
        self.wire_detect = wire_detect
        self._binary_methods: set = set()
        #: raw-span fast paths: method -> fn(raw_params bytes) -> result
        #: (or RAW_FALLBACK to decode generically). Served straight off the
        #: wire framing without building Python param objects.
        self._raw_methods: Dict[str, Callable[[bytes], Any]] = {}
        self.timeout = timeout
        #: per-server span aggregates (multi-server processes must not
        #: merge each other's counters)
        self.trace = trace or Registry()
        self._sock: Optional[socket.socket] = None
        self._threads: list[threading.Thread] = []
        self._pool: Optional[ThreadPoolExecutor] = None
        self._running = False
        self.port: Optional[int] = None
        #: elastic-membership gate (ISSUE 10): called with the method
        #: name before every dispatch; raising rejects the request
        #: BEFORE any state change (the drain plane rejects effectful
        #: methods with the retryable NodeDraining so proxies re-route).
        #: Shared by both transports (NativeRpcServer borrows _invoke).
        self.dispatch_gate: Optional[Callable[[str], None]] = None
        #: usage ledger (utils/usage.py, ISSUE 19): the dispatch layer
        #: notes per-method errors and bytes in/out into it; CPU-seconds
        #: arrive via the registry's usage_sink, not here. Shared by
        #: both transports (NativeRpcServer borrows _execute*).
        self.usage_recorder: Optional[Any] = None

    # -- method table (≙ rpc_server::add<T>) --------------------------------
    def register(self, name: str, fn: Callable[..., Any],
                 arity: Optional[int] = None,
                 binary: bool = False) -> None:
        if binary:
            self._binary_methods.add(name)
        if arity is None:
            try:
                sig = inspect.signature(fn)
                if not any(
                    p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
                    for p in sig.parameters.values()
                ):
                    arity = len(
                        [
                            p
                            for p in sig.parameters.values()
                            if p.default is p.empty
                            and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                        ]
                    )
            except (TypeError, ValueError):
                arity = None
        self._methods[name] = fn
        self._arity[name] = arity

    def register_raw(self, name: str, fn: Callable[[bytes], Any]) -> None:
        """Fast path for ``name``: ``fn`` receives the request's raw params
        msgpack bytes (no Python decode) and returns the result — or
        ``RAW_FALLBACK`` to route the request through the generic decode +
        registered handler (e.g. a wire shape the native parser rejects).
        The generic handler must also be registered (fallback + arity)."""
        self._raw_methods[name] = fn

    def method_names(self):
        return sorted(self._methods)

    # -- lifecycle -----------------------------------------------------------
    def listen(self, port: int, host: str = "0.0.0.0") -> int:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((host, port))
        sock.listen(128)
        self._sock = sock
        self.port = sock.getsockname()[1]
        return self.port

    def start(self, nthreads: int = 2) -> None:
        assert self._sock is not None, "listen() first"
        self._running = True
        self._pool = ThreadPoolExecutor(
            max_workers=max(2, nthreads), thread_name_prefix="rpc-worker"
        )
        t = threading.Thread(target=self._accept_loop, daemon=True, name="rpc-accept")
        t.start()
        self._threads.append(t)

    def serve_background(self, port: int = 0, nthreads: int = 2, host: str = "0.0.0.0") -> int:
        port = self.listen(port, host)
        self.start(nthreads)
        return port

    def stop(self) -> None:
        self._running = False
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # -- wire loop -----------------------------------------------------------
    def _accept_loop(self) -> None:
        while self._running and self._sock is not None:
            try:
                conn, _addr = self._sock.accept()
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(
                target=self._conn_loop, args=(conn,), daemon=True, name="rpc-conn"
            )
            t.start()

    def _conn_loop(self, conn: socket.socket) -> None:
        # Frame messages by span (Unpacker.skip + tell — C-speed, builds
        # no objects), keep a mirror of the bytes, and decode per message:
        # raw-registered methods get the params span directly (zero Python
        # object churn on the hot path); everything else goes through one
        # unpackb. surrogateescape: legacy clients pack datum binary_values
        # as old-raw, which may not be UTF-8 — a decode error must not kill
        # the connection. Datum.from_msgpack re-encodes surrogate-bearing
        # strings back to the exact original bytes.
        framer = msgpack.Unpacker()
        buf = bytearray()
        base = 0       # stream offset of buf[0]
        msg_start = 0  # stream offset of the next undelivered message
        wlock = threading.Lock()
        #: requests fingerprint the peer's wire era (skipped when
        #: --legacy-wire already forces every answer legacy). A legacy
        #: verdict is PROVISIONAL: a modern client whose early calls are
        #: all fixtypes (short method, small args — e.g. get_status) emits
        #: zero post-2013 bytes, so the connection keeps being re-scanned
        #: and upgrades to modern the first time a SCANNED request carries
        #: a modern type byte. Only the modern verdict latches — a
        #: vendored-msgpack client can never send one. Re-scans are
        #: SAMPLED: every small request (<= 1 KB — status/row reads, where
        #: the str/bytes distinction actually bites) but only
        #: power-of-2-numbered bulk ones; an every-request scan measured a
        #: ~3x e2e train throughput hit for genuinely-legacy-looking
        #: pipelined bulk traffic.
        try:
            peer = "%s:%s" % conn.getpeername()[:2]
        except (OSError, TypeError):
            peer = ""
        conn_state = {"legacy": False, "peer": peer}
        scanning = self.wire_detect and not self.legacy_wire
        nreq = 0
        try:
            while self._running:
                data = conn.recv(65536)
                if not data:
                    return
                framer.feed(data)
                buf += data
                while True:
                    try:
                        framer.skip()
                    except msgpack.OutOfData:
                        break
                    end = framer.tell()
                    raw = bytes(buf[msg_start - base:end - base])
                    msg_start = end
                    if scanning:
                        nreq += 1
                        if len(raw) <= 1024 or (nreq & (nreq - 1)) == 0:
                            conn_state["legacy"] = wire_is_legacy(raw)
                            scanning = conn_state["legacy"]
                    self._handle_raw(conn, wlock, raw, conn_state)
                del buf[:msg_start - base]
                base = msg_start
        # RuntimeError: pool.submit after stop() — a hard-killed server's
        # surviving connection threads must die quietly, not traceback
        except (OSError, ValueError, struct.error, RuntimeError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _handle_raw(self, conn: socket.socket, wlock: threading.Lock,
                    raw: bytes, conn_state: Optional[dict] = None) -> None:
        env = _parse_envelope(raw)
        if env is not None:
            msgid, method, off, n_extra = env
            params_span, trace, dl, pr = raw[off:], None, None, None
            if n_extra:
                # traced/deadlined/principal envelope: split the params
                # span from the trailing elements (the walk is paid only
                # on extended requests)
                params_span, trace, dl, pr = split_extras(raw, off)
            if method in self._raw_methods and self._pool is not None:
                self._pool.submit(self._dispatch_fast, conn, wlock, msgid,
                                  method, params_span, conn_state, trace,
                                  dl, pr)
                return
        msg = msgpack.unpackb(raw, raw=False, strict_map_key=False,
                              use_list=True,
                              unicode_errors="surrogateescape")
        self._handle(conn, wlock, msg, conn_state, nbytes=len(raw))

    def _dispatch_fast(self, conn, wlock, msgid, method,
                       raw_params: bytes,
                       conn_state: Optional[dict] = None,
                       trace: Any = None, dl: Any = None,
                       pr: Any = None) -> None:
        # adopt the caller's trace context (or root a fresh one), its
        # deadline AND its principal for the duration of the dispatch;
        # restore after — pool threads are reused
        ctx = tracing.from_wire(trace)
        if conn_state is not None:
            ctx.peer = conn_state.get("peer", "")
        prev = tracing.swap_trace(ctx)
        prev_dl = deadlines.swap(deadlines.adopt_wire(dl))
        p_req = principals.adopt_wire(pr)
        prev_pr = principals.swap(p_req)
        try:
            error, result = self._execute_fast(method, raw_params, conn_state)
        finally:
            tracing.swap_trace(prev)
            deadlines.swap(prev_dl)
            principals.swap(prev_pr)
        payload = build_response(
            msgid, error, result,
            legacy=self.response_legacy(method, conn_state))
        rec = self.usage_recorder
        if rec is not None:
            rec.account(method, principal=p_req, resolve=False,
                        bytes_in=float(len(raw_params)),
                        bytes_out=float(len(payload)))
        try:
            with wlock:
                conn.sendall(payload)
        except OSError:
            pass

    def _execute_fast(self, method: str, raw_params: bytes,
                      conn_state: Optional[dict] = None):
        """Raw-span invoke; falls back to the generic decode + handler when
        the fast fn declines (RAW_FALLBACK). Handlers marked
        ``modern_only`` (the proxy's verbatim relays) are skipped for
        legacy-era connections — their spans must be decoded and
        re-encoded modern, not forwarded as-is. The trace span is recorded
        here only when the fast path served the request — a fallback
        cancels the span handle so the request is counted once, by
        _invoke's span."""
        fn = self._raw_methods[method]
        if conn_state is not None and conn_state.get("legacy") and \
                getattr(fn, "modern_only", False):
            params = msgpack.unpackb(raw_params, raw=False,
                                     strict_map_key=False, use_list=True,
                                     unicode_errors="surrogateescape")
            return self._execute(method, params)
        with self.trace.span(f"rpc.{method}") as sp:
            try:
                if faults.is_armed():
                    faults.fire(f"rpc.dispatch.{method}")
                self._check_deadline(method)
                gate = getattr(self, "dispatch_gate", None)
                if gate is not None:
                    gate(method)
                result = fn(raw_params)
            except Exception as e:  # broad-ok — every failure must answer
                log.debug("rpc raw method %s raised", method, exc_info=True)
                self.trace.count(f"rpc.{method}.errors")
                rec = getattr(self, "usage_recorder", None)
                if rec is not None:
                    rec.note_error(method)
                return error_to_wire(e), None
            if result is not RAW_FALLBACK:
                return None, result
            sp.cancel()
        params = msgpack.unpackb(raw_params, raw=False, strict_map_key=False,
                                 use_list=True,
                                 unicode_errors="surrogateescape")
        return self._execute(method, params)

    def _handle(self, conn: socket.socket, wlock: threading.Lock, msg: Any,
                conn_state: Optional[dict] = None,
                nbytes: int = 0) -> None:
        if not isinstance(msg, (list, tuple)) or not msg:
            return
        if msg[0] == REQUEST and len(msg) in (4, 5, 6, 7):
            # 5th element: optional trace context ({"t","s"}); 6th:
            # optional deadline budget (remaining seconds); 7th:
            # optional principal (tenant id) — see rpc/client.py; plain
            # msgpack-rpc peers send 4
            _, msgid, method, params = msg[:4]
            trace = msg[4] if len(msg) >= 5 else None
            dl = msg[5] if len(msg) >= 6 else None
            pr = msg[6] if len(msg) == 7 else None
            if self._pool is not None:
                self._pool.submit(self._dispatch, conn, wlock, msgid, method,
                                  params, conn_state, trace, dl, pr, nbytes)
        elif msg[0] == NOTIFY and len(msg) == 3:
            _, method, params = msg
            if self._pool is not None:
                self._pool.submit(self._invoke_silent, method, params)

    def _dispatch(self, conn, wlock, msgid, method, params,
                  conn_state: Optional[dict] = None,
                  trace: Any = None, dl: Any = None,
                  pr: Any = None, nbytes: int = 0) -> None:
        ctx = tracing.from_wire(trace)
        if conn_state is not None:
            ctx.peer = conn_state.get("peer", "")
        prev = tracing.swap_trace(ctx)
        prev_dl = deadlines.swap(deadlines.adopt_wire(dl))
        p_req = principals.adopt_wire(pr)
        prev_pr = principals.swap(p_req)
        try:
            error, result = self._execute(method, params)
        finally:
            tracing.swap_trace(prev)
            deadlines.swap(prev_dl)
            principals.swap(prev_pr)
        payload = build_response(
            msgid, error, result,
            legacy=self.response_legacy(method, conn_state))
        rec = self.usage_recorder
        if rec is not None:
            rec.account(method, principal=p_req, resolve=False,
                        bytes_in=float(nbytes),
                        bytes_out=float(len(payload)))
        try:
            with wlock:
                conn.sendall(payload)
        except OSError:
            pass

    def _execute(self, method: str, params: Any):
        """Invoke + error taxonomy, shared by every transport."""
        error, result = None, None
        try:
            result = self._invoke(method, params)
        except Exception as e:  # broad-ok — every failure must answer
            if not isinstance(e, RpcMethodNotFound):
                log.debug("rpc method %s raised", method, exc_info=True)
            # per-method failure counter: the dispatch span times success
            # and failure identically, so error RATE needs its own series
            self.trace.count(f"rpc.{method}.errors")
            rec = getattr(self, "usage_recorder", None)
            if rec is not None:
                rec.note_error(method)
            error = error_to_wire(e)
        return error, result

    def _invoke(self, method: str, params: Any) -> Any:
        fn = self._methods.get(method)
        if fn is None:
            raise RpcMethodNotFound(method)
        params = list(params) if isinstance(params, (list, tuple)) else [params]
        want = self._arity.get(method)
        if want is not None and len(params) != want:
            raise TypeError(f"{method}: expected {want} params, got {len(params)}")
        # injection site for dispatch-side chaos (queueing delay, worker
        # stalls); fired BEFORE the deadline gate so an injected delay can
        # deterministically expire a propagated budget
        if faults.is_armed():
            faults.fire(f"rpc.dispatch.{method}")
        self._check_deadline(method)
        gate = getattr(self, "dispatch_gate", None)
        if gate is not None:
            gate(method)
        with self.trace.span(f"rpc.{method}"):
            return fn(*params)

    def _check_deadline(self, method: str) -> None:
        """Reject already-expired work at dispatch: computing an answer
        nobody is waiting for only steals capacity from live requests.
        Counted per server (``rpc.deadline_rejected``)."""
        if deadlines.expired():
            self.trace.count("rpc.deadline_rejected")
            raise DeadlineExceeded(f"{method}: deadline expired at dispatch")

    def _invoke_silent(self, method: str, params: Any) -> None:
        try:
            self._invoke(method, params)
        except Exception:  # broad-ok
            log.debug("rpc notify %s raised", method, exc_info=True)

    def response_legacy(self, method: str,
                        conn_state: Optional[dict] = None) -> bool:
        """Whether this method's responses go out in the old wire format:
        forced globally by --legacy-wire, or detected per connection from
        its first request's fingerprint (wire_detect)."""
        if method in self._binary_methods:
            return False
        if self.legacy_wire:
            return True
        return bool(conn_state and conn_state.get("legacy"))


def build_response(msgid: int, error: Any, result: Any,
                   legacy: bool = False) -> bytes:
    """Pack one msgpack-rpc response message (shared by all transports).

    ``legacy=True`` packs in the pre-2013 format (no str8/bin type bytes:
    strings and bytes both go out as old "raw") so the reference's vendored
    msgpack — and therefore every deployed jubatus client — can parse it
    (client/common/client.hpp:30-87 links that old library).
    """
    if isinstance(result, RawResult):
        if error is None and not legacy:
            # splice the pre-encoded span: fixarray(4) + RESPONSE + msgid
            # + nil error + the span, no decode/encode of the payload
            return (b"\x94\x01" + msgpack.packb(msgid) + b"\xc0"
                    + result.span)
        # error path or legacy-era connection: materialize and fall
        # through to the normal packer (legacy needs old-raw re-encoding)
        result = msgpack.unpackb(result.span, raw=False,
                                 strict_map_key=False, use_list=True,
                                 unicode_errors="surrogateescape")
    # surrogateescape mirrors the request-decode side: surrogate-bearing
    # strings (legacy non-UTF8 raw admitted by the unpacker, e.g. stored
    # as labels) must re-encode to their original bytes, not raise after
    # dispatch with the client left hanging
    return msgpack.packb([RESPONSE, msgid, error, result], default=_to_wire,
                         use_bin_type=not legacy,
                         unicode_errors="surrogateescape")


def _to_wire(obj: Any) -> Any:
    """msgpack fallback: tuples of dataclass-ish objects → lists; numpy/JAX
    scalars → Python scalars (the serving plane never ships device arrays)."""
    if hasattr(obj, "to_msgpack"):
        return obj.to_msgpack()
    if hasattr(obj, "item"):
        return obj.item()
    if hasattr(obj, "tolist"):
        return obj.tolist()
    raise TypeError(f"cannot msgpack {type(obj)!r}")
