"""The load generator: every connection of a cell from one thread.

A *group* is a set of connections to one server that send one method
from one pool of pre-encoded requests, in a closed loop (the next
request leaves when the answer lands) or an open one (requests leave on a
schedule whatever the server does). The loop stamps each answer on its
own clock when the bytes arrive, so a call counts where its answer
lands. Requests were encoded before the window: the loop copies a frame,
patches its message id and writes it."""

from __future__ import annotations

import selectors
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import msgpack

from . import wire

Address = Tuple[str, int]


def _connect(addr: Address, timeout: float) -> socket.socket:
    s = socket.create_connection(addr, timeout=timeout)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


class Client:
    """One blocking connection, for set-up, control and the check."""

    def __init__(self, addr: Address, timeout: float = 120.0) -> None:
        self.addr = addr
        self.sock = _connect(addr, timeout)
        self.unpacker = msgpack.Unpacker(raw=False, strict_map_key=False)
        self.msgid = 0

    def send_frame(self, frame: bytearray) -> None:
        self.msgid += 1
        self.sock.sendall(wire.with_msgid(bytearray(frame), self.msgid))

    def recv(self) -> Any:
        """The next answer's result; an error answer raises."""
        while True:
            for msg in self.unpacker:
                msgid, err, result = wire.answer(msg)
                if err is not None:
                    raise RuntimeError(f"{self.addr}: rpc error {err!r}")
                return result
            data = self.sock.recv(1 << 20)
            if not data:
                raise ConnectionError(f"{self.addr} closed the connection")
            self.unpacker.feed(data)

    def call_frame(self, frame: bytearray) -> Any:
        self.send_frame(frame)
        return self.recv()

    def call(self, method: str, *params: Any) -> Any:
        return self.call_frame(wire.encode_request(method, params))

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def burst(addr: Address, frames: Sequence[bytearray], connections: int = 0,
          timeout: float = 300.0) -> List[Dict[str, Any]]:
    """Send the frames over ``connections`` connections, every connection
    at once and each in a closed loop over its share of the frames (frame
    ``k`` goes on connection ``k % connections``; 0: a connection for each
    frame). One record per frame, in order: ``{"result" | "error",
    "t_send", "t_recv"}``."""
    out: List[Dict[str, Any]] = [{} for _ in frames]
    n = min(connections or len(frames), len(frames))
    clients = [Client(addr, timeout) for _ in range(n)]

    def one(j: int) -> None:
        for k in range(j, len(frames), n):
            rec = out[k]
            try:
                rec["t_send"] = time.monotonic()
                rec["result"] = clients[j].call_frame(frames[k])
            except Exception as e:  # noqa: BLE001 — judged by the caller
                rec["error"] = repr(e)
                rec["t_recv"] = time.monotonic()
                return
            rec["t_recv"] = time.monotonic()

    threads = [threading.Thread(target=one, args=(j,), daemon=True)
               for j in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    for c in clients:
        c.close()
    now = time.monotonic()
    for rec in out:
        if "result" not in rec and "error" not in rec:
            rec["error"] = "no answer in time"
        rec.setdefault("t_recv", now)
    return out


class Group:
    """Connections that share a server, a method and a request pool."""

    def __init__(self, name: str, addr: Address, method: str,
                 connections: int, rows_per_call: int,
                 pool: Sequence[bytearray], pool_offset: int = 0,
                 rate_calls_per_s: float = 0.0, server: int = 0,
                 summarize: Optional[Callable[[Any], Any]] = None,
                 keep_every: int = 0) -> None:
        self.name = name
        self.addr = addr
        self.method = method
        self.connections = connections
        self.rows_per_call = rows_per_call
        self.pool = pool
        self.next_pool = pool_offset
        self.rate = float(rate_calls_per_s)   # 0: closed loop
        self.server = server
        self.summarize = summarize or (lambda result: result)
        #: keep every so-manieth answer whole (0: none), for the check
        self.keep_every = int(keep_every)
        self.answered = 0


class _Conn:
    __slots__ = ("sock", "group", "unpacker", "out", "sent_at", "due_at",
                 "pool_index", "idle_since", "index")

    def __init__(self, sock: socket.socket, group: Group, index: int) -> None:
        self.sock = sock
        self.group = group
        self.index = index
        self.unpacker = msgpack.Unpacker(raw=False, strict_map_key=False)
        self.out: Optional[memoryview] = None
        self.sent_at = 0.0
        self.due_at = 0.0
        self.pool_index = -1
        self.idle_since: Optional[float] = None


class LoadGen(threading.Thread):
    """Runs every group until :meth:`stop_sending`, then waits for the
    answers still owed. ``records`` holds one tuple per answered call:
    ``(group, conn, pool_index, t_due, t_send, t_recv, ok, summary, kept)``
    (``kept``: the whole answer, for every ``keep_every``-th of a group);
    ``turnaround`` one ``(group, seconds)`` per closed-loop resend."""

    def __init__(self, groups: Sequence[Group], drain_timeout: float = 90.0
                 ) -> None:
        super().__init__(name="loadgen", daemon=True)
        self.groups = list(groups)
        self.records: List[tuple] = []
        self.turnaround: List[Tuple[str, float]] = []
        self.failed = 0
        self.error: Optional[BaseException] = None
        self.cpu_seconds = 0.0
        self._stop_sending = threading.Event()
        self._drain_timeout = drain_timeout
        self._msgid = 0
        self._sel = selectors.DefaultSelector()
        self._conns: List[_Conn] = []
        for g in self.groups:
            for i in range(g.connections):
                s = _connect(g.addr, 60.0)
                s.setblocking(False)
                c = _Conn(s, g, i)
                self._conns.append(c)
                self._sel.register(s, selectors.EVENT_READ, c)

    # -- sending -------------------------------------------------------------
    def _start_send(self, c: _Conn, now: float, due: float) -> None:
        g = c.group
        c.pool_index = g.next_pool % len(g.pool)
        g.next_pool += 1
        self._msgid += 1
        frame = wire.with_msgid(bytearray(g.pool[c.pool_index]), self._msgid)
        if c.idle_since is not None and g.rate == 0.0:
            self.turnaround.append((g.name, now - c.idle_since))
        c.idle_since = None
        c.sent_at = now
        c.due_at = due
        c.out = memoryview(frame)
        self._write(c)

    def _write(self, c: _Conn) -> None:
        try:
            while c.out is not None and len(c.out):
                n = c.sock.send(c.out)
                c.out = c.out[n:]
        except BlockingIOError:
            self._sel.modify(c.sock, selectors.EVENT_READ
                             | selectors.EVENT_WRITE, c)
            return
        c.out = None
        self._sel.modify(c.sock, selectors.EVENT_READ, c)

    # -- receiving -----------------------------------------------------------
    def _read(self, c: _Conn) -> None:
        try:
            data = c.sock.recv(1 << 18)
        except BlockingIOError:
            return
        now = time.monotonic()
        if not data:
            raise ConnectionError(f"{c.group.addr} closed a connection of "
                                  f"group {c.group.name}")
        c.unpacker.feed(data)
        for msg in c.unpacker:
            _msgid, err, result = wire.answer(msg)
            ok = err is None
            if not ok:
                self.failed += 1
            g = c.group
            g.answered += 1
            keep = ok and g.keep_every and g.answered % g.keep_every == 0
            self.records.append((
                g.name, c.index, c.pool_index, c.due_at, c.sent_at, now, ok,
                g.summarize(result) if ok else repr(err),
                result if keep else None))
            c.idle_since = now
            c.pool_index = -1
            if g.rate == 0.0 and not self._stop_sending.is_set():
                self._start_send(c, time.monotonic(), now)

    def run(self) -> None:
        t_cpu = time.thread_time()
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — reported by the harness
            self.error = e
        finally:
            self.cpu_seconds = time.thread_time() - t_cpu
            for c in self._conns:
                try:
                    self._sel.unregister(c.sock)
                    c.sock.close()
                except (OSError, KeyError, ValueError):
                    pass

    def _idle(self, g: Group) -> Optional[_Conn]:
        """A connection of ``g`` that owes no answer and no bytes."""
        return next((c for c in self._conns if c.group is g
                     and c.pool_index < 0 and c.out is None), None)

    def _loop(self) -> None:
        now = time.monotonic()
        schedule: Dict[str, float] = {}
        for c in self._conns:
            if c.group.rate == 0.0:
                self._start_send(c, now, now)
            else:
                c.idle_since = now
                schedule.setdefault(c.group.name, now)
        stop_seen: Optional[float] = None
        while True:
            # wake when the next open-loop call is due, not a tick later;
            # a call that is overdue for want of an idle connection leaves
            # when an answer arrives, which wakes the loop by itself
            due_in = min([schedule[g.name] - time.monotonic()
                          for g in self.groups
                          if g.rate > 0.0 and self._idle(g) is not None]
                         + [0.02])
            for key, mask in self._sel.select(timeout=max(0.0, due_in)):
                c = key.data
                if mask & selectors.EVENT_WRITE:
                    self._write(c)
                if mask & selectors.EVENT_READ:
                    self._read(c)
            now = time.monotonic()
            stopping = self._stop_sending.is_set()
            if not stopping:
                # open loop: a request leaves when it is due, on any idle
                # connection of its group; with none idle it leaves late
                # and its latency counts from when it was due
                for g in self.groups:
                    if g.rate == 0.0:
                        continue
                    while schedule[g.name] <= now:
                        idle = self._idle(g)
                        if idle is None:
                            break
                        self._start_send(idle, now, schedule[g.name])
                        schedule[g.name] += 1.0 / g.rate
            else:
                stop_seen = stop_seen or now
                if all(c.pool_index < 0 for c in self._conns):
                    return
                if now - stop_seen > self._drain_timeout:
                    owed = sum(c.pool_index >= 0 for c in self._conns)
                    self.failed += owed
                    raise TimeoutError(f"{owed} calls never answered")

    def stop_sending(self) -> None:
        self._stop_sending.set()
