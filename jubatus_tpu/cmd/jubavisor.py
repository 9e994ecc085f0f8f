"""Per-host process supervisor daemon (≙ jubavisor/jubavisor.{hpp,cpp}).

An RPC-controlled process manager: ``jubactl -c start`` asks every
registered jubavisor to spawn N engine servers; ``stop`` kills them.

RPC surface (jubavisor.hpp:36-86, wire names identical):
- ``start(name, N, argv) -> int``   name = "<server>/<cluster>"
  (e.g. "jubaclassifier/mycluster" — the reference passes the executable
  name; plain engine names work too), argv = flag map forwarded to each
  spawned server. 0 on success.
- ``stop(name, N) -> int``          kills all children of that name
  (the reference ignores N and stops all, jubavisor.hpp:47-49).

Children are ``python -m jubatus_tpu.server <engine> ...`` subprocesses
given ports from a pool [port+1, port+max] (jubavisor.cpp port_pool_).
One ``start`` of N > 1 children binds child k to chip k of this host
(``cmd.tpu_process_env``), so the first child does not open every chip;
with ``jax_processes`` / ``jax_coordinator`` in the argv map the children
also join one jax world (``--mixer collective_mixer``), ranked from
``jax_process_id`` upward. A
reaper thread collects exits and recycles ports (≙ SIGCHLD handler);
``stop_all`` runs at exit (atexit_ kill-all). Registers ephemerally under
/jubatus/supervisors so jubactl can find it (membership.cpp).
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from jubatus_tpu.cmd import resolve_coordinator, tpu_process_env
from jubatus_tpu.coord import create_coordinator, membership
from jubatus_tpu.framework.idl import ENGINES
from jubatus_tpu.rpc.server import RpcServer

log = logging.getLogger(__name__)

#: jubactl argv-map keys → our server CLI flags
_FLAG_MAP = {
    "listen_if": "--listen-addr",
    "thread": "--thread",
    "timeout": "--timeout",
    "datadir": "--datadir",
    "logdir": "--logdir",
    "mixer": "--mixer",
    "interval_sec": "--interval-sec",
    "interval_count": "--interval-count",
    "zookeeper_timeout": "--coordinator-timeout",
    "interconnect_timeout": "--interconnect-timeout",
    "jax_processes": "--jax-processes",
    "jax_coordinator": "--jax-coordinator",
}


def parse_engine(name: str) -> str:
    """"jubaclassifier/c1" | "classifier/c1" → engine name."""
    server = name.split("/", 1)[0]
    engine = server[4:] if server.startswith("juba") else server
    if engine not in ENGINES:
        raise ValueError(f"unknown engine in {name!r}")
    return engine


class _Child:
    __slots__ = ("proc", "port", "name")

    def __init__(self, proc: subprocess.Popen, port: int, name: str) -> None:
        self.proc = proc
        self.port = port
        self.name = name


class Jubavisor:
    def __init__(self, coordinator: str, port: int = 9198, max_children: int = 10,
                 logfile: str = "", host: str = "127.0.0.1") -> None:
        self.coordinator = coordinator
        self.host = host
        self.port = port
        self.logfile = logfile
        self.coord = create_coordinator(coordinator)
        self.rpc = RpcServer()
        self.rpc.register("start", self.start_procs, arity=3)
        self.rpc.register("stop", self.stop_procs, arity=2)
        self._mu = threading.Lock()
        self.max_children = max_children
        self._pool: List[int] = []  # filled in start() once the port is known
        self._children: Dict[str, List[_Child]] = {}
        self._stop_event = threading.Event()
        self._reaper = threading.Thread(target=self._reap_loop, daemon=True,
                                        name="visor-reaper")

    # -- RPC: start (jubavisor.cpp start_) -----------------------------------
    def start_procs(self, name: str, n: int, argv: Optional[Dict[str, Any]]) -> int:
        try:
            engine = parse_engine(name)
        except ValueError as e:
            log.error("%s", e)
            return -1
        cluster = name.split("/", 1)[1] if "/" in name else name
        argv = argv or {}
        n = int(n)
        with self._mu:
            if len(self._pool) < n:
                log.error("port pool exhausted (max children reached)")
                return -1
            ports = [self._pool.pop(0) for _ in range(n)]
            for k, port in enumerate(ports):
                cmd = [sys.executable, "-m", "jubatus_tpu.server", engine,
                       "-z", self.coordinator, "-n", cluster, "-p", str(port)]
                for key, flag in _FLAG_MAP.items():
                    if key in argv and argv[key] not in ("", None):
                        cmd += [flag, str(argv[key])]
                if argv.get("jax_processes"):
                    cmd += ["--jax-process-id",
                            str(int(argv.get("jax_process_id") or 0) + k)]
                env = None
                if n > 1:
                    # a chip belongs to one process: child k gets chip k.
                    # The TPU runtime's mesh ports sit above the rpc pool.
                    try:
                        env = dict(os.environ, **tpu_process_env(
                            k, [p + self.max_children for p in ports]))
                    except ValueError as e:
                        log.warning("%s: children are not bound to chips", e)
                out = (open(self.logfile, "ab") if self.logfile
                       else subprocess.DEVNULL)
                try:
                    proc = subprocess.Popen(cmd, stdout=out, stderr=out,
                                            env=env)
                except OSError as e:
                    log.error("spawn failed: %s", e)
                    self._pool[:0] = ports[k:]
                    return -1
                finally:
                    if out is not subprocess.DEVNULL:
                        out.close()
                self._children.setdefault(name, []).append(
                    _Child(proc, port, name))
                log.info("started %s on port %d (pid %d)", name, port, proc.pid)
        return 0

    # -- RPC: stop (reference stops ALL processes of the name) ---------------
    def stop_procs(self, name: str, _n: int = 0) -> int:
        with self._mu:
            children = self._children.pop(name, [])
        self._kill(children)
        log.info("stopped %d process(es) of %s", len(children), name)
        return 0

    def stop_all(self) -> None:
        with self._mu:
            everything = [c for lst in self._children.values() for c in lst]
            self._children.clear()
        self._kill(everything)

    def _kill(self, children: List[_Child]) -> None:
        # members of one jax world leave together (the runtime's shutdown
        # is a barrier): signal all of them before waiting for any
        for child in children:
            try:
                child.proc.terminate()
            except OSError:
                pass
        for child in children:
            try:
                try:
                    child.proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    child.proc.kill()
                    child.proc.wait(timeout=5.0)
            except OSError:
                pass
            with self._mu:
                self._pool.append(child.port)

    def _reap_loop(self) -> None:
        """Collect dead children, recycle their ports (≙ SIGCHLD reaping)."""
        while not self._stop_event.wait(1.0):
            with self._mu:
                for name, lst in list(self._children.items()):
                    for c in list(lst):
                        if c.proc.poll() is not None:
                            lst.remove(c)
                            self._pool.append(c.port)
                            log.warning("child %s port %d exited with %s",
                                        name, c.port, c.proc.returncode)
                    if not lst:
                        self._children.pop(name, None)

    def status(self) -> Dict[str, List[int]]:
        with self._mu:
            return {name: [c.port for c in lst]
                    for name, lst in self._children.items()}

    # -- lifecycle -----------------------------------------------------------
    def start(self, port: Optional[int] = None) -> int:
        actual = self.rpc.serve_background(
            port if port is not None else self.port, host="0.0.0.0")
        self.port = actual
        # child ports [port+1, port+max] (jubavisor.cpp port_pool_)
        self._pool = list(range(actual + 1, actual + 1 + self.max_children))
        membership.register_supervisor(self.coord, self.host, actual)
        self._reaper.start()
        log.info("jubavisor listening on %d", actual)
        return actual

    def join(self) -> None:
        self._stop_event.wait()

    def stop(self) -> None:
        self._stop_event.set()
        self.stop_all()
        self.rpc.stop()
        self.coord.close()


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="jubavisor")
    p.add_argument("-p", "--rpc-port", type=int, default=9198)
    p.add_argument("-z", "--coordinator", default="")
    p.add_argument("-m", "--max", type=int, default=10,
                   help="max children (= port pool size)")
    p.add_argument("-l", "--logfile", default="",
                   help="redirect child output here")
    p.add_argument("-b", "--host", default="127.0.0.1",
                   help="address to register in the supervisor registry")
    ns = p.parse_args(argv)
    spec = resolve_coordinator(ns.coordinator)
    if not spec:
        print("no coordinator: pass -z or set JUBATUS_COORDINATOR/ZK",
              file=sys.stderr)
        return 1
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s [jubavisor] %(message)s")
    visor = Jubavisor(spec, ns.rpc_port, ns.max, ns.logfile, host=ns.host)
    signal.signal(signal.SIGTERM, lambda *_: visor.stop())
    signal.signal(signal.SIGINT, lambda *_: visor.stop())
    visor.start()
    visor.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
