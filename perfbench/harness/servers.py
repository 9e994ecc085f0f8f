"""Starting and stopping the system under test: the server of a
configuration as a child process, through the entry point a user calls.
This process never imports jax; the child holds the chip.

One standalone server is all that a cell runs today. A configuration of
several replicas needs the rest of ``chip_smoke.py``'s recipe (PR 21: a
coordinator, one process per chip bound through libtpu's environment,
every member of a jax world signalled before any is waited for), which
comes back with the cell that needs it (PERF.md, Open questions)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from .loadgen import Client

HOST = "127.0.0.1"


def free_port() -> int:
    with socket.socket() as s:
        s.bind((HOST, 0))
        return s.getsockname()[1]


class Child:
    def __init__(self, tag: str, argv: List[str], env: Dict[str, str],
                 cwd: str, log_dir: str) -> None:
        self.tag = tag
        self.log_path = os.path.join(log_dir, f"{tag}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(argv, cwd=cwd, env=env,
                                     stdout=self._log, stderr=self._log)

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")

    def alive(self) -> bool:
        return self.proc.poll() is None

    def close_log(self) -> None:
        self._log.close()


class Fleet:
    """The servers of one run, stopped together."""

    def __init__(self, root: str, run_dir: str, config: Dict[str, Any],
                 connections: int, rehearse: bool, trace: bool,
                 server_entry: Optional[List[str]] = None) -> None:
        self.root = root
        self.run_dir = run_dir
        self.config = config
        self.rehearse = rehearse
        self.children: List[Child] = []
        self.servers: List[Child] = []
        self.ports: List[int] = []
        self.profile_dirs: List[str] = []
        self.name = config["cluster_name"]
        os.makedirs(run_dir, exist_ok=True)
        model = json.loads(json.dumps(config["model"]))
        if rehearse:
            model["converter"]["hash_max_size"] = \
                config["rehearsal"]["hash_max_size"]
        cfg_path = os.path.join(run_dir, "model.json")
        with open(cfg_path, "w") as f:
            json.dump(model, f)
        entry = server_entry or [sys.executable, "-m", "jubatus_tpu.server"]
        if int(config["replicas"]) != 1:
            raise NotImplementedError("one standalone server to a "
                                      "configuration, so far")
        port = free_port()
        argv = entry + [config["engine"], "-f", cfg_path, "-d", run_dir,
                        "-c", str(connections + 16), "-p", str(port)] \
            + list(config["server_flags"])
        if trace:
            pdir = os.path.join(run_dir, "profile0")
            self.profile_dirs.append(pdir)
            argv += ["--profile-dir", pdir]
        self.servers.append(self._spawn("server0", argv))
        self.ports.append(port)

    def _spawn(self, tag: str, argv: List[str]) -> Child:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [self.root] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        child = Child(tag, argv, env, self.root, self.run_dir)
        self.children.append(child)
        return child

    def address(self, i: int):
        return (HOST, self.ports[i])

    def check_alive(self) -> None:
        for c in self.children:
            if not c.alive():
                raise RuntimeError(f"{c.tag} died with exit code "
                                   f"{c.proc.returncode}:\n{c.log_tail()}")

    def wait_for_status(self, i: int, timeout: float) -> Dict[str, Any]:
        """Poll ``get_status`` until server ``i`` answers."""
        deadline = time.monotonic() + timeout
        while True:
            self.check_alive()
            try:
                with Client(self.address(i), timeout=30.0) as c:
                    return next(iter(c.call("get_status", self.name).values()))
            except (OSError, RuntimeError, ValueError) as e:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"server{i} did not answer get_status in "
                        f"{timeout:.0f}s ({e!r}):\n"
                        f"{self.servers[i].log_tail()}") from e
                time.sleep(0.25)

    def status(self, i: int) -> Dict[str, Any]:
        with Client(self.address(i), timeout=60.0) as c:
            return next(iter(c.call("get_status", self.name).values()))

    def stop(self, timeout: float = 60.0) -> List[str]:
        """Signal every child before waiting for any, kill what does not
        leave; returns what went wrong."""
        problems: List[str] = []
        for c in self.children:
            if c.alive():
                c.proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        for c in self.children:
            try:
                c.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                problems.append(f"{c.tag} ignored SIGTERM; killed")
                c.proc.kill()
                c.proc.wait(timeout=30)
        for c in self.children:
            c.close_log()
        return problems
