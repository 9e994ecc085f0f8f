"""What keeps a CPU run from looking like a chip run, and the chip with the
one process that serves from it (ISSUE 21).

Everything here runs on the CPU, in fresh subprocesses where the property
is about a process: which ones initialise a jax backend, where the compile
cache goes, what the native loader rebuilds, and that ``chip_smoke.py``
passes only as a marked rehearsal off the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env=None, timeout=600, **kw):
    # conftest's eight virtual devices make every child slower to start
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    base["PYTHONPATH"] = REPO
    base.update(env or {})
    return subprocess.run(argv, cwd=REPO, env=base, capture_output=True,
                          text=True, timeout=timeout, **kw)


def _py(prog, env=None, timeout=300):
    return _run([sys.executable, "-c", prog], env=env, timeout=timeout)


# -- (a) chip_smoke.py -----------------------------------------------------------

def test_chip_smoke_rehearsal_passes_and_says_so():
    proc = _run([sys.executable, "chip_smoke.py", "--rehearse-on-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert "rehearsal" in last
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    report = json.loads(proc.stdout.strip().splitlines()[-2])
    assert report["classifier"]["accuracy"] >= 0.9
    assert report["classifier"]["transport"] == "native"
    assert report["classifier"]["ingest_native"] is True
    assert report["kernel_parity"]["cases"] > 0


def test_chip_smoke_without_the_rehearsal_argument_fails_off_the_chip():
    proc = _run([sys.executable, "chip_smoke.py"],
                env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line
    assert "FAILED" in proc.stderr


# -- (b) only an engine server initialises a backend ------------------------------

_PROXY_PROG = """
import json, sys
from jubatus_tpu.coord.memory import MemoryCoordinator, _Store
from jubatus_tpu.server.proxy import Proxy, ProxyArgs
from jubatus_tpu.utils.runtime_telemetry import jax_backend_initialized

proxy = Proxy(ProxyArgs(engine="classifier", coordinator="(shared)"),
              coord=MemoryCoordinator(_Store()))
sample = proxy.telemetry.sample()
status = next(iter(proxy.get_proxy_status().values()))
import jax
from jax._src import xla_bridge
print(json.dumps({
    "sampled": sample["jax_backend_initialized"],
    "status": status["runtime.jax_backend_initialized"],
    "transport": status["rpc.transport"],
    "has_device_keys": "jax_platform" in sample,
    "really": xla_bridge.backends_are_initialized(),
    "helper": jax_backend_initialized()}))
"""


def test_proxy_never_initialises_a_backend():
    proc = _py(_PROXY_PROG)
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc == {"sampled": False, "status": False,
                   "transport": doc["transport"], "has_device_keys": False,
                   "really": False, "helper": False}
    assert doc["transport"] in ("native", "python")


def test_telemetry_reports_the_device_once_the_process_has_one():
    proc = _py(
        "import json, jax.numpy as jnp\n"
        "from jubatus_tpu.utils import runtime_telemetry as rt\n"
        "x = jnp.ones(4) + 1\n"
        "print(json.dumps(rt._jax_sample()))\n")
    assert proc.returncode == 0, proc.stderr[-3000:]
    s = json.loads(proc.stdout.strip().splitlines()[-1])
    assert s["jax_backend_initialized"] is True
    assert s["jax_platform"] == "cpu" and s["jax_device_kind"]
    assert s["jax_device_count"] >= 1
    assert s["jax_array_devices"] and s["jax_live_arrays"] >= 1
    assert "jax_cache_hits" in s and "jax_cache_misses" in s


_JUBACONFIG_PROG = """
import json, os, sys, tempfile
from jubatus_tpu.cmd import jubaconfig
d = tempfile.mkdtemp()
cfg = os.path.join(d, "c.json")
with open(cfg, "w") as f:
    json.dump({"method": "AROW", "parameter": {"regularization_weight": 1.0},
               "converter": {"num_rules": [{"key": "*", "type": "num"}]}}, f)
rc = jubaconfig.main(["-c", "write", "-z", os.path.join(d, "coord"),
                      "-t", "classifier", "-n", "n1", "-f", cfg])
import jax
from jax._src import xla_bridge
print(json.dumps({"rc": rc, "platforms": jax.config.jax_platforms,
                  "backends": sorted(xla_bridge._backends)}))
"""


def test_jubaconfig_write_validates_on_the_cpu():
    # the environment asks for no platform: the tool itself must pick CPU
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    proc = subprocess.run([sys.executable, "-c", _JUBACONFIG_PROG], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["rc"] == 0
    assert doc["platforms"] == "cpu"
    assert doc["backends"] == ["cpu"]


# -- (c) the compile cache ----------------------------------------------------------

_CACHE_PROG = (
    "from jubatus_tpu.utils.compile_cache import configure\n"
    "import jax\n"
    "print(repr(configure()), repr(jax.config.jax_compilation_cache_dir))\n")


def _unpinned_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


def _cache_proc(env):
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROG], cwd="/",
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip()


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    d = str(tmp_path / "elsewhere")
    out = _cache_proc(_unpinned_env(JAX_COMPILATION_CACHE_DIR=d))
    assert out == f"{d!r} {d!r}"
    from jubatus_tpu.utils import compile_cache

    assert compile_cache.DEFAULT_DIR not in out


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout():
    a = _cache_proc(_unpinned_env())
    b = _cache_proc(_unpinned_env())
    want = os.path.join(REPO, ".jax_cache")
    assert a == b == f"{want!r} {want!r}"


def test_cpu_pinned_process_gets_no_cache_dir():
    out = _cache_proc(_unpinned_env(JAX_PLATFORMS="cpu"))
    assert out == "'' None"


def test_one_helper_sets_the_cache_directory():
    # status code may READ the option; only the helper may set it
    hits = subprocess.run(
        ["grep", "-rlnE", "--include=*.py",
         r"update\(\s*[\"']jax_compilation_cache_dir|set_cache_dir"
         r"|initialize_cache",
         "jubatus_tpu", "bench.py", "bench_mix.py", "bench_serving.py",
         "benchlib.py", "chip_smoke.py", "tools", "__graft_entry__.py"],
        cwd=REPO, capture_output=True, text=True).stdout.split()
    assert hits == ["jubatus_tpu/utils/compile_cache.py"]


# -- (d) the native loader ------------------------------------------------------------

@pytest.fixture
def native_sandbox(tmp_path, monkeypatch):
    from jubatus_tpu import native

    src_dir = tmp_path / "native"
    src_dir.mkdir()
    monkeypatch.setattr(native, "NATIVE_DIR", str(src_dir))
    monkeypatch.setattr(native, "BUILD_DIR", str(src_dir / "build"))
    return native, src_dir


def test_native_build_is_keyed_by_content_not_mtime(native_sandbox):
    native, src_dir = native_sandbox
    src = src_dir / "probe.cpp"
    src.write_text('extern "C" int jt_probe() { return 1; }\n')
    first = native.build("probe")
    if first is None:
        pytest.skip("no C++ toolchain")
    built_at = os.path.getmtime(first)
    # a newer source with the same bytes (a fresh checkout, a copy to
    # another machine) is the same library
    os.utime(src, (built_at + 3600, built_at + 3600))
    assert native.build("probe") == first
    assert os.path.getmtime(first) == built_at
    # an OLDER source with different bytes is a different library
    src.write_text('extern "C" int jt_probe() { return 2; }\n')
    os.utime(src, (built_at - 3600, built_at - 3600))
    second = native.build("probe")
    assert second is not None and second != first
    import ctypes

    assert ctypes.CDLL(first).jt_probe() == 1
    assert ctypes.CDLL(second).jt_probe() == 2


def test_native_build_failure_is_reported_not_raised(native_sandbox, caplog):
    native, src_dir = native_sandbox
    (src_dir / "broken.cpp").write_text("this is not C++\n")
    with caplog.at_level("WARNING"):
        assert native.build("broken") is None
    assert native.build("missing") is None
    if any("did not run" in r.message for r in caplog.records):
        pytest.skip("no C++ toolchain")
    assert any("native build of broken failed" in r.message
               for r in caplog.records)
    assert not list((src_dir / "build").glob("*.tmp"))


# -- status says which transport and parser serve --------------------------------------

def test_status_names_transport_and_ingest():
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    conf = {"method": "AROW", "parameter": {"regularization_weight": 1.0},
            "converter": {"num_rules": [{"key": "*", "type": "num"}]}}
    srv = EngineServer("classifier", conf,
                       ServerArgs(engine="classifier", telemetry_interval=0))
    srv.start(0)
    try:
        st = next(iter(srv.get_status().values()))
        assert st["rpc.transport"] == srv.rpc.transport
        assert st["rpc.transport"] in ("native", "python")
        assert st["ingest.native"] is (srv.rpc.transport == "native")
        assert st["runtime.jax_backend_initialized"] is True
        assert st["runtime.jax_platform"] == "cpu"
        assert st["runtime.jax_array_devices"]
    finally:
        srv.stop()


def test_tpu_process_env_gives_each_process_its_own_chip():
    from jubatus_tpu.cmd import tpu_process_env

    ports = [9000, 9007, 9003, 9001]
    envs = [tpu_process_env(i, ports) for i in range(4)]
    assert [e["TPU_VISIBLE_DEVICES"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
    assert {e["TPU_PROCESS_ADDRESSES"] for e in envs} == {
        "localhost:9000,localhost:9007,localhost:9003,localhost:9001"}
    assert all(e["TPU_PROCESS_BOUNDS"] == "2,2,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    with pytest.raises(ValueError):
        tpu_process_env(0, [9000, 9001, 9002])


def test_jubavisor_binds_each_child_to_a_chip(tmp_path, monkeypatch):
    """One start of four children: child k gets chip k's variables and,
    when a jax world is asked for, its rank in it. One child alone keeps
    the host's own environment."""
    from jubatus_tpu.cmd import jubavisor

    spawned = []

    class FakeProc:
        pid = 1

        def poll(self):
            return None

    def fake_popen(cmd, stdout=None, stderr=None, env=None):
        spawned.append((cmd, env))
        return FakeProc()

    monkeypatch.setattr(jubavisor.subprocess, "Popen", fake_popen)
    visor = jubavisor.Jubavisor(str(tmp_path / "coord"), port=7000,
                                max_children=10)
    visor._pool = list(range(7001, 7011))
    try:
        assert visor.start_procs(
            "classifier/c1", 4,
            {"mixer": "collective_mixer", "jax_processes": 8,
             "jax_process_id": 4, "jax_coordinator": "10.0.0.1:9999"}) == 0
        assert len(spawned) == 4
        for k, (cmd, env) in enumerate(spawned):
            assert env["TPU_VISIBLE_DEVICES"] == str(k)
            assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
            assert env["TPU_PROCESS_PORT"] == str(7001 + k + 10)
            flags = dict(zip(cmd[4::2], cmd[5::2]))
            assert flags["-p"] == str(7001 + k)
            assert flags["--jax-processes"] == "8"
            assert flags["--jax-process-id"] == str(4 + k)
            assert flags["--jax-coordinator"] == "10.0.0.1:9999"
        spawned.clear()
        assert visor.start_procs("classifier/c2", 1, {}) == 0
        (cmd, env), = spawned
        assert env is None and "--jax-process-id" not in cmd
        # more children than ports left: nothing starts
        spawned.clear()
        assert visor.start_procs("classifier/c3", 6, {}) == -1
        assert not spawned
    finally:
        visor._children.clear()
        visor.coord.close()
