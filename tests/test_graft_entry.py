"""The driver's entry points must work in the DRIVER environment.

The driver imports __graft_entry__ under the real accelerator platform
(one chip) — not under tests/conftest.py's virtual 8-CPU mesh. Round 1's
multichip gate failed precisely because dryrun_multichip assumed someone
else had provisioned virtual devices. These tests run the entry points in
a fresh subprocess WITHOUT conftest's env so what is tested is what the
driver actually runs.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _driver_env():
    """A copy of the environment with conftest's virtual-mesh vars removed,
    pinned to a single CPU device — the shape of the driver's world (one
    real device, no bootstrap help)."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "_JUBATUS_TPU_DRYRUN_CHILD")
    }
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"  # no accelerator in the test sandbox
    env["PYTHONPATH"] = REPO
    return env


def _run(prog: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", prog], env=_driver_env(), cwd=REPO,
        capture_output=True, text=True, timeout=900)


@pytest.mark.slow
def test_dryrun_multichip_bootstraps_from_one_device():
    """dryrun_multichip(8) with only 1 visible device must self-provision
    virtual CPU devices in a child process and succeed (VERDICT round 1:
    the gate crashed with 'mesh 4x2 needs 8 devices, have 1')."""
    proc = _run(
        "import jax\n"
        "assert len(jax.devices()) == 1, jax.devices()\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"
        "print('PARENT-OK')\n"
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARENT-OK" in proc.stdout


@pytest.mark.slow
def test_dryrun_multichip_odd_device_count():
    """Replica-only (1-D mesh) branch must bootstrap too."""
    proc = _run(
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(3)\n"
        "print('PARENT-OK')\n"
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARENT-OK" in proc.stdout


@pytest.mark.slow
def test_entry_compiles_single_device():
    proc = _run(
        "import jax, __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n"
        "print('ENTRY-OK')\n"
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ENTRY-OK" in proc.stdout
