"""The compact bench summary must survive a last-2000-chars stdout window.

Round 4's artifact of record lost its own headline because the driver
keeps only the tail of stdout and the headline keys printed first.
benchlib.summarize() is the fix: one compact JSON line, headline-first
key priority, hard byte budget.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import benchlib  # noqa: E402


def _payload(n_extra=0, **extra):
    e = dict(extra)
    for i in range(n_extra):
        e[f"mix_round_ms_padding_key_with_a_long_name_{i:04d}"] = 123.456789
    return {"metric": "classifier_train_samples_per_sec_arow_d2^20",
            "value": 321654.9, "unit": "samples/s", "vs_baseline": 0.62,
            "extra": e}


def test_summary_fits_budget_under_heavy_extra():
    s = benchlib.summarize(_payload(200, bench_platform="tpu"), "BENCH_FULL_r05.json")
    assert len(json.dumps(s)) <= benchlib.SUMMARY_BYTES
    assert s["keys_dropped"] > 0


def test_headline_and_platform_always_survive():
    s = benchlib.summarize(
        _payload(500, bench_platform="tpu",
                 baseline_samples_per_sec=522000.0,
                 **{"tpu_d2^24_samples_per_sec": 238000.0}),
        "BENCH_FULL_r05.json")
    assert s["metric"] == "classifier_train_samples_per_sec_arow_d2^20"
    assert s["value"] == 321654.9
    assert s["extra"]["bench_platform"] == "tpu"
    assert s["extra"]["tpu_d2^24_samples_per_sec"] == 238000.0
    assert s["full"] == "BENCH_FULL_r05.json"


def test_priority_order_beats_insertion_order():
    # a key listed in SUMMARY_EXACT must win over earlier-inserted noise
    e = {}
    for i in range(300):
        e[f"aaa_noise_{i:04d}"] = "x" * 40
    e["e2e_proxy_vs_direct"] = 0.83
    s = benchlib.summarize(_payload(0, **e), "f.json")
    assert s["extra"]["e2e_proxy_vs_direct"] == 0.83


def test_no_truncation_when_small():
    s = benchlib.summarize(_payload(0, bench_platform="cpu"), "f.json")
    assert s["keys_dropped"] == 0
    assert s["extra"] == {"bench_platform": "cpu"}


def test_round_trip_is_valid_json_line():
    s = benchlib.summarize(_payload(50, bench_platform="cpu"), "f.json")
    line = json.dumps(s)
    assert "\n" not in line
    assert json.loads(line)["unit"] == "samples/s"


def test_bench_refuses_to_run_off_the_chip():
    """A device metric comes from the chip or not at all: off the TPU
    bench.py exits non-zero at start and prints no result."""
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "bench.py")], cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr


def test_bench_exits_nonzero_when_a_phase_raised(monkeypatch):
    """A phase that raises leaves its *_error key in the output AND fails
    the run; nothing is swallowed into an exit code of 0."""
    import types

    import bench

    class Dev:
        platform, device_kind = "tpu", "test"

    payloads = []
    monkeypatch.setattr(bench.jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(bench.jax, "device_put", lambda x, _dev: x)
    monkeypatch.setattr(bench, "require_tpu", lambda: Dev())
    monkeypatch.setattr(bench, "D", 1 << 10)
    monkeypatch.setattr(bench, "BATCH", 64)
    monkeypatch.setattr(bench, "STEPS", 1)
    monkeypatch.setattr(bench, "WARMUP_STEPS", 1)
    monkeypatch.setattr(bench, "d24_throughput", lambda: 123.0)
    monkeypatch.setattr(bench, "cpp_arow_baseline",
                        lambda *a, **k: (1000.0, "stub"))
    monkeypatch.setattr(bench, "emit", payloads.append)

    def boom(*_a, **_k):
        raise RuntimeError("mix plane exploded")

    ok = types.ModuleType("ok")
    ok.collect = ok.cpu_axes = ok.chip_l_sweep = lambda *a, **k: {}
    bad = types.ModuleType("bad")
    bad.collect = boom
    monkeypatch.setitem(sys.modules, "bench_mix", bad)
    monkeypatch.setitem(sys.modules, "bench_chip_axes", ok)
    monkeypatch.setitem(sys.modules, "bench_serving", ok)
    assert bench.main() == 1
    extra = payloads[0]["extra"]
    assert "mix plane exploded" in extra["mix_error"]
    assert extra["failed_phases"] == ["mix_error"]
    assert extra["tpu_d2^24_samples_per_sec"] == 123.0

    payloads.clear()
    monkeypatch.setitem(sys.modules, "bench_mix", ok)
    assert bench.main() == 0
    assert "failed_phases" not in payloads[0]["extra"]
