"""The readers of the phase spans and counters, in the CPU rehearsal of
each cell's kind: a span is the program's own clock and a counter counts,
so they need no chip, and a traced rehearsal prints a number for each."""

import pytest

import pbtest_util as u
from test_rehearsal import checkout

PHASE_METRICS = {
    "train": ["coalescer.train_queue_wait_ms", "coalescer.short_flush_share",
              "step.train_stage_ms_per_flush",
              "step.train_lock_wait_ms_per_flush",
              "step.train_dispatch_ms_per_flush", "step.train_pad_share"],
    "serve": ["coalescer.classify_queue_wait_ms",
              "step.classify_lock_wait_ms", "step.classify_stage_ms",
              "step.classify_wait_ms", "ingest.classify_encode_ms"],
}


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_traced_rehearsal_reads_the_phase_metrics(tmp_path, kind):
    root, cell = checkout(tmp_path, kind)
    res = u.rehearse(root, cell, trace=True)
    assert res["correct"] is True, res["compared"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(PHASE_METRICS[kind]) <= set(got), sorted(got)
    assert all(got[k] >= 0 for k in PHASE_METRICS[kind])
    # the CPU's allocator reports no high-water mark: left out, never 0
    assert "device.peak_bytes_in_use" not in got
    if kind == "train":
        # six connections of 100 rows never fill seven eighths of 8,192
        assert got["coalescer.short_flush_share"] == 100.0
        assert 0 < got["step.train_pad_share"] < 50
        assert got["step.train_stage_ms_per_flush"] > 0
        assert got["step.train_dispatch_ms_per_flush"] > 0
    else:
        assert got["step.classify_stage_ms"] > 0
        assert got["ingest.classify_encode_ms"] > 0
