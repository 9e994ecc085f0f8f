"""The width of a padded sparse batch (ISSUE 27): ``core/sparse.py
_width_bucket``'s eighth-octave ladder, the native parser's copy of it
(``native/fast_ingest.cpp pack``), and what a server counts of the widths
its flushes ran at. Gather and scatter cost per entry of the ``[B, K]``
arrays, padding included, so the rule is held here point by point."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import msgpack
import numpy as np
import pytest

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.core.fv.converter import make_fv_converter
from jubatus_tpu.core.sparse import (CSRBatch, SparseBatch, _bucket,
                                     _request_width, _width_bucket)
from jubatus_tpu.native import ingest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

native_only = pytest.mark.skipif(
    not ingest.available(), reason="native toolchain unavailable")


@pytest.mark.parametrize("n,width", [
    (1, 8), (8, 8), (9, 16), (39, 40), (64, 64), (65, 72), (129, 144),
    (780, 832), (1024, 1024), (1025, 1152)])
def test_the_ladders_values(n, width):
    assert _width_bucket(n) == width
    # and the row bucket is what it was: a power of two
    assert _bucket(n, 16) == max(16, 1 << (n - 1).bit_length())


def _rungs(upto):
    return sorted({_width_bucket(n) for n in range(1, upto + 1)})


def test_never_under_the_rows_never_an_eighth_over_and_monotone():
    last = 0
    for n in range(1, 5000):
        w = _width_bucket(n)
        assert w >= max(n, 8) and w % 8 == 0
        assert w <= n * 1.125 if n > 64 else w - n < 8
        assert w >= last
        assert _width_bucket(w) == w            # a rung is its own bucket
        last = w


def test_eight_rungs_an_octave_and_forty_to_1024():
    rungs = _rungs(4096)
    assert [r for r in rungs if r <= 128] == list(range(8, 129, 8))
    for lo in (128, 256, 512, 1024, 2048):
        octave = [r for r in rungs if lo < r <= 2 * lo]
        assert octave == list(range(lo + lo // 8, 2 * lo + 1, lo // 8))
    assert len([r for r in rungs if r <= 1024]) == 40


def test_the_widest_of_two_rungs_is_a_rung():
    """What ``service.py _pad_concat`` leans on: requests padded to the
    widest of a flush land on a width the ladder has."""
    rungs = _rungs(2200)
    for a in rungs:
        for b in rungs:
            assert _width_bucket(max(a, b)) == max(a, b)


@pytest.mark.parametrize("minimum,n,width", [
    (8, 3, 8), (16, 3, 16), (16, 39, 40), (12, 3, 16), (256, 39, 256)])
def test_the_minimum_is_a_floor_on_the_ladder(minimum, n, width):
    assert _width_bucket(n, minimum) == width


NUM_CONV = {"num_rules": [{"key": "*", "type": "num"}]}
#: widths on either side of a rung, at each step size up to 2,100 features
ROW_WIDTHS = [1, 7, 8, 9, 39, 40, 41, 64, 65, 127, 128, 129, 255, 257, 513,
              780, 832, 833, 1024, 1025, 1535, 2047, 2049, 2100]


def _num_rows(widest, rng):
    """Three rows of distinct numeric keys, the last the widest."""
    return [Datum({f"k{rng.integers(1 << 30)}_{j}": float(rng.uniform(0.5, 2))
                   for j in range(n)})
            for n in (max(widest // 2, 1), max(widest - 1, 1), widest)]


@native_only
@pytest.mark.parametrize("widest", ROW_WIDTHS)
def test_the_native_parser_pads_as_to_padded_and_from_vectors_do(widest):
    rng = np.random.default_rng(widest)
    rows = _num_rows(widest, rng)
    p = ingest.IngestParser(ingest.spec_from_converter_config(NUM_CONV), 24)
    conv = make_fv_converter(NUM_CONV, dim_bits=24)
    raw = msgpack.packb(["c", [["x", d.to_msgpack()] for d in rows]])
    _labels, idx, val = p.parse(raw)
    csr = conv.convert_batch(rows)
    padded = csr.to_padded()
    vectors = SparseBatch.from_vectors(csr.rows())
    most = int(np.count_nonzero(idx, axis=1).max())
    assert idx.shape[1] == _width_bucket(most)
    assert most in (widest, widest - 1)          # two keys may share a column
    for other in (padded, vectors):
        assert other.idx.shape == idx.shape
        assert other.idx.tobytes() == np.ascontiguousarray(idx).tobytes()
        assert other.val.tobytes() == np.ascontiguousarray(val).tobytes()
    # and through CSRBatch.from_vectors, the per-datum pipeline's bridge
    again = CSRBatch.from_vectors(
        [conv.convert(d) for d in rows]).to_padded()
    assert again.idx.tobytes() == padded.idx.tobytes()


# -- one rule for the width uneven rows run at (ISSUE 34) ---------------------
def _counts(kind, rng, n=500):
    """Entries a row of one request of a deployment's kind."""
    if kind == "click_log":          # every row alike: 39 of 40
        return np.full(n, 39)
    if kind == "cross":              # 780 less a few merged: of 832
        return 780 - rng.integers(0, 4, size=n)
    # documents: floor(exp(N(4.5, 0.8))) tokens, most of them distinct
    return np.clip(np.floor(np.exp(rng.normal(4.5, 0.8, size=n)) * 0.73),
                   1, 1000).astype(np.int64)


@pytest.mark.parametrize("kind,width,keeps_rung", [
    ("click_log", 40, True), ("cross", 832, True), ("text", None, False)])
def test_even_rows_keep_their_rung_and_uneven_rows_a_power_of_two(
        kind, width, keeps_rung):
    widths = set()
    for seed in range(40):
        counts = _counts(kind, np.random.default_rng(seed))
        w = _request_width(counts)
        rung = _width_bucket(int(counts.max()))
        filled = counts.sum() / (counts.size * rung)
        assert (filled >= 0.5) == keeps_rung
        assert w == (rung if keeps_rung else
                     1 << int(counts.max() - 1).bit_length())
        assert w >= counts.max()
        widths.add(w)
    if keeps_rung:
        assert widths == {width}
    else:   # a 500-document call: 512 or 1,024, where the rungs were many
        assert widths == {512, 1024}
        rungs = {_width_bucket(int(_counts(
            kind, np.random.default_rng(seed)).max())) for seed in range(40)}
        assert len(rungs) >= 6


@pytest.mark.parametrize("counts,width", [
    ([39] * 5, 40), ([780, 779, 780], 832), ([8, 1, 1, 1], 8),
    ([9, 1, 1, 1], 16), ([100, 3, 2, 1, 1, 1], 128), ([600, 1, 1], 1024),
    ([704, 352, 352], 704), ([704, 351, 351, 1], 1024), ([1], 8), ([], 8),
    ([1025, 2, 2], 2048), ([2049, 1, 1, 1, 1], 4096)])
def test_the_rule_point_by_point(counts, width):
    """At least half full at the fullest row's rung keeps the rung; under
    half is the power of two at or above the fullest row."""
    assert _request_width(np.array(counts, np.int64)) == width
    # the floor holds on both sides of the rule
    assert _request_width(np.array(counts, np.int64), 2048) >= 2048


def _uneven_rows(kind, rng):
    """Datums whose feature counts are a request's of the kind, cut down
    to a few rows (the fullest first)."""
    counts = np.sort(_counts(kind, rng, 40))[::-1]
    return [Datum({f"k{rng.integers(1 << 30)}_{j}": float(rng.uniform(0.5, 2))
                   for j in range(int(n))}) for n in counts]


@native_only
@pytest.mark.parametrize("kind", ["click_log", "cross", "text"])
def test_the_native_pack_and_the_python_twin_agree_on_the_rule(kind):
    rng = np.random.default_rng(34)
    rows = _uneven_rows(kind, rng)
    p = ingest.IngestParser(ingest.spec_from_converter_config(NUM_CONV), 24)
    conv = make_fv_converter(NUM_CONV, dim_bits=24)
    raw = msgpack.packb(["c", [["x", d.to_msgpack()] for d in rows]])
    _labels, idx, val, counts = p.parse_indexed(raw, counts=True)
    csr = conv.convert_batch(rows)
    per_row = np.diff(csr.row_offsets)
    assert idx.shape[1] == _request_width(per_row)
    assert counts.pow2 == (kind == "text")
    assert counts.tokens == counts.terms == 0        # no string rule cut one
    if kind == "text":
        assert idx.shape[1] == 1 << int(per_row.max() - 1).bit_length()
        assert idx.shape[1] != _width_bucket(int(per_row.max()))
    else:
        assert idx.shape[1] == _width_bucket(int(per_row.max()))
    for other in (csr.to_padded(), SparseBatch.from_vectors(csr.rows())):
        assert other.idx.shape == idx.shape
        assert other.idx.tobytes() == np.ascontiguousarray(idx).tobytes()
        assert other.val.tobytes() == np.ascontiguousarray(val).tobytes()
    # row bucketing pads rows, not the rule's count of them
    assert csr.to_padded(batch_bucket=64).idx.shape == (64, idx.shape[1])


TEXT_CONF = {
    "method": "AROW", "parameter": {"regularization_weight": 1.0},
    "converter": {"string_rules": [{"key": "message", "type": "space",
                                    "sample_weight": "bin",
                                    "global_weight": "bin"}],
                  "hash_max_size": 1 << 16}}


def _documents(n, seed, labels=20):
    rng = np.random.default_rng(seed)
    lengths = np.clip(np.floor(np.exp(rng.normal(4.5, 0.8, size=n))), 1,
                      2000).astype(int)
    return [(f"g{i % labels:02d}", Datum({"message": " ".join(
        f"w{int(w)}" for w in rng.zipf(1.3, size=m) % 60000)}))
        for i, m in enumerate(lengths)]


@native_only
def test_mixed_requests_and_the_quality_planes_rows_run_at_powers_of_two():
    """Calls of uneven documents from several connections at once: every
    flush runs at the widest of its requests, a power of two, and so does
    the quality plane's 8-row scoring of a sampled call; the parser's
    counts, the label growth and the widths are all in the registry."""
    import threading

    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    srv = EngineServer(
        "classifier", TEXT_CONF,
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        quality_sample=1.0))
    port = srv.start(0)
    calls = [_documents(n, 100 + n) for n in (40, 300, 8, 120, 500, 60)]
    try:
        def send(docs):
            with ClassifierClient("127.0.0.1", port, "") as c:
                assert c.train(docs) == len(docs)

        send(calls[0])                     # every label live, then at once
        threads = [threading.Thread(target=send, args=(docs,))
                   for docs in calls[1:]]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        with ClassifierClient("127.0.0.1", port, "") as c:
            answers = c.classify([d for _l, d in calls[1][:30]])
        counters = srv.rpc.trace.counters()
        status = next(iter(srv.get_status().values()))
        gauges = srv.rpc.trace.gauges()
    finally:
        srv.stop()
    assert all(len(row) == 20 for row in answers)
    train_w = {int(k.rsplit("_", 1)[1]): v for k, v in counters.items()
               if k.startswith("step.train.width_")}
    classify_w = {int(k.rsplit("_", 1)[1]): v for k, v in counters.items()
                  if k.startswith("step.classify.width_")}
    assert train_w and all(w & (w - 1) == 0 for w in train_w)
    # and each is handed to the device in the form its rows show: every
    # flush counts one program, and the wide ones (a 300- or 500-document
    # call among them) go as slabs, fewer entries than they arrived with
    programs = {k: v for k, v in counters.items()
                if k.startswith("step.train.program_")}
    assert sum(programs.values()) == sum(train_w.values())
    assert all(k.startswith(("step.train.program_slabs_",
                             "step.train.program_rows_")) for k in programs)
    assert 1 <= counters["step.train.slab_flushes"] <= sum(train_w.values())
    assert counters["step.train.slabs"] <= counters["step.train.slabs_padded"]
    assert counters["step.train.entries"] \
        <= counters["step.train.entries_issued"] \
        < counters["step.train.entries_padded"]
    assert classify_w and all(w & (w - 1) == 0 for w in classify_w)
    assert sum(train_w.values()) == status["microbatch.train_raw.flush_count"]
    # five scorings of 8 rows at their calls' widths (the first call
    # found no label to score), one classify call
    assert sum(classify_w.values()) == 6
    assert 5 <= counters["fv.pack.pow2"] <= 7
    docs = [d for call in calls for _l, d in call] \
        + [d for _l, d in calls[1][:30]]
    words = [d.string_values[0][1].split() for d in docs]
    assert counters["fv.tokens"] == sum(len(w) for w in words)
    assert counters["fv.terms"] == sum(len(set(w)) for w in words)
    assert counters["step.train.entries"] <= counters["fv.terms"]
    # twenty labels in the first call: 8 -> 16 -> 32 rows
    assert counters["model.label_grow"] == 2
    assert status["trace.model.grow_labels.count"] == 2
    assert (status["driver.num_labels"], status["driver.label_capacity"]) \
        == (20, 32)
    assert (gauges["model.labels_live"], gauges["model.label_capacity"]) \
        == (20, 32)


def _rows_of(counts, k, rng=None):
    """A flush ``[len(counts), k]``: row i has ``counts[i]`` entries packed
    from column 0 (any column but 0; one value)."""
    counts = np.asarray(counts)
    live = np.arange(k)[None, :] < counts[:, None]
    idx = np.where(live, 7 if rng is None else rng.integers(
        1, 1 << 20, size=live.shape), 0).astype(np.int32)
    return idx, live.astype(np.float32), np.zeros(len(counts), np.int32)


def _text_counts(rng, n):
    """Distinct words of ``n`` documents as news20_arow's generator has
    them (89.8 in the mean, the fullest near 984)."""
    return np.clip(np.floor(np.exp(rng.normal(4.5, 0.8, size=n)) * 0.73),
                   1, 984).astype(int)


@pytest.mark.parametrize("what,rows,k,counts,cut", [
    # criteo_arow: 39 of 40: under one slab's width, nothing read
    ("T", 8000, 40, 39, False),
    # criteo_arow_cross: 780 of 832 is 13 slabs of 64 a row; 104,000 slabs
    # in the bucket of 131,072 issue more than the rows do, and the buckets'
    # chance (4,500 rows in 8,192 against 58,500 slabs in 65,536) brings
    # the rows no further than 1.625 times the slabs
    ("X", 8000, 832, 780, False),
    ("X short", 7000, 832, 780, False),
    ("X, 9 calls", 4500, 832, 780, False),
    ("X, one call", 500, 832, 780, False),
    # news20_arow: 8,000 documents at 1,024 are 15,000 slabs in 16,384: an
    # eighth of the entries; a lone call of 500 likewise
    ("N", 8000, 1024, "text", True),
    ("N, one call", 500, 1024, "text", True),
    ("N, 9 calls", 4500, 1024, "text", True),
    # full rows can never be cut, whatever the two buckets' chance
    ("full", 4097, 512, 512, False),
    ("full", 17, 1024, 1024, False),
    # at the slab's own width a row is a slab; a multiple of it is counted
    ("one slab wide", 100, 64, 5, False),
    ("two slabs wide", 100, 128, 5, True),
    ("no multiple of a slab", 100, 160, 5, False),
    ("half full", 128, 128, 65, False),
])
def test_the_rule_that_cuts_a_flush_point_by_point(what, rows, k, counts, cut,
                                                   rng):
    """``S_b * W * c <= bsz * K``, from what the flush shows and nothing
    else: the benchmark's three train cells on their side of the line at
    every fill they meet, and what a cut flush is made of."""
    from jubatus_tpu.models import classifier as M

    assert (M._SLAB_WIDTH, M._SLAB_GAIN) == (64, 2)
    if isinstance(counts, str):
        counts = _text_counts(rng, rows)
        counts[0] = 984
    else:
        counts = np.full(rows, counts)
    idx, val, labels = _rows_of(counts, k)
    labels[:] = np.arange(rows) % 20
    bsz = _bucket(rows, 16)
    got = M._cut_slabs(idx, val, labels, bsz)
    slabs = int(np.sum(-(-counts // 64)))
    bucket = _bucket(slabs, 16)
    assert (got is not None) == cut, what
    assert cut == (k > 64 and k % 64 == 0
                   and bucket * 64 * 2 <= bsz * k), what
    if not cut:
        return
    sidx, sval, slabels, owner, n, entries = got
    assert n == slabs and entries == counts.sum()
    assert sidx.shape == sval.shape == (bucket, 64)
    assert slabels.shape == owner.shape == (bucket,)
    assert np.count_nonzero(sidx[:n]) == np.count_nonzero(sval) == entries
    assert not sidx[n:].any()
    # a document's slabs in order, its label on each, documents numbered
    # as they come; the padding slabs a document of their own, the last
    per_doc = -(-counts // 64)
    assert np.array_equal(owner[:n], np.repeat(np.arange(rows), per_doc))
    assert np.array_equal(slabels[:n], np.repeat(labels, per_doc))
    assert (owner[n:] == bucket - 1).all() and not slabels[n:].any()
    if "N" in what:
        assert bucket * 64 * 8 == bsz * k or bucket * 64 * 16 == bsz * k


def test_every_flush_of_one_to_sixteen_calls_runs_a_warmed_program(rng):
    """The program of a cut flush is its slab bucket's alone (a slab
    carries its label; the row bucket is no part of the shape), so the
    five lone calls of the benchmark's warm-up (500 to 8,000 documents)
    compile every program a flush of 1 to 16 calls of 500 can meet."""
    from jubatus_tpu.models import classifier as M

    def bucket_of(docs):
        idx, val, labels = _rows_of(_text_counts(rng, docs), 1024)
        return len(M._cut_slabs(idx, val, labels, _bucket(docs, 16))[0])

    warmed = {bucket_of(n) for n in (500, 1000, 2000, 4000, 8000)}
    assert warmed == {1024, 2048, 4096, 8192, 16384}
    assert {bucket_of(500 * calls) for calls in range(1, 17)} <= warmed


def _criteo_like(n, seed, n_num=13, n_str=26):
    rng = np.random.default_rng(seed)
    return [("pos" if i % 2 else "neg", Datum(
        {f"I{k}": float(rng.integers(1, 50)) for k in range(n_num)}
        | {f"C{k}": f"v{int(rng.integers(1 << 20))}" for k in range(n_str)}))
        for i in range(n)]


def _conf(cross: bool, dim: int):
    conv = {
        "string_rules": [{"key": "*", "type": "str", "sample_weight": "bin",
                          "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
        "hash_max_size": dim,
    }
    if cross:
        conv["combination_types"] = {"comb": {"method": "mul"}}
        conv["combination_rules"] = [{"key_left": "*", "key_right": "*",
                                      "type": "comb"}]
    return {"method": "AROW", "parameter": {"regularization_weight": 1.0},
            "converter": conv}


@native_only
def test_a_combination_parse_packs_its_expanded_rows_on_the_ladder():
    """The expanded rows (780 entries) ride at 832 like ``to_padded``'s.
    The rows before the cross product (39) keep the power of two for now:
    the benchmark's ``tests/perfbench/test_cross.py`` holds them at 64 and
    is not this PR's to edit (ROADMAP R-B1); they feed the plan cache and
    the device expansion, no gather or scatter a cell runs."""
    conv = _conf(True, 1 << 22)["converter"]
    p = ingest.IngestParser.from_converter_config(conv, 22)
    rows = _criteo_like(12, 5)
    raw = msgpack.packb(["c", [[lb, d.to_msgpack()] for lb, d in rows]])
    _labels, idx, val, cross = p.parse_indexed(raw, cross=True)
    assert idx.shape[1] == 832 and cross.base_idx.shape[1] == 64
    want = make_fv_converter(conv, dim_bits=22).convert_batch(
        [d for _lb, d in rows]).to_padded()
    assert want.idx.tobytes() == np.ascontiguousarray(idx).tobytes()
    assert np.allclose(want.val, val, rtol=1e-6)


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("cross,width,pad_lo,pad_hi", [
    (True, 832, 6.0, 8.0), (False, 40, 2.4, 2.6)])
def test_a_server_counts_the_width_its_flush_ran_at(
        cross, width, pad_lo, pad_hi, native, monkeypatch):
    """A 780-feature flush through ``_train_slots`` counts
    ``step.train.width_832`` and 6-8% of padding (it was 23.8% in the
    bucket of 1,024), a 39-feature one ``width_40`` (1 entry in 40)."""
    from jubatus_tpu.client import ClassifierClient
    from jubatus_tpu.server import EngineServer
    from jubatus_tpu.server.args import ServerArgs

    if native and not ingest.available():
        pytest.skip("native toolchain unavailable")
    if not native:
        monkeypatch.setenv("JUBATUS_TPU_NATIVE_INGEST", "0")
    srv = EngineServer(
        "classifier", _conf(cross, 1 << 20),
        args=ServerArgs(engine="classifier", listen_addr="127.0.0.1",
                        quality_sample=0.0))
    port = srv.start(0)
    try:
        assert ("train_raw" in srv.coalescers) == native
        with ClassifierClient("127.0.0.1", port, "") as c:
            assert c.train(_criteo_like(48, 9)) == 48
            assert len(c.classify([d for _l, d in _criteo_like(5, 10)])) == 5
        counters = srv.rpc.trace.counters()
    finally:
        srv.stop()
    widths = {k: v for k, v in counters.items()
              if k.startswith("step.train.width_")}
    assert widths == {f"step.train.width_{width}": 1}
    assert counters["step.train.entries_padded"] == 48 * width
    share = 100 * (1 - counters["step.train.entries"]
                   / counters["step.train.entries_padded"])
    assert pad_lo <= share < pad_hi, share


REHEARSAL = """
import json, os, pathlib, sys
sys.path.insert(0, {tests!r})
import pbtest_util as u
# a checkout of its own: the run directory is <root>/.perfbench_run/<cell>,
# and the benchmark's own test of this cell may be running in the repo's
root, bench = u.make_checkout(pathlib.Path({tmp!r}))
with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
    json.dump(bench, f)
res = u.rehearse(root, "criteo_arow_cross.train", trace=True)
print("RESULT " + json.dumps({{
    "correct": res["correct"], "compared": res["compared"],
    "metrics": {{k: v["value"] for k, v in res["metrics"].items()}}}}))
"""


def test_the_cross_cells_rehearsal_is_correct_at_width_832(tmp_path):
    """``tests/perfbench/test_cross.py::test_the_cells_rehearsal_is_correct``
    with the padding share the ladder leaves (that file is the benchmark's
    and holds the share the power-of-two bucket gave: ROADMAP R-B1). A
    process of its own, so that its time limit is its own."""
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(
            tests=os.path.join(REPO, "tests", "perfbench"),
            tmp=str(tmp_path))],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    assert m["ingest.cross_slots_per_row"] == 741
    assert m["ingest.cross_generic_share"] == 0
    assert m["ingest.cross_us_per_row"] > 0
    # 780 features less the merged ones, at the rung of 832
    assert 6.2 <= m["step.train_width_pad_share"] < 8
    assert m["step.train_upload_mb_per_flush"] > 1
    assert m["compile.in_window"] == 0
    assert m["ingest.sparse_flush_share"] == 100
