"""Native library tests: compile-on-demand via g++, bit-parity with the
Python paths, and the C splitter plugin ABI (the dlopen seam of
SURVEY.md §2.8 done natively).
"""

from __future__ import annotations

import os
import subprocess
import zlib

import numpy as np
import pytest

from jubatus_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="g++ unavailable / native build failed"
)


def test_crc32_matches_zlib(rng):
    for size in (0, 1, 7, 256, 4096):
        data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        assert native.crc32(data) == (zlib.crc32(data) & 0xFFFFFFFF)


def test_hash_names_matches_python():
    from jubatus_tpu.core.fv.hashing import FeatureHasher

    hasher = FeatureHasher(dim_bits=16)
    names = [f"key${i}@space#bin/bin" for i in range(500)] + ["", "日本語テスト"]
    got = native.hash_names(names, hasher._mask)
    want = [hasher.index(n, remember=False) for n in names]
    assert got.tolist() == want


def test_index_many_uses_native_and_remembers(monkeypatch):
    from jubatus_tpu.core.fv.hashing import FeatureHasher

    monkeypatch.setenv("JUBATUS_TPU_NATIVE", "1")  # native path is opt-in
    hasher = FeatureHasher(dim_bits=16)
    names = ["alpha", "beta", "gamma"]
    idxs = hasher.index_many(names)
    assert idxs == [hasher.index(n, remember=False) for n in names]
    assert hasher.name_of(idxs[0]) == "alpha"


def test_converter_convert_same_with_and_without_native(monkeypatch):
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter

    conf = {
        "string_rules": [{"key": "*", "type": "space",
                          "sample_weight": "tf", "global_weight": "bin"}],
        "num_rules": [{"key": "*", "type": "num"}],
    }
    d = Datum({"txt": "a b a c", "x": 2.5})
    monkeypatch.setenv("JUBATUS_TPU_NATIVE", "1")
    with_native = make_fv_converter(conf).convert(d)
    monkeypatch.setenv("JUBATUS_TPU_NATIVE", "0")
    without = make_fv_converter(conf).convert(d)
    assert with_native == without


@pytest.fixture(scope="module")
def sample_splitter_so(tmp_path_factory):
    out = native.build("sample_ngram_splitter")
    if out is None:
        pytest.skip("cannot build sample splitter")
    return out


def test_native_splitter_plugin(sample_splitter_so):
    split = native.load_native_splitter(sample_splitter_so, {"char_num": "2"})
    assert split("abcd") == ["ab", "bc", "cd"]
    assert split("a") == []


def test_native_splitter_through_converter(sample_splitter_so):
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter

    conf = {
        "string_types": {
            "bigram": {"method": "dynamic", "path": sample_splitter_so,
                       "char_num": "2"},
        },
        "string_rules": [{"key": "*", "type": "bigram",
                          "sample_weight": "bin", "global_weight": "bin"}],
    }
    named = make_fv_converter(conf).convert_named(Datum({"t": "abc"}))
    terms = {k.split("$")[1].split("@")[0] for k in named}
    assert terms == {"ab", "bc"}


def test_native_splitter_bad_params(sample_splitter_so):
    from jubatus_tpu.core.fv.converter import ConverterError

    with pytest.raises(ConverterError, match="rejected"):
        native.load_native_splitter(sample_splitter_so, {"char_num": "0"})


def test_make_builds_both_libraries():
    res = subprocess.run(["make", "-C", native.NATIVE_DIR],
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    assert os.path.exists(os.path.join(native.BUILD_DIR, "libjt_native.so"))
