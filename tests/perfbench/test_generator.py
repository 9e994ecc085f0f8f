"""A generator is a function of the seed: same seed, same bytes; another
seed, other rows. ``click_log``'s rows have the configuration's shape and
are the rows the benchmark has always sent, and the reference's hashing
is the program's."""

import hashlib

import numpy as np

import pytest

import pbtest_util
from harness import datagen, wire

config = pbtest_util.load_config
make_rows = pbtest_util.make_rows


def test_same_seed_same_bytes_other_seed_other_rows():
    conf = config()
    big = 3000000019     # more than 32 signed bits hold
    a = make_rows(conf, big, 10, 200)
    b = make_rows(conf, big, 10, 200)
    c = make_rows(conf, big + 1, 10, 200)
    d = make_rows(conf, big, 11, 200)
    engine = pbtest_util.subject(1 << 16).engine
    for encode in engine.ENCODERS.values():
        assert encode("n", a) == encode("n", b)
    assert a != c and a != d


def test_rows_have_the_click_logs_shape():
    conf = config()
    rows = make_rows(conf, 7, 0, 4000)
    label, strings, nums = rows[0]
    assert len(strings) == 26 and len(nums) == 13
    assert [k for k, _ in nums] == [f"I{i}" for i in range(1, 14)]
    assert all(len(v) == 8 for _k, v in strings)
    assert {r[0] for r in rows} == set(conf["data"]["labels"])
    rate = np.mean([r[0] == conf["data"]["labels"][0] for r in rows])
    assert 0.01 < rate < 0.08        # a click rate near 3%
    # every row has features_per_row distinct columns at the real width
    dim = conf["model"]["converter"]["hash_max_size"]
    featurize = pbtest_util.subject(dim).featurize
    widths = [len(featurize(r)) for r in rows[:200]]
    assert max(widths) == conf["features_per_row"]
    # a Zipf field: the commonest value of the widest field is common
    top = max(np.unique([r[1][25][1] for r in rows], return_counts=True)[1])
    assert top > 40


#: recorded on the parent of the PR that moved the generator out of the
#: harness (5614cde), at seed 2200000123: ``repr`` of 2,000 rows of stream
#: 10, and the 64 encoded requests of ``traffic/train.json``'s group 0
GOLDEN = {
    "rows": "b16738cc46498b1840e126643569b5a9a8cc2cf586a39fd7741c8227b51f7f46",
    "pool": "f1402e4434e71c6693dfecefda5f0c900c77948b9c7774a1ce023fac3d118035",
}


@pytest.mark.parametrize("what", sorted(GOLDEN))
def test_click_log_sends_what_the_benchmark_has_always_sent(what):
    """The cells' rows and request bytes are those of the generator that
    lived in the harness: a reading of before the move compares with one
    of after it."""
    from harness import cell

    if what == "rows":
        conf = config()
        digest = hashlib.sha256(repr(make_rows(
            conf, 2200000123, 10, 2000)).encode()).hexdigest()
    else:
        _bench, run = cell.load_cell(pbtest_util.REPO, pbtest_util.BENCH,
                                     "criteo_arow.train")
        group = run.traffic["groups"][0]["name"]
        pool = cell._pools(run, 2200000123, run.config["cluster_name"])[group]
        assert len(pool) == 64 and sum(map(len, pool)) == 17185728
        digest = hashlib.sha256(b"".join(bytes(f) for f in pool)).hexdigest()
    assert digest == GOLDEN[what]


def test_the_shipped_configurations_name_click_log_and_it_wants_two_labels():
    for name in ("criteo_arow", "criteo_arow_cross", "criteo_arow_sharded4"):
        assert config(name)["data"]["generator"] == "click_log"
    conf = config()
    conf["data"]["labels"] = ["a", "b", "c"]
    with pytest.raises(ValueError, match="data.labels"):
        make_rows(conf, 7, 0, 10)


def test_zipf_ranks_stay_in_range_and_skew():
    u = np.linspace(0.0, 0.999999, 10001)
    r = datagen.zipf_ranks(u, 1000, 1.05)
    assert r.min() == 0 and r.max() <= 999
    assert (r == 0).mean() > 0.05 and (r[1:] >= r[:-1]).all()


def test_message_id_is_patched_in_place():
    frame = wire.encode_request("train", ["n", []])
    import msgpack

    assert msgpack.unpackb(bytes(wire.with_msgid(frame, 77)))[1] == 77
    assert msgpack.unpackb(bytes(wire.with_msgid(frame, 2 ** 32 - 1)))[:3] \
        == [0, 2 ** 32 - 1, "train"]


def test_reference_featurize_is_the_programs_converter():
    """The plain reference states the converter's rules on its own; this
    ties its statement to the program's converter, feature for feature."""
    from jubatus_tpu.core.datum import Datum
    from jubatus_tpu.core.fv.converter import make_fv_converter

    conf = config()
    dim = 1 << 20
    conv = make_fv_converter(dict(conf["model"]["converter"],
                                  hash_max_size=dim))
    featurize = pbtest_util.subject(dim).featurize
    for row in make_rows(conf, 11, 3, 20):
        d = Datum()
        for k, v in row[1]:
            d.add_string(k, v)
        for k, v in row[2]:
            d.add_number(k, v)
        assert dict(conv.convert(d)) == featurize(row)


@pytest.mark.parametrize("change", [
    {"string_rules": [{"key": "*", "type": "space", "sample_weight": "tf",
                       "global_weight": "bin"}]},
    {"num_rules": [{"key": "*", "type": "log"}]},
    {"string_filter_rules": [{"key": "*", "type": "x", "suffix": "-f"}]},
])
def test_reference_refuses_a_converter_rule_it_does_not_state(change):
    """A configuration whose converter the reference does not implement
    fails loudly, before a run; it is never featurized by another rule."""
    conf = config()
    conf["model"]["converter"].update(change)
    with pytest.raises(NotImplementedError):
        pbtest_util.subject(1 << 16, conf)


def test_reference_reads_the_rules_keys_and_the_label_count():
    conf = config()
    conf["model"]["converter"]["num_rules"] = [{"key": "I1*", "type": "num"}]
    conf["data"]["labels"] = ["a", "b", "c"]
    sub = pbtest_util.subject(1 << 16, conf)
    row = ("a", [("C1", "x")], [("I1", 2.0), ("I10", 3.0), ("I2", 5.0)])
    assert sorted(sub.featurize(row).values()) == [1.0, 2.0, 3.0]
    import numpy as np

    assert sub.model(np.arange(4), "float32").w.shape == (3, 4)
