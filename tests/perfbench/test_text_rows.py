"""``generators/text_rows.py``: labelled documents for a ``space``-split
text key, every number from the configuration's ``data`` section. A
function of the seed, every label drawn, the word ranks and the lengths
distributed as ``data`` says, words that hold no space and never collide,
rows of two key suffixes that share no key, and rows the classifier's
encoders take."""

import collections
import os

import msgpack
import numpy as np
import pytest

import pbtest_util as u

#: what a news20-like configuration's ``data`` section may hold (no cell
#: uses it yet: the numbers here are the test's)
DATA = {
    "generator": "text_rows",
    "labels": ["g%02d" % i for i in range(20)],
    "text_key": "text",
    "vocabulary": 62061,
    "zipf_exponent": 1.07,
    "length_lognormal_mu": 4.5,
    "length_lognormal_sigma": 0.8,
    "length_max": 2000,
    "label_word_share": 0.3,
}


def rows_of(data, seed, stream, n, key_suffix=""):
    return u.make_rows({"data": data}, seed, stream, n, key_suffix)


def words(row):
    return row[1][0][1].split(" ")


@pytest.fixture(scope="module")
def rows():
    return rows_of(DATA, 3000000019, 10, 6000)


def test_same_seed_same_rows_other_seed_or_stream_other_rows(rows):
    big = 3000000019         # more than 32 signed bits hold
    assert rows_of(DATA, big, 10, 6000) == rows
    a = rows_of(DATA, big, 10, 300)
    assert a == rows_of(DATA, big, 10, 300)
    assert a != rows_of(DATA, big + 1, 10, 300)
    assert a != rows_of(DATA, big, 11, 300)
    # another seed is another vocabulary too
    assert not set(words(a[0])) & {w for r in rows_of(DATA, big + 1, 10, 300)
                                   for w in words(r)}


def test_a_row_is_one_text_key_and_every_label_is_drawn(rows):
    for label, strings, nums in rows[:200]:
        assert nums == [] and len(strings) == 1
        assert strings[0][0] == "text" and label in DATA["labels"]
    count = collections.Counter(r[0] for r in rows)
    assert set(count) == set(DATA["labels"])
    # equal weights where the configuration gives none: 300 a label
    assert 200 < min(count.values()) and max(count.values()) < 400


def test_label_weights_are_the_labels_frequencies():
    data = dict(DATA, labels=["a", "b", "c"], label_weights=[6, 3, 1])
    count = collections.Counter(r[0] for r in rows_of(data, 5, 0, 4000))
    assert 0.56 < count["a"] / 4000 < 0.64
    assert 0.27 < count["b"] / 4000 < 0.33
    assert 0.07 < count["c"] / 4000 < 0.13


def test_words_hold_no_space_and_no_two_ranks_share_one(rows):
    text_rows = u.generator({"data": DATA})
    table = text_rows._words(DATA["vocabulary"], 3000000019)
    assert table.shape == (DATA["vocabulary"], 9)
    vocab = {bytes(w[:8]).decode() for w in table}
    assert len(vocab) == DATA["vocabulary"]
    assert all(len(w) == 8 and set(w) <= set("0123456789abcdef")
               for w in vocab)
    seen = {w for r in rows[:500] for w in words(r)}
    assert seen <= vocab           # so: no empty word, no space within one
    assert not any(r[1][0][1].startswith(" ") or r[1][0][1].endswith(" ")
                   for r in rows)


def test_lengths_are_the_log_normal_of_data(rows):
    n = np.array([len(words(r)) for r in rows])
    mu, sigma = DATA["length_lognormal_mu"], DATA["length_lognormal_sigma"]
    # quartiles of floor(exp(N(mu, sigma))): exp(mu -+ 0.6745 sigma)
    q1, q2, q3 = np.percentile(n, [25, 50, 75])
    assert abs(q2 / np.exp(mu) - 1) < 0.08
    assert abs(q1 / np.exp(mu - 0.6745 * sigma) - 1) < 0.10
    assert abs(q3 / np.exp(mu + 0.6745 * sigma) - 1) < 0.10
    # heavy-tailed: a flush of these rows spans several widths
    assert n.min() >= 1 and n.max() > 8 * q2 and n.max() <= DATA["length_max"]
    capped = rows_of(dict(DATA, length_max=50), 7, 0, 500)
    assert max(len(words(r)) for r in capped) == 50


def test_word_ranks_fall_by_the_exponent_of_data(rows):
    freq = np.array(sorted(collections.Counter(
        w for r in rows for w in words(r)).values(), reverse=True), float)
    rank = np.arange(1, len(freq) + 1)
    mid = (rank >= 10) & (rank <= 1000)
    slope = np.polyfit(np.log(rank[mid]), np.log(freq[mid]), 1)[0]
    assert abs(-slope - DATA["zipf_exponent"]) < 0.1
    # and by another where the configuration says another
    steep = rows_of(dict(DATA, zipf_exponent=1.5), 7, 0, 3000)
    f2 = np.array(sorted(collections.Counter(
        w for r in steep for w in words(r)).values(), reverse=True), float)
    r2 = np.arange(1, len(f2) + 1)
    mid = (r2 >= 5) & (r2 <= 300)
    assert abs(-np.polyfit(np.log(r2[mid]), np.log(f2[mid]), 1)[0] - 1.5) < 0.2
    small = rows_of(dict(DATA, vocabulary=100), 7, 0, 500)
    assert len({w for r in small for w in words(r)}) == 100


def test_a_labels_words_come_from_its_own_slice_of_the_vocabulary(rows):
    """The ground truth a linear model can learn: ``label_word_share`` of
    a document's tokens are of ranks ``r`` with ``r % labels == label``,
    and the rest fall there one time in ``labels``."""
    text_rows = u.generator({"data": DATA})
    table = text_rows._words(DATA["vocabulary"], 3000000019)
    rank_of = {bytes(w[:8]).decode(): r for r, w in enumerate(table)}
    n_labels = len(DATA["labels"])
    own = total = 0
    for label, strings, _nums in rows[:1500]:
        k = DATA["labels"].index(label)
        ranks = [rank_of[w] for w in strings[0][1].split(" ")]
        own += sum(r % n_labels == k for r in ranks)
        total += len(ranks)
    share = DATA["label_word_share"]
    assert abs(own / total - (share + (1 - share) / n_labels)) < 0.02
    none = rows_of(dict(DATA, label_word_share=0.0), 7, 0, 300)
    assert rows_of(dict(DATA, label_word_share=1.0), 7, 0, 300) != none


def test_rows_of_two_key_suffixes_share_no_key():
    a = rows_of(DATA, 11, 3, 50, key_suffix=".0")
    b = rows_of(DATA, 11, 3, 50, key_suffix=".1")
    assert {k for r in a for k, _v in r[1]} == {"text.0"}
    assert {k for r in b for k, _v in r[1]} == {"text.1"}
    # the same documents under another key: the suffix changes no draw
    assert [(r[0], r[1][0][1]) for r in a] == [(r[0], r[1][0][1]) for r in b]


def test_the_classifiers_encoders_take_its_rows(rows):
    from harness import cell

    engine = cell.load_module(os.path.join(u.BENCH, "engines"), "classifier")
    some = rows[:40]
    kind, _msgid, method, params = msgpack.unpackb(
        bytes(engine.ENCODERS["train"]("n", some)), raw=False)
    assert (kind, method, params[0]) == (0, "train", "n")
    assert [lb for lb, _d in params[1]] == [r[0] for r in some]
    assert [d[0] for _lb, d in params[1]] == [
        [list(kv) for kv in r[1]] for r in some]
    assert all(d[1] == [] for _lb, d in params[1])
    _k, _m, method, params = msgpack.unpackb(
        bytes(engine.ENCODERS["classify"]("n", some)), raw=False)
    assert method == "classify" and len(params[1]) == 40
    assert engine.well_formed(
        "classify", engine.summarize_classify(
            [[[lb, 0.5] for lb in DATA["labels"][:3]]] * 40), 40, DATA)


@pytest.mark.parametrize("change,names", [
    ({"labels": ["only"]}, "data.labels"),
    ({"label_weights": [1.0, 2.0]}, "data.label_weights"),
    ({"vocabulary": 5}, "data.vocabulary"),
])
def test_a_data_section_it_cannot_draw_from_is_refused_by_name(change, names):
    with pytest.raises(ValueError, match=names):
        rows_of(dict(DATA, **change), 7, 0, 10)


def test_every_number_is_the_configurations():
    """No default stands in for a key of ``data``: a section that leaves
    one out is refused."""
    for key in ("labels", "text_key", "vocabulary", "zipf_exponent",
                "length_lognormal_mu", "length_lognormal_sigma",
                "length_max", "label_word_share"):
        with pytest.raises(KeyError, match=key):
            rows_of({k: v for k, v in DATA.items() if k != key}, 7, 0, 10)
