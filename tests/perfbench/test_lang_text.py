"""``generators/lang_text.py``: paragraphs of many written languages, every
number from the configuration's ``data`` section (``wili2018_arow``'s).
A function of the seed, the labels in aligned blocks that each hold every
label once, each language's code points from its own script, its own code
points at the share ``data`` gives and in no other language, lengths as
``data`` says, spaces only where a script writes them, and rows of two key
suffixes that share no key."""

import collections

import numpy as np
import pytest

import pbtest_util as u

CONF = u.load_config("wili2018_arow")
DATA = CONF["data"]
N_LABELS = len(DATA["labels"])


def rows_of(data, seed, stream, n, key_suffix=""):
    return u.make_rows({"data": data}, seed, stream, n, key_suffix)


def text(row):
    return row[1][0][1]


@pytest.fixture(scope="module")
def rows():
    return rows_of(DATA, 3000000019, 10, 4 * 4700)


def _script_of_language():
    """Each label's script entry, by the configuration's counts."""
    out = []
    for s in DATA["scripts"]:
        out += [s] * s["languages"]
    return out


def _points(ranges):
    return {c for a, b in ranges for c in range(a, b + 1)}


def test_same_seed_same_rows_other_seed_or_stream_other_rows(rows):
    big = 3000000019         # more than 32 signed bits hold
    assert rows_of(DATA, big, 10, 4 * 4700) == rows
    # the first rows of a stream are the same whatever n
    assert rows_of(DATA, big, 10, 300) == rows[:300]
    assert rows_of(DATA, big, 10, 5000) == rows[:5000]
    a = rows_of(DATA, big, 10, 300)
    assert a != rows_of(DATA, big + 1, 10, 300)
    assert a != rows_of(DATA, big, 11, 300)


@pytest.mark.parametrize("n", [235, 470, 500, 4700, 4701, 9999])
def test_every_aligned_block_holds_every_label_once(rows, n):
    """Labels in aligned blocks of 235 rows, each a permutation of all
    235: any run of 2 x 235 - 1 rows holds every label, so a lone call of
    470 rows or more makes every label live."""
    got = [r[0] for r in rows_of(DATA, 7, 3, n)]
    assert len(got) == n
    for b in range(n // N_LABELS):
        block = got[b * N_LABELS:(b + 1) * N_LABELS]
        assert sorted(block) == sorted(DATA["labels"])
    labels = [r[0] for r in rows]
    for start in (0, 1, 117, 234, 4000):
        assert set(labels[start:start + 2 * N_LABELS - 1]) \
            == set(DATA["labels"])
    count = collections.Counter(labels)
    assert set(count.values()) == {len(rows) // N_LABELS}


def test_a_languages_code_points_are_its_scripts_and_its_own(rows):
    """Every code point of a paragraph is a space (where its script writes
    them) or one of its script's; its own code points, those of ``own``
    at its place modulo the script's languages, make ``own_share`` of
    them, and no other language draws one of them."""
    scripts = _script_of_language()
    place = {}
    for s in DATA["scripts"]:
        for j in range(s["languages"]):
            place[len(place)] = j
    owned_by = {}
    for k, s in enumerate(scripts):
        own = sorted(_points(s["own"]))
        for c in own[place[k]::s["languages"]]:
            owned_by[c] = k
    own_n = letters = 0
    for label, strings, nums in rows[:3000]:
        assert nums == [] and len(strings) == 1
        assert strings[0][0] == DATA["text_key"]
        k = DATA["labels"].index(label)
        s = scripts[k]
        allowed = _points(s["common"]) | _points(s["own"])
        cps = [ord(ch) for ch in strings[0][1]]
        assert all(c in allowed or (c == 0x20 and s["spaces"]) for c in cps)
        mine = [c for c in cps if c in owned_by]
        assert all(owned_by[c] == k for c in mine)
        own_n += len(mine)
        letters += sum(c != 0x20 for c in cps)
    # a space takes the place of a code point whichever it was
    assert abs(own_n / letters - DATA["own_share"]) < 0.01


def test_lengths_are_the_clipped_log_normal_of_data(rows):
    n = np.array([len(text(r)) for r in rows])
    assert n.min() >= DATA["length_min"] and n.max() <= DATA["length_max"]
    mu = DATA["length_lognormal_mu"]
    assert abs(np.median(n) / np.exp(mu) - 1) < 0.05
    # heavy-tailed: a flush of these rows spans several widths
    assert n.max() > 8 * np.median(n)
    capped = {len(text(r)) for r in rows_of(dict(DATA, length_max=150),
                                            7, 0, 500)}
    assert min(capped) == 140 and max(capped) == 150


def test_spaces_only_where_a_script_writes_them_never_two_in_a_row(rows):
    scripts = _script_of_language()
    for label, strings, _n in rows[:3000]:
        t = strings[0][1]
        s = scripts[DATA["labels"].index(label)]
        assert "  " not in t and t[0] != " " and t[-1] != " "
        if not s["spaces"]:
            assert " " not in t
    spaced = [text(r) for r in rows[:3000]
              if scripts[DATA["labels"].index(r[0])]["spaces"]]
    share = sum(t.count(" ") for t in spaced) / sum(map(len, spaced))
    # drawn at p, and not where the code point before was drawn too
    p = 1 / (DATA["word_length_mean"] + 1)
    assert abs(share - p * (1 - p)) < 0.01


def test_code_points_fall_by_the_exponent_of_data():
    """Within one Latin language, the common letters' counts fall with
    their rank as the power law says."""
    one = [r for r in rows_of(DATA, 11, 0, 4700 * 4) if r[0] == "L000"]
    common = _points(DATA["scripts"][0]["common"])
    freq = np.array(sorted(collections.Counter(
        c for r in one for c in text(r) if ord(c) in common).values(),
        reverse=True), float)
    rank = np.arange(1, len(freq) + 1)
    mid = (rank >= 2) & (rank <= 15)
    slope = np.polyfit(np.log(rank[mid]), np.log(freq[mid]), 1)[0]
    assert abs(-slope - DATA["zipf_exponent"]) < 0.25


def test_multi_byte_code_points_come_in_the_text(rows):
    widths = collections.Counter(len(ch.encode()) for r in rows[:2000]
                                 for ch in text(r))
    assert widths[1] and widths[2] and widths[3]


def test_rows_of_two_key_suffixes_share_no_key():
    a = rows_of(DATA, 11, 3, 50, key_suffix=".0")
    b = rows_of(DATA, 11, 3, 50, key_suffix=".1")
    assert {k for r in a for k, _v in r[1]} == {"text.0"}
    assert {k for r in b for k, _v in r[1]} == {"text.1"}
    assert [(r[0], text(r)) for r in a] == [(r[0], text(r)) for r in b]


@pytest.mark.parametrize("change,names", [
    ({"labels": ["only"]}, "data.labels"),
    ({"labels": DATA["labels"][:-1]}, "data.scripts"),
])
def test_a_data_section_it_cannot_draw_from_is_refused_by_name(change, names):
    with pytest.raises(ValueError, match=names):
        rows_of(dict(DATA, **change), 7, 0, 10)


def test_every_number_is_the_configurations():
    """No default stands in for a key of ``data``: a section that leaves
    one out is refused."""
    for key in ("labels", "text_key", "scripts", "own_share",
                "zipf_exponent", "word_length_mean", "length_lognormal_mu",
                "length_lognormal_sigma", "length_min", "length_max"):
        with pytest.raises(KeyError, match=key):
            rows_of({k: v for k, v in DATA.items() if k != key}, 7, 0, 10)
