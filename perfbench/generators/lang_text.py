"""Paragraphs of many written languages: the generator a configuration names
with ``"data": {"generator": "lang_text", ...}``, driven by that ``data``
section.

Written for WiLI-2018 (Thoma, arXiv:1801.07779) as a language identifier
trains on it: one string key holds a paragraph of Wikipedia text, and the
converter slides a window of code points over it. Every number is the
configuration's, none is this file's:

- ``labels``: one name for each language, two or more;
- ``text_key``: the one string key of a row;
- ``scripts``: the writing systems, in order, each ``{"name", "languages",
  "spaces", "common", "own"}``: ``languages`` of the labels, taken in
  order (the counts add up to the labels), write in it; ``common`` and
  ``own`` are lists of ``[first, last]`` code point ranges. Every
  language of a script draws from all of its ``common`` code points, in an
  order of frequency of its own (a permutation fixed by the seed); its
  *own* code points are those of ``own`` whose index ``i`` (ranges
  concatenated) has ``i % languages`` equal to the language's place in
  the script, in an order fixed by the seed. No two languages share one,
  so a paragraph's own code points are the ground truth a linear model can
  learn;
- ``own_share``: the share of a paragraph's code points drawn from its
  language's own ones, the rest from the common ones;
- ``zipf_exponent``: a code point's frequency falls with its rank in the
  language's order as ``(rank + 1) ** -zipf_exponent`` (1 allowed);
- ``spaces`` (a script's) and ``word_length_mean``: where a script writes
  spaces between words, each code point of a paragraph but its first and
  last is drawn a space with probability ``p = 1 / (word_length_mean +
  1)``, and stays one unless the code point before it was drawn too: about
  ``p (1 - p)`` of them, never two in a row; elsewhere the text runs on
  without one;
- ``length_lognormal_mu``, ``length_lognormal_sigma``, ``length_min``,
  ``length_max``: a paragraph is ``floor(exp(N(mu, sigma)))`` code points,
  kept between ``length_min`` and ``length_max``.

Labels come in aligned blocks of as many rows as there are labels, each
block a permutation of them all: any run of ``2 * labels - 1``
consecutive rows holds every label, and the labels stay balanced.

A row is ``(label, [(text_key + key_suffix, paragraph)], [])``. The rows
are made in whole chunks of ``20 * labels``, each from its own draw of
``(seed, stream, chunk)``, and cut to ``n``, so the first rows of a stream
are the same whatever ``n``. Everything is a pure function of ``(seed,
stream, n)``."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from harness.datagen import Row

_SPACE = 0x20
_BLOCKS = 20          # label blocks a chunk


def _points(ranges: Sequence[Sequence[int]]) -> np.ndarray:
    return np.concatenate([np.arange(int(a), int(b) + 1, dtype=np.uint32)
                           for a, b in ranges])


def _zipf(u: np.ndarray, n, s: float) -> np.ndarray:
    """Ranks 0..n-1 (``n`` a number or one per draw) with P(rank) ~
    (rank+1)^-s, by inverting the continuous power law on [1, n+1)."""
    n = np.asarray(n, np.float64)
    if s == 1.0:
        x = (n + 1.0) ** u
    else:
        a = 1.0 - s
        x = (((n + 1.0) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, n.astype(np.int64) - 1).clip(0)


class _Languages:
    """Each language's script, common code points in its own order and own
    code points, as arrays the draws index."""

    def __init__(self, data: Dict[str, Any], seed: int) -> None:
        n_labels = len(data["labels"])
        scripts = data["scripts"]
        if sum(int(s["languages"]) for s in scripts) != n_labels:
            raise ValueError("generator lang_text: data.scripts' languages "
                             f"add up to {sum(int(s['languages']) for s in scripts)}"
                             f", not to the {n_labels} of data.labels")
        rng = np.random.default_rng([int(seed), 0x1A46])
        self.script = np.zeros(n_labels, np.int64)    # a language's script
        self.place = np.zeros(n_labels, np.int64)     # its place in it
        self.spaces = np.array([bool(s["spaces"]) for s in scripts])
        self.common: List[np.ndarray] = []            # [languages, A]
        self.own: List[np.ndarray] = []               # [languages, O]
        self.own_n: List[np.ndarray] = []             # [languages]
        k = 0
        for si, s in enumerate(scripts):
            m = int(s["languages"])
            common, own = _points(s["common"]), _points(s["own"])
            if m < 1 or len(own) < m:
                raise ValueError(f"generator lang_text: script {s['name']!r}"
                                 f" has {len(own)} own code points for {m} "
                                 "languages")
            self.script[k:k + m] = si
            self.place[k:k + m] = np.arange(m)
            order = np.argsort(rng.random((m, len(common))), axis=1)
            self.common.append(common[order])
            width = -(-len(own) // m)
            table = np.zeros((m, width), np.uint32)
            counts = np.zeros(m, np.int64)
            for j in range(m):
                mine = own[j::m]
                table[j, :len(mine)] = mine[rng.permutation(len(mine))]
                counts[j] = len(mine)
            self.own.append(table)
            self.own_n.append(counts)
            k += m


def _chunk(data: Dict[str, Any], langs: _Languages,
           rng: np.random.Generator) -> List[tuple]:
    """One chunk's rows, _BLOCKS blocks of every label: (label index,
    paragraph)."""
    n_labels = len(data["labels"])
    n = _BLOCKS * n_labels
    label = np.argsort(rng.random((_BLOCKS, n_labels)), axis=1).reshape(-1)
    length = np.clip(np.floor(np.exp(rng.normal(
        data["length_lognormal_mu"], data["length_lognormal_sigma"],
        size=n))), int(data["length_min"]),
        int(data["length_max"])).astype(np.int64)
    total = int(length.sum())
    of = np.repeat(label, length)                     # a code point's language
    script = langs.script[of]
    place = langs.place[of]
    own = rng.random(total) < float(data["own_share"])
    u = rng.random(total)
    s = float(data["zipf_exponent"])
    cps = np.zeros(total, np.uint32)
    for si in range(len(langs.common)):
        here = script == si
        if not here.any():
            continue
        common = langs.common[si]
        mine = here & ~own
        cps[mine] = common[place[mine], _zipf(u[mine], common.shape[1], s)]
        mine = here & own
        j = place[mine]
        cps[mine] = langs.own[si][j, _zipf(u[mine], langs.own_n[si][j], s)]
    start = np.cumsum(length) - length
    edge = np.zeros(total, bool)
    edge[start] = True
    edge[start + length - 1] = True
    p = 1.0 / (float(data["word_length_mean"]) + 1.0)
    space = (rng.random(total) < p) & langs.spaces[script] & ~edge
    space[1:] &= ~space[:-1]
    cps[space] = _SPACE
    text = cps.astype("<u4").tobytes().decode("utf-32-le")
    return [(int(k), text[a:a + m]) for k, a, m in
            zip(label.tolist(), start.tolist(), length.tolist())]


def make_rows(data: Dict[str, Any], seed: int, stream: int, n: int,
              key_suffix: str = "") -> List[Row]:
    """``n`` labelled paragraphs of stream ``stream`` of ``seed``. With a
    ``key_suffix`` the text key carries it, and the converter's features
    are named by the key: rows of different suffixes share no feature."""
    labels = list(data["labels"])
    if len(labels) < 2:
        raise ValueError("generator lang_text: data.labels needs two labels "
                         f"or more, not {len(labels)}")
    langs = _Languages(data, seed)
    key = str(data["text_key"]) + key_suffix
    per = _BLOCKS * len(labels)
    out: List[Row] = []
    for c in range(-(-n // per)):
        rng = np.random.default_rng([int(seed), int(stream), c])
        for k, text in _chunk(data, langs, rng)[:n - c * per]:
            out.append((labels[k], [(key, text)], []))
    return out
