"""Rows of labelled text: the generator a configuration names with
``"data": {"generator": "text_rows", ...}``, driven by that ``data``
section.

Written for the 20 Newsgroups collection as the upstream tutorial trains
it: one string key holds the document, and the converter splits it on
spaces. As far as is known here without a network: 20 labels, about
18,800 documents, 62,061 features in LIBSVM's ``news20`` multiclass file
and about 80 distinct terms a document. Every number is the
configuration's, none is this file's:

- ``labels``: two or more; ``label_weights`` (optional, as many) are
  their relative frequencies, equal where left out;
- ``text_key``: the one string key of a row;
- ``vocabulary``: how many words there are; ``zipf_exponent``: a word's
  frequency falls with its rank as ``(rank + 1) ** -zipf_exponent`` (not
  1: ``zipf_ranks`` inverts the power law);
- ``length_lognormal_mu``, ``length_lognormal_sigma``, ``length_max``: the
  tokens of a document are ``floor(exp(N(mu, sigma)))``, at least 1 and at
  most ``length_max``: heavy-tailed, so the rows of one flush differ in
  width;
- ``label_word_share``: the share of a document's tokens drawn from its
  label's own slice of the vocabulary, the ranks ``r`` with ``r % len(
  labels)`` the label's index, by the same power law over the slice; the
  rest are drawn from the whole vocabulary. That slice is the ground truth
  a linear model can learn, and since every slice runs from the commonest
  words to the rarest, the words of all documents together keep the
  exponent.

A row is ``(label, [(text_key + key_suffix, "w1 w2 ...")], [])``. A word
is eight hexadecimal digits, a bijection of its rank fixed by the seed, so
it holds no space and two ranks never share a word. Everything is a pure
function of ``(seed, stream, n)``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from harness.datagen import Row, zipf_ranks

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_WORD = 9       # eight digits and the space after them


def _words(vocabulary: int, seed: int) -> np.ndarray:
    """``[vocabulary, 9]`` bytes: the word of every rank and a space.
    murmur3's 32-bit finaliser over the rank offset by the seed: a
    bijection on uint32, so no two ranks share a word."""
    x = np.arange(vocabulary, dtype=np.uint32)
    with np.errstate(over="ignore"):
        x = x + np.uint32((int(seed) * 0x9E3779B1) & 0xFFFFFFFF)
        x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
        x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    shifts = np.arange(28, -4, -4, dtype=np.uint32)
    out = np.full((vocabulary, _WORD), ord(" "), np.uint8)
    out[:, :8] = _HEX[(x[:, None] >> shifts[None, :]) & np.uint32(15)]
    return out


def make_rows(data: Dict[str, Any], seed: int, stream: int, n: int,
              key_suffix: str = "") -> List[Row]:
    """``n`` labelled documents of stream ``stream`` of ``seed``. With a
    ``key_suffix`` the text key carries it, and the converter's features
    are named by the key: rows of different suffixes share no feature."""
    labels = list(data["labels"])
    n_labels = len(labels)
    vocabulary = int(data["vocabulary"])
    if n_labels < 2:
        raise ValueError("generator text_rows: data.labels needs two labels "
                         f"or more, not {n_labels}")
    if vocabulary < n_labels:
        raise ValueError("generator text_rows: data.vocabulary "
                         f"({vocabulary}) has no word for every label")
    weights = np.asarray(data.get("label_weights", [1.0] * n_labels),
                         np.float64)
    if weights.shape != (n_labels,) or weights.min() < 0 \
            or not weights.sum() > 0:
        raise ValueError("generator text_rows: data.label_weights needs one "
                         "weight for each of data.labels, none negative")
    exponent = float(data["zipf_exponent"])
    rng = np.random.default_rng([int(seed), int(stream)])
    label = rng.choice(n_labels, size=n, p=weights / weights.sum())
    length = np.clip(np.floor(np.exp(rng.normal(
        data["length_lognormal_mu"], data["length_lognormal_sigma"],
        size=n))), 1, int(data["length_max"])).astype(np.int64)
    total = int(length.sum())
    own = rng.random(total) < float(data["label_word_share"])
    u = rng.random(total)
    of_label = np.repeat(label, length)                   # a token's label
    rank = zipf_ranks(u, vocabulary, exponent)
    for k in range(n_labels):
        mine = own & (of_label == k)
        # the label's slice: ranks k, k + L, k + 2L, ... below `vocabulary`
        size = (vocabulary - k + n_labels - 1) // n_labels
        rank[mine] = zipf_ranks(u[mine], size, exponent) * n_labels + k
    text = _words(vocabulary, seed)[rank].tobytes().decode("ascii")
    key = str(data["text_key"]) + key_suffix
    end = (np.cumsum(length) * _WORD).tolist()
    start = [0] + end[:-1]
    # (a document's last word leaves its space behind)
    return [(labels[k], [(key, text[a:b - 1])], [])
            for k, a, b in zip(label.tolist(), start, end)]
