"""The plain reference of the linear classifier over character n-grams:
``linear_classifier.py``'s numpy float32 AROW, with a converter of its own
that states Jubatus' ``ngram`` string type. A configuration names it
(``"reference": "linear_classifier_ngram"``). Like its siblings it imports
nothing of the program and works from the configuration file's ``model``
section and the rows the generator sent. The model is the sibling's, its
state and its update rule; only the layout of a flush differs: a row's
entries are kept as one flat list, so that hundreds of labels over
thousands of paragraphs never make a [labels, rows, widest row] array.
The arithmetic is the sibling's, product for product, in float32.

The ``ngram`` type and the rule, as the upstream converter states them (a
``string_types`` entry ``{"method": "ngram", "char_num": "<n>"}`` and a
``string_rules`` entry of that type, ``sample_weight`` ``tf`` or ``bin``,
``global_weight`` ``bin``):

- the value of every string key that the rule's ``key`` pattern matches is
  cut into every window of ``n`` consecutive code points, ``value[i:i +
  n]`` for each ``i`` from 0 to ``len(value) - n``: none where the value
  is shorter than ``n``;
- every *distinct* window ``t`` of the value of key ``k`` is the feature
  ``"k$t@<type>#<sample_weight>/bin"``, valued by how often ``t`` occurs in
  the value (``tf``) or 1 (``bin``);
- a feature's column is ``crc32(name) & (D - 1)``, 0 mapped to 1, the name
  encoded as UTF-8 with lone surrogates (bytes the wire carried that are no
  UTF-8) back to the bytes they stand for; values that meet on one column
  within a row add.

A rule of another type or weight, a num rule, a filter or a combination
is refused by name, not guessed at."""

from __future__ import annotations

import importlib.util
import os
import zlib
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np


def _sibling(name: str) -> Any:
    """``<name>.py`` beside this file: the harness loads references by
    path, so a sibling is found the same way."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_references_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_plain = _sibling("linear_classifier")

NEG = _plain.NEG
universe_of = _plain.universe_of
_matches = _plain._matches

#: table elements one gather of a flush's scores may make at once
_CHUNK = 1 << 24


def column(name: str, dim: int) -> int:
    h = zlib.crc32(name.encode("utf-8", "surrogateescape")) & (dim - 1)
    return h or 1


def windows(text: str, n: int) -> List[str]:
    """The ``n``-grams of one value, in order."""
    return [text[i:i + n] for i in range(len(text) - n + 1)]


class Featurizer:
    """``model.converter`` of a configuration whose string rules are all of
    ``ngram`` types with ``tf`` or ``bin`` and ``bin``, at width ``dim``
    (the file's ``hash_max_size``, or a rehearsal's)."""

    def __init__(self, converter: Dict[str, Any], dim: int) -> None:
        self.dim = int(dim)
        for k in ("string_filter_rules", "num_filter_rules", "num_types",
                  "num_rules", "binary_rules", "combination_rules"):
            if converter.get(k):
                raise NotImplementedError(
                    f"converter.{k}: this reference knows ngram string "
                    "rules and nothing beside them")
        types = converter.get("string_types", {})
        self.string_rules: List[Tuple[str, int, bool, str]] = []
        for rule in converter.get("string_rules", []):
            t = types.get(rule["type"], {})
            if t.get("method") != "ngram" \
                    or rule["sample_weight"] not in ("tf", "bin") \
                    or rule["global_weight"] != "bin":
                raise NotImplementedError(
                    f"string rule {rule}: this reference knows ngram types "
                    "with tf or bin and bin")
            n = int(t.get("char_num", "1"))
            if n < 1:
                raise NotImplementedError(f"string type {rule['type']}: "
                                          f"char_num {n}")
            self.string_rules.append((
                rule["key"], n, rule["sample_weight"] == "tf",
                f"@{rule['type']}#{rule['sample_weight']}/bin"))
        if not self.string_rules:
            raise NotImplementedError("converter.string_rules: none given")
        #: (key, window, suffix) -> its column: windows come back often
        self._seen: Dict[Tuple[str, str, str], int] = {}

    def _column(self, key: str, term: str, suffix: str) -> int:
        c = self._seen.get((key, term, suffix))
        if c is None:
            c = self._seen[(key, term, suffix)] = column(
                f"{key}${term}{suffix}", self.dim)
        return c

    def features(self, key: str, value: str) -> List[Tuple[int, float]]:
        """(column, value) for each distinct window of each matching rule
        (a column twice where two names collide)."""
        out: List[Tuple[int, float]] = []
        for pattern, n, tf, suffix in self.string_rules:
            if not _matches(pattern, key):
                continue
            counts: Dict[str, int] = {}
            for t in windows(value, n):
                counts[t] = counts.get(t, 0) + 1
            out += [(self._column(key, t, suffix), float(c) if tf else 1.0)
                    for t, c in counts.items()]
        return out

    def string_columns(self, key: str, value: str) -> List[int]:
        return [c for c, _v in self.features(key, value)]

    def num_columns(self, key: str) -> List[int]:
        return []                  # no num rule is stated here

    def __call__(self, row) -> Dict[int, float]:
        """One row -> {column: value}."""
        _label, strings, _nums = row
        out: Dict[int, float] = {}
        for k, s in strings:
            for c, v in self.features(k, s):
                out[c] = out.get(c, 0.0) + v
        return out


class Batch:
    """Rows featurized into one flat list of entries: ``cols`` and ``val``
    for each entry, a row's entries from ``starts[r]``, ``lengths[r]`` of
    them, one row after another; ``row`` each entry's row."""

    def __init__(self, rows: Sequence[Any], featurize: Featurizer) -> None:
        feats = [featurize(r) for r in rows]
        self.labels = [r[0] for r in rows]
        self.n = len(rows)
        self.lengths = np.array([len(f) for f in feats], np.int64)
        self.starts = np.cumsum(self.lengths) - self.lengths
        self.row = np.repeat(np.arange(self.n), self.lengths)
        self.cols = np.array([c for f in feats for c in f], np.int64)
        self.val = np.array([v for f in feats for v in f.values()],
                            np.float32)


def _by_row(x: np.ndarray, starts: np.ndarray,
            lengths: np.ndarray) -> np.ndarray:
    """[..., entries] summed in float32 over each row's run of entries:
    [..., rows], 0 for a row without one."""
    out = np.zeros(x.shape[:-1] + (len(starts),), np.float32)
    live = lengths > 0
    if live.any():
        out[..., live] = np.add.reduceat(x, starts[live], axis=-1)
    return out


class Model(_plain.Model):
    """The sibling's model and rule over a flush's flat entries."""

    def scores(self, batch: Batch) -> np.ndarray:
        """[B, live labels] in the order of ``self.labels``, a run of rows
        at a time so that no gather over the live labels makes more than
        _CHUNK elements."""
        rnd = self.rnd
        n = len(self.labels)
        eff = rnd(self.w[:n] + self.dw[:n])
        idx = self._compact(batch.cols)
        val = rnd(batch.val)
        out = np.zeros((batch.n, n), np.float32)
        ends = batch.starts + batch.lengths
        per = max(1, _CHUNK // max(1, n))
        r0 = 0
        while r0 < batch.n:
            e0 = int(batch.starts[r0])
            r1 = min(batch.n, max(r0 + 1, int(np.searchsorted(
                ends, e0 + per, side="right"))))
            e1 = int(ends[r1 - 1])
            prod = rnd(eff[:, idx[e0:e1]] * val[None, e0:e1])     # [n, E]
            out[r0:r1] = _by_row(prod, batch.starts[r0:r1] - e0,
                                 batch.lengths[r0:r1]).T
            r0 = r1
        return out

    def train_flush(self, batch: Batch) -> None:
        """All rows decided against the model at the flush's start."""
        rnd = self.rnd
        y = np.array([self._slot(lb) for lb in batch.labels], np.int64)
        n = len(self.labels)
        b = len(y)
        rows = np.arange(b)
        idx = self._compact(batch.cols)
        val = rnd(batch.val)
        at = batch.row
        s = self.scores(batch)                                    # [B, n]
        s_c = s[rows, y]
        masked = s.copy()
        masked[rows, y] = NEG
        no_rival = n < 2
        wrong = masked.argmax(axis=1) if not no_rival else np.zeros(b, np.int64)
        s_w = np.float32(0.0) if no_rival else masked[rows, wrong]
        margin = s_c - s_w
        loss = np.maximum(np.float32(0.0), np.float32(1.0) - margin)
        x2v = rnd(val * val)                                      # [E]
        x2 = _by_row(x2v, batch.starts, batch.lengths)
        y_e, w_e = y[at], wrong[at]
        sig_c = np.float32(1.0) / rnd(self.p[y_e, idx] + self.dp[y_e, idx])
        sig_w = np.ones_like(sig_c) if no_rival else \
            np.float32(1.0) / rnd(self.p[w_e, idx] + self.dp[w_e, idx])
        v = _by_row(rnd((sig_c + sig_w) * x2v), batch.starts, batch.lengths)
        hit = (loss > 0) & (x2 > 0)
        alpha = np.where(hit, loss / (v + self.r), np.float32(0.0)
                         ).astype(np.float32)
        dpv = np.where(hit[at], x2v / self.r, np.float32(0.0))
        up_c = rnd(alpha[at] * sig_c * val)
        np.add.at(self.dw, (y_e, idx), up_c)
        np.add.at(self.dp, (y_e, idx), dpv)
        if not no_rival:
            up_w = rnd(alpha[at] * sig_w * val)
            np.add.at(self.dw, (w_e, idx), -up_w)
            np.add.at(self.dp, (w_e, idx), dpv)
        self.dw = rnd(self.dw)
        self.dp = rnd(self.dp)


def label_scores(model: Model, batch: Batch) -> List[Dict[str, float]]:
    """What ``classify`` answers for each row: {label: score}."""
    s = model.scores(batch)
    return [{lb: float(s[i, j]) for j, lb in enumerate(model.labels)}
            for i in range(s.shape[0])]
