"""The plain reference of the linear classifier: Jubatus' converter rules,
feature hashing, the AROW update as the configuration states it and
classify scores, in straightforward numpy. A configuration names it
(``"reference": "linear_classifier"``) and the harness finds this file by
that name. It imports nothing of the program and takes nothing the
program made; it works from the configuration file's ``model`` section and
the same rows the generator sent.

Semantics, from the configuration file's ``model`` and ``guarantees``:

- converter (:class:`Featurizer`, from ``model.converter``): a numeric key
  ``k`` with value ``v`` under a ``num`` rule is the feature ``"k@num"``
  with value ``v``; a string key ``k`` with value ``s`` under a ``str``
  rule with ``bin``/``bin`` weights is ``"k$s@str#bin/bin"`` with value 1.
  A feature's column is ``crc32(name) & (D - 1)``, 0 mapped to 1; values
  that share a column within a row add. A rule of any other type, a
  filter, or another weight is refused, not guessed at: the configuration
  that needs it adds it here, or brings a reference of its own.
- train: the rows of one flush are all decided against the model as it
  stood when the flush began, and their updates add (the program's
  microbatch semantics); flushes follow one another.
- AROW (``model.method``), multiclass by the best rival label, diagonal
  covariance kept as precision, ``r = model.parameter
  .regularization_weight``: margin ``m = s_y - s_rival``, loss
  ``l = max(0, 1 - m)``, ``v = sum((1/p_y + 1/p_rival) x^2)``,
  ``alpha = l / (v + r)``; ``w_y += alpha x / p_y``,
  ``w_rival -= alpha x / p_rival``, ``p += x^2 / r`` on both rows.

``precision`` is ``"float32"`` (the configuration's) or ``"bfloat16"``
(the control: tables and products rounded to 8 bits of mantissa)."""

from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, List, Sequence

import numpy as np

NEG = np.float32(-1e30)


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    r = b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))
    return (r & np.uint32(0xFFFF0000)).view(np.float32)


ROUNDERS: Dict[str, Callable[[np.ndarray], np.ndarray]] = {
    "float32": lambda x: x,
    "bfloat16": to_bfloat16,
}


def column(name: str, dim: int) -> int:
    h = zlib.crc32(name.encode("utf-8")) & (dim - 1)
    return h or 1


def _matches(pattern: str, key: str) -> bool:
    """Jubatus' key patterns: ``*``, ``prefix*``, ``*suffix``, or the key."""
    if pattern.endswith("*"):
        return key.startswith(pattern[:-1])
    if pattern.startswith("*"):
        return key.endswith(pattern[1:])
    return pattern == key


class Featurizer:
    """``model.converter`` of a configuration file, at width ``dim`` (the
    file's ``hash_max_size``, or a rehearsal's)."""

    def __init__(self, converter: Dict[str, Any], dim: int) -> None:
        self.dim = int(dim)
        for k in ("string_filter_rules", "num_filter_rules", "string_types",
                  "num_types"):
            if converter.get(k):
                raise NotImplementedError(f"converter.{k}: this reference "
                                          "knows no filters or plugin types")
        self.string_rules = []
        for rule in converter.get("string_rules", []):
            if (rule["type"], rule["sample_weight"], rule["global_weight"]) \
                    != ("str", "bin", "bin"):
                raise NotImplementedError(f"string rule {rule}: this "
                                          "reference knows str with bin/bin")
            self.string_rules.append((rule["key"], "@str#bin/bin"))
        self.num_rules = []
        for rule in converter.get("num_rules", []):
            if rule["type"] != "num":
                raise NotImplementedError(f"num rule {rule}: this reference "
                                          "knows num")
            self.num_rules.append((rule["key"], "@num"))
        #: (key, value) or key -> columns: a check featurizes the same
        #: rows more than once
        self._seen: Dict[Any, List[int]] = {}

    def string_columns(self, key: str, value: str) -> List[int]:
        cols = self._seen.get((key, value))
        if cols is None:
            cols = self._seen[(key, value)] = [
                column(f"{key}${value}{suffix}", self.dim)
                for pattern, suffix in self.string_rules
                if _matches(pattern, key)]
        return cols

    def num_columns(self, key: str) -> List[int]:
        cols = self._seen.get(key)
        if cols is None:
            cols = self._seen[key] = [
                column(f"{key}{suffix}", self.dim)
                for pattern, suffix in self.num_rules
                if _matches(pattern, key)]
        return cols

    def __call__(self, row) -> Dict[int, float]:
        """One row -> {column: value}."""
        _label, strings, nums = row
        out: Dict[int, float] = {}
        for k, v in nums:
            for c in self.num_columns(k):
                out[c] = out.get(c, 0.0) + float(v)
        for k, s in strings:
            for c in self.string_columns(k, s):
                out[c] = out.get(c, 0.0) + 1.0
        return out


class Batch:
    """Rows featurized into padded [B, K] arrays over the raw columns."""

    def __init__(self, rows: Sequence[Any], featurize: Featurizer) -> None:
        feats = [featurize(r) for r in rows]
        k = max((len(f) for f in feats), default=1)
        self.labels = [r[0] for r in rows]
        self.cols = np.zeros((len(rows), k), np.int64)   # 0 = padding
        self.val = np.zeros((len(rows), k), np.float32)
        for i, f in enumerate(feats):
            self.cols[i, :len(f)] = list(f.keys())
            self.val[i, :len(f)] = list(f.values())


class Model:
    """The configuration's model over a compact universe of columns, with
    a row for each of the ``labels`` its data can carry."""

    def __init__(self, universe: np.ndarray, model: Dict[str, Any],
                 labels: Sequence[str], precision: str) -> None:
        if model["method"] != "AROW":
            raise NotImplementedError(f"method {model['method']!r}: this "
                                      "reference knows AROW")
        max_labels = len(labels)
        self.universe = universe        # sorted raw columns, 0 first
        self.r = np.float32(model["parameter"]["regularization_weight"])
        self.rnd = ROUNDERS[precision]
        self.labels: List[str] = []
        n = len(universe)
        self.w = np.zeros((max_labels, n), np.float32)
        self.dw = np.zeros((max_labels, n), np.float32)
        self.p = np.ones((max_labels, n), np.float32)
        self.dp = np.zeros((max_labels, n), np.float32)

    def _slot(self, label: str) -> int:
        if label not in self.labels:
            self.labels.append(label)
        return self.labels.index(label)

    def _compact(self, cols: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.universe, cols)

    def scores(self, batch: Batch) -> np.ndarray:
        """[B, live labels] in the order of ``self.labels``."""
        rnd = self.rnd
        idx = self._compact(batch.cols)
        n = len(self.labels)
        eff = rnd(self.w[:n] + self.dw[:n])
        g = eff[:, idx]                                           # [L, B, K]
        return rnd(g * rnd(batch.val)[None]).sum(axis=2, dtype=np.float32).T

    def train_flush(self, batch: Batch) -> None:
        """All rows decided against the model at the flush's start."""
        rnd = self.rnd
        y = np.array([self._slot(lb) for lb in batch.labels], np.int64)
        n = len(self.labels)
        b = len(y)
        rows = np.arange(b)
        idx = self._compact(batch.cols)
        val = rnd(batch.val)
        s = self.scores(batch)                                    # [B, n]
        s_c = s[rows, y]
        masked = s.copy()
        masked[rows, y] = NEG
        no_rival = n < 2
        wrong = masked.argmax(axis=1) if not no_rival else np.zeros(b, np.int64)
        s_w = np.float32(0.0) if no_rival else masked[rows, wrong]
        margin = s_c - s_w
        loss = np.maximum(np.float32(0.0), np.float32(1.0) - margin)
        x2v = rnd(val * val)
        x2 = x2v.sum(axis=1, dtype=np.float32)
        prec = rnd(self.p[:n] + self.dp[:n])[:, idx]              # [n, B, K]
        sig_c = np.float32(1.0) / prec[y, rows]                   # [B, K]
        sig_w = np.ones_like(sig_c) if no_rival \
            else np.float32(1.0) / prec[wrong, rows]
        v = rnd((sig_c + sig_w) * x2v).sum(axis=1, dtype=np.float32)
        hit = (loss > 0) & (x2 > 0)
        alpha = np.where(hit, loss / (v + self.r), np.float32(0.0)
                         ).astype(np.float32)
        dpv = np.where(hit[:, None], x2v / self.r, np.float32(0.0))
        up_c = rnd(alpha[:, None] * sig_c * val)
        np.add.at(self.dw, (y[:, None], idx), up_c)
        np.add.at(self.dp, (y[:, None], idx), dpv)
        if not no_rival:
            up_w = rnd(alpha[:, None] * sig_w * val)
            np.add.at(self.dw, (wrong[:, None], idx), -up_w)
            np.add.at(self.dp, (wrong[:, None], idx), dpv)
        self.dw = rnd(self.dw)
        self.dp = rnd(self.dp)

    def clear(self) -> None:
        self.labels = []
        self.w[:] = 0
        self.dw[:] = 0
        self.p[:] = 1
        self.dp[:] = 0


def universe_of(batches: Sequence[Batch]) -> np.ndarray:
    cols = [np.zeros(1, np.int64)] + [b.cols.ravel() for b in batches]
    return np.unique(np.concatenate(cols))


def label_scores(model: Model, batch: Batch) -> List[Dict[str, float]]:
    """What ``classify`` answers for each row: {label: score}."""
    s = model.scores(batch)
    return [{lb: float(s[i, j]) for j, lb in enumerate(model.labels)}
            for i in range(s.shape[0])]
