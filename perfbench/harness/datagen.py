"""What the row generators share. A configuration names its generator
(``"data": {"generator": "<name>", ...}``) and the harness finds
``generators/<name>.py`` by that name; nothing here makes a row.

A generator exposes ``make_rows(data, seed, stream, n, key_suffix="")``,
a pure function of ``(seed, stream, n)`` over the configuration's ``data``
section: the same seed gives the same rows, byte for byte, and rows of
different ``key_suffix`` share no feature (``check.py _disjoint_calls``
relies on it)."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

#: (label, [(key, string)], [(key, number)])
Row = Tuple[str, List[Tuple[str, str]], List[Tuple[str, float]]]


def zipf_ranks(u: np.ndarray, n: int, s: float) -> np.ndarray:
    """Ranks 0..n-1 with P(rank) ~ (rank+1)^-s, by inverting the
    continuous power law on [1, n+1); ``s`` may not be 1."""
    a = 1.0 - s
    x = ((float(n + 1) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, n - 1).clip(0)
