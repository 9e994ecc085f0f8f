"""Rows of a click log: the generator a configuration names with
``"data": {"generator": "click_log", ...}``, driven by that ``data``
section.

A row has ``integer_fields`` numeric keys (``I1``..) and one string key
(``C1``..) for each entry of ``categorical_cardinalities``; the label is
one of the two ``labels`` (``[clicked, not clicked]``), drawn from a
logistic ground truth over the same fields. Everything is a pure function
of ``(seed, stream, n)``: the same seed gives the same rows, byte for
byte."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from harness.datagen import Row, zipf_ranks


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a seeded bijection on uint64."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _unit(x: np.ndarray) -> np.ndarray:
    """uint64 -> float64 in [0, 1)."""
    return (x >> np.uint64(11)).astype(np.float64) / float(1 << 53)


def cardinalities(data: Dict[str, Any]) -> List[int]:
    c = data["categorical_cardinalities"]
    if isinstance(c, dict):  # {"fields": n, "low": a, "high": b}: log-spaced
        return [int(round(v)) for v in np.logspace(
            np.log10(c["low"]), np.log10(c["high"]), int(c["fields"]))]
    return [int(v) for v in c]


def make_rows(data: Dict[str, Any], seed: int, stream: int, n: int,
              key_suffix: str = "") -> List[Row]:
    """``n`` labelled rows of stream ``stream`` of ``seed``. With a
    ``key_suffix`` every key carries it (``I1.3``, ``C7.3``): rows of
    different suffixes share no feature."""
    labels = data["labels"]
    if len(labels) != 2:
        raise ValueError("generator click_log: data.labels is [clicked, not "
                         f"clicked], two labels, not {len(labels)}")
    rng = np.random.default_rng([int(seed), int(stream)])
    cards = cardinalities(data)
    n_int = int(data["integer_fields"])
    seed64 = np.uint64(int(seed) & 0xFFFFFFFF)
    # integer fields: log-normal counts, as a click log's are
    ints = np.floor(np.exp(rng.normal(
        data["integer_lognormal_mu"], data["integer_lognormal_sigma"],
        size=(n, n_int)))).astype(np.float64)
    ints = np.minimum(ints, float(data["integer_max"]))
    ranks = np.stack([zipf_ranks(rng.random(n), c, float(data["zipf_exponent"]))
                      for c in cards], axis=1)                    # [n, n_cat]
    field = np.arange(len(cards), dtype=np.uint64)[None, :]
    ident = _mix64(ranks.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                   + field * np.uint64(0xD1B54A32D192ED03))
    # ground truth: a weight for every (field, value) and integer field,
    # fixed by the seed; the bias is set so that the click rate is the
    # configuration's whatever the seed drew
    w_cat = (_unit(_mix64(ident ^ seed64)) - 0.5) * 2.0 * data["truth_scale"]
    w_int = (_unit(_mix64(np.arange(n_int, dtype=np.uint64) + seed64
                          + np.uint64(977))) - 0.5) * data["truth_scale"]
    logit = w_cat.sum(axis=1) + (np.log1p(ints) * w_int[None, :]).sum(axis=1)
    lo, hi = -40.0, 40.0
    for _ in range(60):   # the bias at which these rows' mean rate is the target
        bias = (lo + hi) / 2.0
        if np.mean(1.0 / (1.0 + np.exp(-(logit + bias)))) < data["click_rate"]:
            lo = bias
        else:
            hi = bias
    clicked = rng.random(n) < 1.0 / (1.0 + np.exp(-(logit + bias)))
    tokens = (ident & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pos, neg = labels
    rows: List[Row] = []
    ikeys = [f"I{j + 1}{key_suffix}" for j in range(n_int)]
    ckeys = [f"C{j + 1}{key_suffix}" for j in range(len(cards))]
    for i in range(n):
        rows.append((pos if clicked[i] else neg,
                     [(k, f"{t:08x}") for k, t in zip(ckeys, tokens[i].tolist())],
                     [(k, v) for k, v in zip(ikeys, ints[i].tolist())]))
    return rows
