"""Uniform-schema dense train/score path ≡ the sparse path.

A fixed key schema (every datum hashes to the same index vector) lets
the driver run the classifier step as dense matmuls over the
[L, K] submatrix instead of B*K-element gathers/scatters
(ops.classifier.train_batch_schema). Same semantics as
train_batch_parallel — batch-start snapshot, updates land together —
different execution plan, so agreement is to tolerance, not bitwise.
Reference semantics: classifier_serv.cpp:127-146's per-datum update,
microbatched per SURVEY.md §7 hard part (b).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from jubatus_tpu.ops import classifier as C

D = 1 << 14
L = 3
K = 16
B = 64


def _mk(seed=0, k=K, b=B, dup_pad=False):
    rng = np.random.default_rng(seed)
    uidx = rng.choice(np.arange(1, D), size=k, replace=False).astype(np.int32)
    if dup_pad:  # width padding: trailing zero index columns, zero vals
        uidx = np.concatenate([uidx[:-2], np.zeros(2, np.int32)])
    val = rng.normal(size=(b, k)).astype(np.float32)
    if dup_pad:
        val[:, -2:] = 0.0
    labels = rng.integers(0, L, size=b).astype(np.int32)
    return uidx, val, labels


@pytest.mark.parametrize("method", ["AROW", "CW", "NHERD", "PA", "PA1",
                                    "perceptron"])
def test_schema_train_matches_parallel(method):
    uidx, val, labels = _mk()
    mask = jnp.ones(L, dtype=bool)
    conf = method in C.CONFIDENCE_METHODS
    st_a = C.init_state(L, D, confidence=conf)
    st_b = C.init_state(L, D, confidence=conf)
    tiled = jnp.asarray(np.broadcast_to(uidx, (B, K)).copy())
    for step in range(3):
        v = jnp.asarray(val * (1.0 + 0.1 * step))
        st_a = C.train_batch_parallel(st_a, tiled, v, jnp.asarray(labels),
                                      mask, 1.0, method=method)
        st_b = C.train_batch_schema(st_b, jnp.asarray(uidx), v,
                                    jnp.asarray(labels), mask, 1.0,
                                    method=method)
    np.testing.assert_allclose(np.asarray(st_a.dw), np.asarray(st_b.dw),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_a.dprec), np.asarray(st_b.dprec),
                               rtol=2e-4, atol=1e-5)


def test_schema_trained_model_scores_as_the_sparse_trained_one():
    uidx, val, labels = _mk(seed=1)
    mask = jnp.ones(L, dtype=bool)
    tiled = jnp.asarray(np.broadcast_to(uidx, (B, K)).copy())
    st_a = C.train_batch_parallel(
        C.init_state(L, D, confidence=True), tiled, jnp.asarray(val),
        jnp.asarray(labels), mask, 1.0, method="AROW")
    st_b = C.train_batch_schema(
        C.init_state(L, D, confidence=True), jnp.asarray(uidx),
        jnp.asarray(val), jnp.asarray(labels), mask, 1.0, method="AROW")
    s_a = np.asarray(C.scores(st_a, tiled, jnp.asarray(val), mask))
    s_b = np.asarray(C.scores(st_b, tiled, jnp.asarray(val), mask))
    assert np.abs(s_a).max() > 0.0
    np.testing.assert_allclose(s_a, s_b, rtol=2e-4, atol=1e-5)


def test_schema_duplicate_pad_columns_are_noops():
    """Width-pad columns (index 0, val 0) must not corrupt slot 0."""
    uidx, val, labels = _mk(seed=2, dup_pad=True)
    mask = jnp.ones(L, dtype=bool)
    st_a = C.init_state(L, D, confidence=True)
    st_b = C.init_state(L, D, confidence=True)
    tiled = jnp.asarray(np.broadcast_to(uidx, (B, K)).copy())
    st_a = C.train_batch_parallel(st_a, tiled, jnp.asarray(val),
                                  jnp.asarray(labels), mask, 1.0,
                                  method="AROW")
    st_b = C.train_batch_schema(st_b, jnp.asarray(uidx), jnp.asarray(val),
                                jnp.asarray(labels), mask, 1.0, method="AROW")
    np.testing.assert_allclose(np.asarray(st_a.dw), np.asarray(st_b.dw),
                               rtol=2e-4, atol=1e-5)
    assert float(jnp.sum(jnp.abs(st_b.dw[:, 0]))) == 0.0


def test_schema_zero_rows_are_noops():
    """Row padding (val all-zero) must produce no update (alpha gating)."""
    uidx, val, labels = _mk(seed=3)
    val[B // 2:] = 0.0
    mask = jnp.ones(L, dtype=bool)
    st_full = C.init_state(L, D, confidence=True)
    st_half = C.init_state(L, D, confidence=True)
    st_full = C.train_batch_schema(st_full, jnp.asarray(uidx),
                                   jnp.asarray(val), jnp.asarray(labels),
                                   mask, 1.0, method="AROW")
    st_half = C.train_batch_schema(
        st_half, jnp.asarray(uidx), jnp.asarray(val[: B // 2]),
        jnp.asarray(labels[: B // 2]), mask, 1.0, method="AROW")
    np.testing.assert_allclose(np.asarray(st_full.dw), np.asarray(st_half.dw),
                               rtol=1e-5, atol=1e-6)


def test_single_label_no_rival_matches_parallel():
    uidx, val, _ = _mk(seed=4)
    mask = jnp.array([True, False, False])
    labels = np.zeros(B, np.int32)
    st_a = C.init_state(L, D, confidence=True)
    st_b = C.init_state(L, D, confidence=True)
    tiled = jnp.asarray(np.broadcast_to(uidx, (B, K)).copy())
    st_a = C.train_batch_parallel(st_a, tiled, jnp.asarray(val),
                                  jnp.asarray(labels), mask, 1.0,
                                  method="AROW")
    st_b = C.train_batch_schema(st_b, jnp.asarray(uidx), jnp.asarray(val),
                                jnp.asarray(labels), mask, 1.0, method="AROW")
    np.testing.assert_allclose(np.asarray(st_a.dw), np.asarray(st_b.dw),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_a.dprec),
                               np.asarray(st_b.dprec), rtol=2e-4, atol=1e-5)
