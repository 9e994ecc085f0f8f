"""Bytes an operation has to move for the rows it was given, from its
shapes: the numerator of a roofline share. Whole-table sweeps that the
program happens to make are not needed bytes; they show as a low share."""

from __future__ import annotations

F32 = 4
I32 = 4


def train_flush_bytes(rows: int, feats: int, labels: int) -> int:
    """One AROW flush of ``rows`` x ``feats`` entries against ``labels``
    live labels and four f32 tables (w, dw, precision, its diff):

    - read each entry's column and value;
    - gather all four tables' cell for every live label (scores need the
      weights of every label, the step sizes the precisions);
    - read-modify-write ``dw`` and the precision diff on two rows (the
      correct label's and the rival's);
    - read each row's label."""
    entries = rows * feats
    read = entries * (I32 + F32) + entries * 4 * labels * F32
    update = entries * 2 * 2 * (F32 + F32)
    return read + update + rows * I32


def classify_flush_bytes(rows: int, feats: int, labels: int) -> int:
    """Scores of ``rows`` x ``feats`` entries: each entry's column and
    value, the weight and its diff for every live label, and one f32 score
    per row and label written."""
    entries = rows * feats
    return entries * (I32 + F32) + entries * 2 * labels * F32 \
        + rows * labels * F32


def roofline_share_pct(needed_bytes: float, seconds: float,
                       peak_bytes_per_s: float) -> float:
    """The least time the chip could take over the time it took, in %."""
    return 100.0 * (needed_bytes / peak_bytes_per_s) / seconds
