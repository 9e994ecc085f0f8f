"""What the harness has to know of the ``classifier`` engine's RPC surface:
which call updates and which reads, how each is encoded from rows, what of
an answer is kept for every call of a window, when an answer is well
formed, and which of the server's coalescers counts the rows of each. A
configuration names its engine (``"engine": "classifier"``, also the
server's first argument) and the harness finds this file by that name."""

from __future__ import annotations

import math
from typing import Any, Dict, List

from harness import wire

#: the call that changes the model, and the one that reads it
UPDATE = "train"
READ = "classify"

#: the server's coalescer (``microbatch.<queue>.*`` in ``get_status``)
#: that counts the rows of a method's calls
QUEUE = {"train": "train_raw"}


def train_request(name: str, rows: List[Any]) -> bytearray:
    return wire.encode_request("train", [name, [
        [label, wire.datum(s, nv)] for label, s, nv in rows]])


def classify_request(name: str, rows: List[Any]) -> bytearray:
    return wire.encode_request("classify", [name, [
        wire.datum(s, nv) for _label, s, nv in rows]])


ENCODERS = {"train": train_request, "classify": classify_request}


def summarize_train(result: Any) -> Any:
    return result


def summarize_classify(result: Any) -> Any:
    """(rows, labels per row as a sorted tuple or None if they differ,
    all scores finite) of a classify answer, kept for every call of the
    window without keeping the scores."""
    try:
        labels = {tuple(sorted(str(e[0]) for e in row)) for row in result}
        finite = all(math.isfinite(float(e[1])) for row in result for e in row)
        return (len(result), labels.pop() if len(labels) == 1 else None,
                finite)
    except (TypeError, ValueError, IndexError):
        return (-1, None, False)


SUMMARIZERS = {"train": summarize_train, "classify": summarize_classify}


def well_formed(method: str, summary: Any, rows: int,
                data: Dict[str, Any]) -> bool:
    """Whether the answer to a ``method`` call of ``rows`` rows, as its
    summarizer kept it, is what the engine owes: a ``train`` answers its
    row count, a ``classify`` one finite score per row and live label (a
    model that has seen one label only answers that label)."""
    if method == "train":
        return summary == rows
    n, labs, finite = summary
    return n == rows and finite and labs is not None \
        and set(labs) <= set(data["labels"])
