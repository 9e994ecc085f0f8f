"""The plain reference of the linear classifier over split text:
``linear_classifier.py``'s numpy float32 AROW, with a converter of its own
that states Jubatus' ``space`` splitter. A configuration names it
(``"reference": "linear_classifier_text"``). Like its sibling it imports
nothing of the program and works from the configuration file's ``model``
section and the rows the generator sent; the model, the batches, the
update and the scores are the sibling's own classes, unchanged (AROW there
is multiclass by the best rival label, so twenty labels need nothing new).

The ``space`` rule, as the upstream converter states it (a
``string_rules`` entry of type ``space`` with ``sample_weight`` ``bin``
and ``global_weight`` ``bin``):

- the value of every string key that the rule's ``key`` pattern matches is
  cut where Python's ``str.split()`` cuts it: at runs of whitespace, with
  no empty token at either end;
- every *distinct* token ``t`` of the value of key ``k`` is the feature
  ``"k$t@space#bin/bin"`` with value 1, however often it occurs (``bin``
  counts presence; ``tf`` would count occurrences);
- a feature's column is ``crc32(name) & (D - 1)``, 0 mapped to 1, and
  values that meet on one column within a row add (two distinct tokens
  whose names collide give 2).

A string rule of another type or weight, a num rule, a filter or a plugin
type is refused by name, not guessed at: the sibling knows ``str`` and
``num``, and a configuration that mixes them with ``space`` brings a
reference that states the mix."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List


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

Batch = _plain.Batch
Model = _plain.Model
universe_of = _plain.universe_of
label_scores = _plain.label_scores
column = _plain.column
_matches = _plain._matches


def split(text: str) -> List[str]:
    """The tokens of one value: ``str.split()``'s."""
    return text.split()


class Featurizer:
    """``model.converter`` of a configuration whose string rules are all
    ``space`` with ``bin``/``bin``, at width ``dim`` (the file's
    ``hash_max_size``, or a rehearsal's)."""

    def __init__(self, converter: Dict[str, Any], dim: int) -> None:
        self.dim = int(dim)
        for k in ("string_filter_rules", "num_filter_rules", "string_types",
                  "num_types", "num_rules", "binary_rules",
                  "combination_rules"):
            if converter.get(k):
                raise NotImplementedError(
                    f"converter.{k}: this reference knows space-split "
                    "string rules and nothing beside them")
        self.string_rules = []
        for rule in converter.get("string_rules", []):
            if (rule["type"], rule["sample_weight"], rule["global_weight"]) \
                    != ("space", "bin", "bin"):
                raise NotImplementedError(f"string rule {rule}: this "
                                          "reference knows space with bin/bin")
            self.string_rules.append((rule["key"], "@space#bin/bin"))
        if not self.string_rules:
            raise NotImplementedError("converter.string_rules: none given")
        #: (key, token) -> the columns its matching rules give it; a
        #: vocabulary's words come back in every document
        self._seen: Dict[Any, List[int]] = {}

    def token_columns(self, key: str, token: str) -> List[int]:
        cols = self._seen.get((key, token))
        if cols is None:
            cols = self._seen[(key, token)] = [
                column(f"{key}${token}{suffix}", self.dim)
                for pattern, suffix in self.string_rules
                if _matches(pattern, key)]
        return cols

    def string_columns(self, key: str, value: str) -> List[int]:
        """One column for each distinct token and matching rule (a column
        twice where two names collide)."""
        return [c for token in dict.fromkeys(split(value))
                for c in self.token_columns(key, token)]

    def num_columns(self, key: str) -> List[int]:
        return []                  # no num rule is stated here

    def __call__(self, row) -> Dict[int, float]:
        """One row -> {column: value}."""
        _label, strings, nums = row
        out: Dict[int, float] = {}
        for k, s in strings:
            for c in self.string_columns(k, s):
                out[c] = out.get(c, 0.0) + 1.0
        return out
