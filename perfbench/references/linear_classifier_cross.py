"""The plain reference of the linear classifier under Jubatus' combination
rules: ``linear_classifier.py``'s numpy float32 AROW, with a converter that
also crosses a row's features. A configuration names it (``"reference":
"linear_classifier_cross"``). Like its sibling it imports nothing of the
program and works from the configuration file's ``model`` section and the
rows the generator sent; the model, the batches and the scores are the
sibling's own classes, unchanged.

The combination rule, as the upstream converter states it
(``combination_rules`` of ``model.converter``, each with ``key_left``,
``key_right`` and a ``type`` that ``combination_types`` maps to ``mul`` or
``add``):

- the features a row has *before* any combination are frozen: names and
  values (a numeric key whose value is 0 is a feature of value 0);
- for each rule, every unordered pair of two of those features with
  different names, one matching ``key_left`` and the other ``key_right``
  (either way round), gives once the feature ``"<a>&<b>"``, ``a`` the
  smaller name in code-point order, with the product (``mul``) or the sum
  (``add``) of the two frozen values; the patterns are matched against the
  feature *names*;
- values that meet on one name, and then on one hashed column, add.

A rule's type that is neither, or any rule the sibling refuses, is
refused here too."""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List, Tuple


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

OPS = {"mul": lambda a, b: a * b, "add": lambda a, b: a + b}


class Featurizer(_plain.Featurizer):
    """``model.converter`` with ``combination_rules``, at width ``dim``."""

    def __init__(self, converter: Dict[str, Any], dim: int) -> None:
        super().__init__(converter, dim)
        types = {"mul": "mul", "add": "add"}
        for name, params in (converter.get("combination_types") or {}).items():
            types[name] = (params or {}).get("method")
        self.combination_rules: List[Tuple[str, str, Any]] = []
        for rule in converter.get("combination_rules", []):
            method = types.get(rule["type"])
            if method not in OPS:
                raise NotImplementedError(f"combination rule {rule}: this "
                                          "reference knows mul and add")
            self.combination_rules.append(
                (rule["key_left"], rule["key_right"], OPS[method]))

    def named(self, row) -> Dict[str, float]:
        """One row -> {feature name: value} before any combination."""
        _label, strings, nums = row
        out: Dict[str, float] = {}
        for pattern, suffix in self.string_rules:
            for k, s in strings:
                if _matches(pattern, k):
                    name = f"{k}${s}{suffix}"
                    out[name] = out.get(name, 0.0) + 1.0
        for pattern, suffix in self.num_rules:
            for k, v in nums:
                if _matches(pattern, k):
                    name = f"{k}{suffix}"
                    out[name] = out.get(name, 0.0) + float(v)
        return out

    def combined(self, base: Dict[str, float]) -> List[Tuple[str, float]]:
        """The pair features of the frozen ``base``, rule by rule."""
        names = sorted(base)
        out: List[Tuple[str, float]] = []
        for left, right, op in self.combination_rules:
            is_left = [_matches(left, n) for n in names]
            is_right = [_matches(right, n) for n in names]
            for i, a in enumerate(names):
                for j in range(i + 1, len(names)):
                    if (is_left[i] and is_right[j]) \
                            or (is_left[j] and is_right[i]):
                        b = names[j]
                        out.append((f"{a}&{b}", op(base[a], base[b])))
        return out

    def __call__(self, row) -> Dict[int, float]:
        """One row -> {column: value}."""
        base = self.named(row)
        out: Dict[int, float] = {}
        for name, v in list(base.items()) + self.combined(base):
            c = column(name, self.dim)
            out[c] = out.get(c, 0.0) + v
        return out
