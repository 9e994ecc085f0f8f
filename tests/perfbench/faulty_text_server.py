"""A server with one fault of the text deployment planted, one for each of
the two guarantees ``news20_arow`` adds, beside those of
``faulty_server.py``:

    python faulty_text_server.py <fault> classifier -f ... (the server's arguments)

Faults: ``repeated_word_counted`` (a word that occurs twice in a document
counts twice: the string rules run with ``sample_weight`` ``tf`` where the
configuration says ``bin``), ``first_8_labels_only`` (``classify``
answers the first 8 labels' scores, the rows a model is born with, however
many were trained), ``none``."""

import json
import os
import sys


def _rewrite_config(argv, change) -> None:
    """The server's ``-f`` file, changed and written beside itself."""
    at = argv.index("-f") + 1
    with open(argv[at]) as f:
        model = json.load(f)
    change(model)
    argv[at] = os.path.join(os.path.dirname(argv[at]), "model_faulty.json")
    with open(argv[at], "w") as f:
        json.dump(model, f)


def plant(fault: str, argv) -> None:
    if fault == "repeated_word_counted":
        def tf(model):
            for rule in model["converter"]["string_rules"]:
                rule["sample_weight"] = "tf"
        _rewrite_config(argv, tf)
    elif fault == "first_8_labels_only":
        from jubatus_tpu.models.classifier import ClassifierDriver

        real = ClassifierDriver.classify_hashed

        def first_8(self, idx, val):
            return [row[:8] for row in real(self, idx, val)]

        ClassifierDriver.classify_hashed = first_8
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv[1]
    from jubatus_tpu.server.__main__ import main

    argv = sys.argv[2:]
    plant(fault, argv)
    sys.exit(main(argv))
