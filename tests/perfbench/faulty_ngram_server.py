"""A server with one fault of the language-identification deployment
planted, one for each guarantee of its features that ``wili2018_arow``
states, beside those of ``faulty_text_server.py``:

    python faulty_ngram_server.py <fault> classifier -f ... (the server's arguments)

Faults: ``windows_counted_once`` (a bigram that occurs twice in a
paragraph counts once: the rule runs with ``sample_weight`` ``bin`` where
the configuration says ``tf``), ``trigrams`` (the type slides three code
points where the configuration says two), and every fault of
``faulty_text_server.py`` (``first_8_labels_only``, ``none``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faulty_text_server as text  # noqa: E402


def plant(fault: str, argv) -> None:
    if fault == "windows_counted_once":
        def once(model):
            for rule in model["converter"]["string_rules"]:
                rule["sample_weight"] = "bin"
        text._rewrite_config(argv, once)
    elif fault == "trigrams":
        def three(model):
            for t in model["converter"]["string_types"].values():
                t["char_num"] = "3"
        text._rewrite_config(argv, three)
    else:
        text.plant(fault, argv)


if __name__ == "__main__":
    fault = sys.argv[1]
    from jubatus_tpu.server.__main__ import main

    argv = sys.argv[2:]
    plant(fault, argv)
    sys.exit(main(argv))
