"""A server with one fault planted in the timed path, for the tests that
see ``correct`` come out false:

    python faulty_server.py <fault> classifier -f ... (the server's arguments)

Faults: ``state_unchanged`` (the train step returns its state as it got
it), ``half_batch`` (the second half of every flush's rows is left out),
``answer_altered`` (one classify score is altered where it is produced),
``none``."""

import sys


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from jubatus_tpu.ops import classifier as ops

    if fault == "state_unchanged":
        ops.train_batch = lambda state, *a, **k: state
    elif fault == "half_batch":
        real = ops.train_batch

        def half(state, idx, val, labels, mask, param, **kw):
            # leave out every second row (a row of zeros is a no-op)
            keep = (jnp.arange(val.shape[0]) % 2) == 0
            return real(state, idx, val * keep[:, None], labels, mask,
                        param, **kw)

        ops.train_batch = half
    elif fault == "answer_altered":
        real_scores = ops.scores

        def altered(state, idx, val, mask):
            s = real_scores(state, idx, val, mask)
            return s.at[0, 0].add(0.01 * (1.0 + jnp.abs(s[0, 0])))

        ops.scores = altered
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv[1]
    from jubatus_tpu.server.__main__ import main

    plant(fault)
    sys.exit(main(sys.argv[2:]))
