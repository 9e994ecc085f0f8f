"""A server with one fault of the combination path planted, beside those of
``faulty_server.py``:

    python faulty_cross_server.py <fault> classifier -f ... (the server's arguments)

Faults: ``last_slot_dropped`` (the train step leaves out the feature in the
highest column of every row: one of its 780), ``none``."""

import sys


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from jubatus_tpu.ops import classifier as ops

    if fault == "last_slot_dropped":
        real = ops.train_batch

        def dropped(state, idx, val, labels, mask, param, **kw):
            last = jnp.maximum(jnp.sum(idx != 0, axis=1) - 1, 0)
            keep = jnp.arange(val.shape[1])[None, :] != last[:, None]
            return real(state, idx, val * keep, labels, mask, param, **kw)

        ops.train_batch = dropped
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv[1]
    from jubatus_tpu.server.__main__ import main

    plant(fault)
    sys.exit(main(sys.argv[2:]))
