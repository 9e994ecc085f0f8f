"""A ``--shard-devices`` server with one fault of the mesh planted, beside
those of ``faulty_server.py``:

    python faulty_sharded_server.py <fault> classifier -f ... (the server's arguments)

Faults: ``shard_drops_updates`` (the second shard of the mesh forgets what
every train step taught its column range: its slice of the two diff tables
is as it was born), ``none``."""

import sys


def plant(fault: str) -> None:
    from jubatus_tpu.parallel import sharded_model as sm

    if fault == "shard_drops_updates":
        real = sm.train_batch

        def dropped(mesh, state, *a, axis=sm.DEFAULT_AXIS, **kw):
            new = real(mesh, state, *a, axis=axis, **kw)
            d = new.dw.shape[1] // mesh.shape[axis]
            return new._replace(dw=new.dw.at[:, d:2 * d].set(0.0),
                                dprec=new.dprec.at[:, d:2 * d].set(0.0))

        sm.train_batch = dropped
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv[1]
    from jubatus_tpu.server.__main__ import main

    plant(fault)
    sys.exit(main(sys.argv[2:]))
