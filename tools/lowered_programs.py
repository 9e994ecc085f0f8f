"""Hashes of the classifier's lowered step programs, to show which
programs a change to ops/classifier.py leaves as they were.

Lowers (does not compile) the train step (rows and slabs, every method;
the schema and scan forms), the scores, and the mesh's train step and
scores over four devices, at the label shapes the benchmark's cells run
and a few beside them, each on the CPU and for a v5e described by
``jax.experimental.topologies``; writes one JSON object, program name ->
sha256 of its StableHLO text, sorted by name. A Pallas kernel's body is
MLIR bytecode that carries its source's path and lines; it is hashed as
printed without them, so that two checkouts of the same code agree.
Lowering reads no device, so it runs on a host without the chip.

    python tools/lowered_programs.py [--root CHECKOUT] --out F

``--root`` lowers the package of another checkout (a ``git archive`` of
the parent, say); ``diff`` of two files then names the programs whose
text differs."""

from __future__ import annotations

import argparse
import base64
import hashlib
import itertools
import json
import os
import re
import sys

#: (capacity, columns, label rows addressed): the Criteo cells, news20 at
#: 24 and 32 rows and 8, tables in no whole tile, one to four groups,
#: wili2018 at 240, 32 and 8 rows
SHAPES = [(8, 1 << 25, 8), (32, 1 << 23, 24), (32, 1 << 23, 32),
          (32, 1 << 23, 8), (4, 256, 4), (48, 200, 48), (16, 1 << 12, 16),
          (256, 1 << 20, 240), (256, 1 << 20, 32), (256, 1 << 20, 8)]
#: (rows, entries a row, slabs): the train flushes, serve's step, slabs
BATCHES = [(8192, 40, False), (8192, 832, False), (512, 40, False),
           (16384, 64, True), (2048, 64, True)]
METHODS = ["AROW", "PA", "CW", "NHERD", "perceptron", "PA1"]
#: (capacity, columns, rows) of the mesh's state, sharded on columns
MESH_SHAPES = [(8, 1 << 28, 8), (8, 1 << 14, 8), (32, 1 << 14, 24),
               (64, 1 << 14, 32)]


#: a Pallas kernel's body in the text of a program that calls it
_BODY = re.compile(r"(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)")


def _text(lowered):
    """The program's StableHLO text, each Pallas body replaced by the
    sha256 of its assembly without locations."""
    from jaxlib.mlir import ir

    def plain(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(m.group(2))) \
                .operation.get_asm(enable_debug_info=False)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest() \
            + m.group(3)
    return _BODY.sub(plain, lowered.as_text())


def _programs(jax, jnp, C, sm, topo):
    """(name, lowered) of every program, on the CPU and for the v5e."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    for plat in ("cpu", "v5e"):
        one = None if plat == "cpu" else SingleDeviceSharding(topo.devices[0])

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        for (cap, dim, rows), (b, k, slabs), method in itertools.product(
                SHAPES, BATCHES, METHODS):
            if method not in ("AROW", "PA") and (b, k) != (8192, 40):
                continue
            conf = method in C.CONFIDENCE_METHODS
            state = C.ClassifierState(
                sds((cap, dim), jnp.float32), sds((cap, dim), jnp.float32),
                *[sds((cap, dim) if conf else (1, 1), jnp.float32)] * 2)
            idx, val = sds((b, k), jnp.int32), sds((b, k), jnp.float32)
            labels, mask = sds((b,), jnp.int32), sds((rows,), jnp.bool_)
            owner = sds((b,), jnp.int32) if slabs else None
            for token in (True, False):
                yield (f"{plat} rows {cap} {dim} {rows} {b}x{k} "
                       f"slabs={slabs} {method} {token}",
                       C.train_batch_parallel.lower(
                           state, idx, val, labels, mask, 1.0, owner,
                           method=method, token=token))
            if method != "AROW":
                continue
            yield (f"{plat} scores {cap} {dim} {rows} {b}x{k}",
                   C.scores.lower(state, idx, val, mask))
            if slabs or rows != cap:
                continue
            yield (f"{plat} schema {cap} {dim} {b}x{k}",
                   C.train_batch_schema.lower(
                       state, sds((k,), jnp.int32), val, labels, mask, 1.0,
                       method=method, token=True))
            if b <= 512:
                yield (f"{plat} scan {cap} {dim} {b}x{k}",
                       C.train_batch_sequential.lower(
                           state, idx, val, labels, mask, 1.0,
                           method=method, token=True))
        devs = np.asarray(jax.devices()[:4] if plat == "cpu"
                          else topo.devices[:4])
        mesh = Mesh(devs, ("shard",))
        cols, flat = (NamedSharding(mesh, P(None, "shard")),
                      NamedSharding(mesh, P("shard")))
        rep = NamedSharding(mesh, P())
        for cap, dim, rows in MESH_SHAPES:
            state = C.ClassifierState(*[jax.ShapeDtypeStruct(
                (cap, dim), jnp.float32, sharding=cols)] * 4)
            mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=rep)
            for b, ks in [(8192, 24), (512, 16)]:
                yield (f"{plat} mesh train {cap} {dim} {rows} {b}x{ks}",
                       sm.train_batch.lower(
                           mesh, state,
                           jax.ShapeDtypeStruct((4, ks, b), jnp.int32,
                                                sharding=flat),
                           jax.ShapeDtypeStruct((4, ks, b), jnp.float32,
                                                sharding=flat),
                           jax.ShapeDtypeStruct((b,), jnp.int32,
                                                sharding=rep),
                           mask, 1.0, method="AROW", token=True))
                yield (f"{plat} mesh scores {cap} {dim} {rows} {b}x{ks}",
                       sm.scores.lower(
                           mesh, state,
                           jax.ShapeDtypeStruct((b, ks), jnp.int32,
                                                sharding=rep),
                           jax.ShapeDtypeStruct((b, ks), jnp.float32,
                                                sharding=rep), mask))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", required=True)
    ns = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.abspath(ns.root))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from jubatus_tpu.ops import classifier as C
    from jubatus_tpu.parallel import sharded_model as sm

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    out = {name: hashlib.sha256(_text(low).encode()).hexdigest()
           for name, low in _programs(jax, jnp, C, sm, topo)}
    os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump(out, f, indent=0, sort_keys=True)
    print(len(out), "programs", file=sys.stderr)


if __name__ == "__main__":
    main()
