"""Pallas TPU kernels for the similarity-search hot loops.

The LSH backends (nearest_neighbor / recommender / anomaly, reference
jubatus_core lsh/minhash indexes) reduce every query to a dense
signature-table scan: XOR+popcount over packed uint32 bit signatures
(hamming) or lane-match counting (minhash). The XLA formulation
(ops/knn.py) broadcasts a [B, C, W] intermediate and relies on fusion;
these kernels tile the candidate table into VMEM blocks and unroll the
small signature-word axis into 2D VPU ops, so HBM traffic is one
transpose and one scan of the table regardless of batch size.

Layout per grid step (candidate block c):
    q   [B,  W] uint32   resident across all steps (constant index map)
    rT  [W, Cb] uint32   one table tile, TRANSPOSED: candidates on lanes
    out [B, Cb] float32  distances for this tile

The table is stored [C, W]; the jitted wrapper transposes it (one XLA pass
over C*W words) so that, inside the kernel, word w of every candidate is
one lane-dense [1, Cb] row and word w of every query one [B, 1] column —
each unrolled step is a broadcast VPU op. Slicing a column out of a
[Cb, W] tile and turning it into a row instead (the first version of this
kernel) makes Mosaic move every element from sublanes to lanes: on
libtpu 0.0.34 that took minutes to compile and ran out of scoped VMEM at
C = 2048 (CHANGES.md, PR 21).

Popcount is the classic SWAR bit-ladder (shift/mask adds) — elementwise
uint32 ops the VPU executes natively; no MXU involvement.

Interpret mode runs the same kernels on CPU (tests, and the virtual
8-device mesh); on a real TPU backend `enabled()` flips them on by
default — set JUBATUS_TPU_PALLAS=0/1 to force either way.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# block of candidate rows per grid step, at most; W is small (hash_num/32
# ≤ 16 words, or hash_num ≤ 64 minhash lanes), so the [B, block] uint32
# accumulator tile dominates VMEM. It is held near _TILE_ELEMS elements
# whatever B is: 64 x 2048 x 4 = 512 KiB.
CAND_BLOCK = 2048
_TILE_ELEMS = 64 * 2048


def _block_for(b: int, c: int, block: int) -> int:
    """Candidates per grid step: a multiple of 128 lanes, or all of them."""
    block = min(block, max(128, _TILE_ELEMS // b // 128 * 128))
    return c if c <= block else block


def enabled() -> bool:
    """Route knn distance scans through pallas? Default: only on TPU."""
    flag = os.environ.get("JUBATUS_TPU_PALLAS", "")
    if flag in ("0", "false", "no"):
        return False
    if flag in ("1", "true", "yes"):
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _popcount32(v):
    """SWAR popcount over uint32 (no lax.population_count: keeps the op set
    to shifts/ands/adds that Mosaic lowers everywhere)."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def _sig_scan_kernel(q_ref, rt_ref, o_ref, *, mode: str, words: int, scale: float):
    """One [B, Cb] output tile; unrolled loop over the signature words."""
    acc = jnp.zeros(o_ref.shape, jnp.uint32)
    for w in range(words):
        qw = q_ref[:, w:w + 1]         # [B, 1]
        rw = rt_ref[w:w + 1, :]        # [1, Cb]
        if mode == "hamming":
            acc += _popcount32(jnp.bitwise_xor(qw, rw))
        else:  # minhash: count matching lanes
            acc += (qw == rw).astype(jnp.uint32)
    # Mosaic has no uint32→f32 cast; counts are ≤ hash_num so int32 is exact
    d = acc.astype(jnp.int32).astype(jnp.float32) * jnp.float32(scale)
    o_ref[:] = (jnp.float32(1.0) - d) if mode == "minhash" else d


@functools.partial(jax.jit, static_argnames=("mode", "hash_num", "block"))
def _sig_scan(q_sigs, row_sigs, *, mode: str, hash_num: int, block: int):
    b, words = q_sigs.shape
    c = row_sigs.shape[0]
    block = _block_for(b, c, block)
    grid = (pl.cdiv(c, block),)
    if mode == "hamming":
        scale = 1.0 / float(hash_num)
    else:
        scale = 1.0 / float(words)  # minhash sigs are one word per hash
    out = pl.pallas_call(
        functools.partial(_sig_scan_kernel, mode=mode, words=words, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, words), lambda i: (0, 0)),
            pl.BlockSpec((words, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((b, block), lambda i: (0, i)),
        interpret=_interpret(),
    )(q_sigs, row_sigs.T)
    return out


def hamming_distances_batch(q_sigs, row_sigs, *, hash_num: int,
                            block: int = CAND_BLOCK):
    """q_sigs [B, W], row_sigs [C, W] uint32 → [B, C] normalized Hamming."""
    return _sig_scan(q_sigs, row_sigs, mode="hamming", hash_num=hash_num,
                     block=block)


def hamming_distances(q_sig, row_sigs, *, hash_num: int,
                      block: int = CAND_BLOCK):
    """q_sig [W], row_sigs [C, W] → [C]."""
    return hamming_distances_batch(q_sig[None, :], row_sigs,
                                   hash_num=hash_num, block=block)[0]


def minhash_distances_batch(q_sigs, row_sigs, *, block: int = CAND_BLOCK):
    """q_sigs [B, H], row_sigs [C, H] uint32 → [B, C] (1 - match fraction)."""
    return _sig_scan(q_sigs, row_sigs, mode="minhash",
                     hash_num=q_sigs.shape[1], block=block)


def minhash_distances(q_sig, row_sigs, *, block: int = CAND_BLOCK):
    return minhash_distances_batch(q_sig[None, :], row_sigs, block=block)[0]
