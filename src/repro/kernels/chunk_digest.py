"""Pallas TPU kernel: per-chunk content digests.

The checkpoint hot path of this framework (DESIGN §2): dirty-chunk
detection runs *on device*, so only a small per-chunk digest tensor — not
the data — crosses HBM->host before a sync. This kernel is the TPU
adaptation of CRUM's page-fault tracking: the VPU scans HBM-resident state
at memory bandwidth and emits one digest per chunk.

Layout: the caller lays the leaf's byte stream out as rows of 128 lanes
— either i32 words, or, for 16-bit dtypes, the raw u16 halves, two per
word — with each chunk's ``row`` words padded to a whole number of tiles
(1024 words: (8, 128) i32 or (16, 128) u16). Grid = (n_chunks, n_sub); the
sub-block axis is innermost and sequential, and each step folds its block,
one tile at a time, into a resident (2, 8, 128) lane-wise accumulator for
the chunk (its index map ignores ``j``). A 16-bit tile is widened to i32
in VMEM and each even lane takes its odd neighbour as the word's high
half (a lane rotate), so 16-bit state is read from HBM once, as it is
stored. The wrapper then folds the 1024 lanes of each chunk to the final
(hi, lo) pair.

All arithmetic is on i32 bit patterns: wrapping add, multiply, shift and
xor give the same low 32 bits as u32, and Mosaic vectorises i32 only.
Both mixes are associative, so lane- and sub-block partials combine
exactly:
    lo = wrapping-sum of (w ^ (idx * PRIME))
    hi = xor of (w * ((idx << 1) | 1)), finally xored with SEED
where ``idx`` is the 1-based word index within the chunk. Padding words
are masked by comparing idx to the chunk's real word count (computed from
static sizes), so device digests equal host digests bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import DIGEST_PRIME, DIGEST_SEED

LANES = 128
TILE_ROWS = 8
TILE_WORDS = TILE_ROWS * LANES
# 64K words = 256 KiB per sub-block: (512, 128) i32 or (1024, 128) u16,
# double-buffered well inside the scoped VMEM limit.
SUB_WORDS = 64 * 1024

_PRIME = int(np.uint32(DIGEST_PRIME).view(np.int32))


def padded_row_words(chunk_words: int) -> int:
    """Words per chunk row in the kernel's layout: a whole number of
    sub-blocks, each a whole number of (8, 128) tiles."""
    sub = min(SUB_WORDS, -(-chunk_words // TILE_WORDS) * TILE_WORDS)
    return -(-chunk_words // sub) * sub


def _digest_kernel(x_ref, o_ref, *, chunk_words: int, sub_words: int, total_words: int):
    i = pl.program_id(0)  # chunk ordinal
    j = pl.program_id(1)  # sub-block ordinal within the chunk
    halves = x_ref.dtype.itemsize == 2  # u16 halves, two lanes per word
    rows = TILE_ROWS * (2 if halves else 1)  # rows of one 1024-word tile

    # real (unpadded) words in this chunk, from static sizes. i32 is safe:
    # a single shard stream is < 2**31 words (8 GiB) on 16 GiB-HBM parts.
    real = jnp.clip(total_words - i * chunk_words, 0, chunk_words)
    # 1-based word index within the chunk of each slot of the first tile;
    # with halves, the word starts at the even lane and odd lanes drop out
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    if halves:
        pos = row * (LANES // 2) + (lane >> 1) + (j * sub_words + 1)
        word_lane = (lane & 1) == 0
    else:
        pos = row * LANES + lane + (j * sub_words + 1)

    def fold(t, carry):
        hi, lo = carry
        x = x_ref[pl.ds(pl.multiple_of(t * rows, rows), rows), :]
        if halves:
            x = x.astype(jnp.int32)
            # little-endian: the next lane's half is the word's high half
            w = x | (pltpu.roll(x, LANES - 1, 1) << 16)
        else:
            w = x
        idx = pos + t * TILE_WORDS
        mask = idx <= real
        if halves:
            mask = mask & word_lane
        lo = lo + jnp.where(mask, w ^ (idx * _PRIME), 0)
        hi = hi ^ jnp.where(mask, w * ((idx << 1) | 1), 0)
        return hi, lo

    zero = jnp.zeros((rows, LANES), jnp.int32)
    n_tiles = sub_words // TILE_WORDS
    hi, lo = jax.lax.fori_loop(0, n_tiles, fold, (zero, zero), unroll=True)
    if halves:  # fold the tile's two (8, 128) halves together
        hi = hi[:TILE_ROWS] ^ hi[TILE_ROWS:]
        lo = lo[:TILE_ROWS] + lo[TILE_ROWS:]

    @pl.when(j == 0)
    def _init():
        o_ref[0, 0] = jnp.zeros((TILE_ROWS, LANES), jnp.int32)
        o_ref[0, 1] = jnp.zeros((TILE_ROWS, LANES), jnp.int32)

    o_ref[0, 0] = o_ref[0, 0] ^ hi
    o_ref[0, 1] = o_ref[0, 1] + lo


@functools.partial(jax.jit, static_argnames=("chunk_words", "total_words", "interpret"))
def digest_words(
    words2d: jax.Array,
    *,
    chunk_words: int,
    total_words: int,
    interpret: bool = False,
) -> jax.Array:
    """Digest a (n_chunks * row // 128, 128) word array -> (n_chunks, 2) u32.

    ``chunk_words`` is the *logical* chunk length; ``row`` (the padded
    words per chunk) is :func:`padded_row_words` of it. ``words2d`` may be
    i32 or u32 words, or u16 halves in stream order (low half first), in
    which case it has twice the rows. Only its bit patterns matter.
    """
    row = padded_row_words(chunk_words)
    sub = min(SUB_WORDS, row)
    n_sub = row // sub
    per_row = LANES // 2 if words2d.dtype.itemsize == 2 else LANES  # words
    if words2d.ndim != 2 or words2d.shape[1] != LANES or (words2d.shape[0] * per_row) % row:
        raise ValueError(
            f"words2d {words2d.shape} {words2d.dtype} is not "
            f"(n_chunks * {row // per_row}, {LANES})"
        )
    n_chunks = words2d.shape[0] * per_row // row
    if words2d.dtype.itemsize == 2:
        x = jax.lax.bitcast_convert_type(words2d, jnp.uint16)
    else:
        x = jax.lax.bitcast_convert_type(words2d, jnp.int32)
    kernel = functools.partial(
        _digest_kernel,
        chunk_words=chunk_words,
        sub_words=sub,
        total_words=total_words,
    )
    sub_rows = sub // per_row
    acc = pl.pallas_call(
        kernel,
        grid=(n_chunks, n_sub),
        in_specs=[pl.BlockSpec((sub_rows, LANES), lambda i, j: (i * n_sub + j, 0))],
        out_specs=pl.BlockSpec((1, 2, TILE_ROWS, LANES), lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 2, TILE_ROWS, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="chunk_digest",
    )(x)
    acc = jax.lax.bitcast_convert_type(acc, jnp.uint32).reshape(n_chunks, 2, TILE_WORDS)
    hi = jax.lax.reduce(
        acc[:, 0], np.uint32(0), jax.lax.bitwise_xor, (1,)
    ) ^ jnp.uint32(DIGEST_SEED)
    lo = acc[:, 1].sum(axis=1, dtype=jnp.uint32)
    return jnp.stack([hi, lo], axis=1)
