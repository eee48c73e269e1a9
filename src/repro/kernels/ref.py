"""Pure-jnp / numpy oracles for every Pallas kernel.

Each kernel in this package must agree with its oracle bit-for-bit
(digests) or to numerical tolerance (attention) across the shape/dtype
sweeps in tests/kernels/.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.chunking import chunk_digest_np, num_chunks

DIGEST_PRIME = np.uint32(16777619)
DIGEST_SEED = np.uint32(2166136261)


# ---------------------------------------------------------------------------
# chunk_digest
# ---------------------------------------------------------------------------

def chunk_digests_np(arr: np.ndarray, chunk_bytes: int) -> np.ndarray:
    """Host oracle: (n_chunks, 2) u32 [hi, lo] digests of the byte stream."""
    raw = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
    n = num_chunks(raw.nbytes, chunk_bytes)
    out = np.zeros((n, 2), np.uint32)
    for i in range(n):
        d = chunk_digest_np(raw[i * chunk_bytes : min(raw.nbytes, (i + 1) * chunk_bytes)])
        out[i, 0] = np.uint32(d >> 32)
        out[i, 1] = np.uint32(d & 0xFFFFFFFF)
    return out


def to_u32_words(x: jax.Array) -> jax.Array:
    """Bit-reinterpret any array as a flat little-endian u32 word stream.

    Matches numpy's ``.view(np.uint8)`` + zero-pad + ``.view(np.uint32)``.
    Narrow dtypes are packed from strided slices of the flat element
    stream, never through a (n, k) array whose tiny minor dimension a TPU
    would pad to 128 lanes.
    """
    flat = x.reshape(-1)
    size = flat.dtype.itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if size > 4:
        b = jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1, 4)
        return jax.lax.bitcast_convert_type(b, jnp.uint32)
    per_word = 4 // size
    u = jax.lax.bitcast_convert_type(flat, jnp.uint8 if size == 1 else jnp.uint16)
    pad = (-u.shape[0]) % per_word
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)])
    words = u[0::per_word].astype(jnp.uint32)
    for p in range(1, per_word):
        words = words | (u[p::per_word].astype(jnp.uint32) << jnp.uint32(8 * size * p))
    return words


def chunk_digests_jnp(x: jax.Array, chunk_bytes: int) -> jax.Array:
    """jit-friendly oracle: same math as :func:`chunk_digest_np`, batched."""
    if chunk_bytes % 4:
        raise ValueError("chunk_bytes must be a multiple of 4")
    words = to_u32_words(x)
    nbytes = int(np.prod(x.shape, dtype=np.int64)) * x.dtype.itemsize
    total_words = words.shape[0]
    cw = chunk_bytes // 4
    n = num_chunks(nbytes, chunk_bytes)
    padded = n * cw
    if padded != total_words:
        words = jnp.concatenate(
            [words, jnp.zeros((padded - total_words,), jnp.uint32)]
        )
    w = words.reshape(n, cw)
    idx = jax.lax.broadcasted_iota(jnp.uint32, (n, cw), 1) + jnp.uint32(1)
    # real word counts are static (shapes known at trace time)
    real = jnp.asarray(
        np.minimum(
            cw, np.maximum(total_words - np.arange(n, dtype=np.int64) * cw, 0)
        ).astype(np.uint32)
    )
    mask = idx <= real[:, None]
    lo_terms = jnp.where(mask, w ^ (idx * jnp.uint32(DIGEST_PRIME)), jnp.uint32(0))
    lo = lo_terms.sum(axis=1, dtype=jnp.uint32)
    hi_terms = jnp.where(
        mask, w * ((idx << jnp.uint32(1)) | jnp.uint32(1)), jnp.uint32(0)
    )
    hi = jax.lax.reduce(
        hi_terms, np.uint32(0), lambda a, b: jax.lax.bitwise_xor(a, b), (1,)
    ) ^ jnp.uint32(DIGEST_SEED)
    return jnp.stack([hi, lo], axis=1)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Dense softmax attention oracle.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0 (GQA).
    Returns (B, Hq, Sq, D) in q's dtype; softmax in f32.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = float(scale if scale is not None else 1.0 / np.sqrt(D))
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kq.astype(jnp.float32))
    s = s * scale
    if causal:
        Sk = k.shape[2]
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned (cache decode)
        kpos = jnp.arange(Sk)[None, :]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32))
    return out.astype(q.dtype)
