"""Public jit'd wrappers for the kernels package.

Dispatch policy (``use_pallas``):
  - ``"auto"``  — the compiled Pallas kernel on TPU backends, the jnp
                  reference elsewhere (CPU tests; the dry-run/roofline
                  path intentionally lowers the jnp path).
  - ``"interpret"`` — Pallas kernel body executed by the interpreter (CPU
                  correctness validation; used by tests/kernels/).
  - ``"pallas"`` / ``"ref"`` — forced.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.chunking import num_chunks
from repro.kernels import ref as _ref
from repro.kernels.chunk_digest import LANES, digest_words, padded_row_words
from repro.kernels.flash_attention import flash_attention_pallas

Dispatch = Literal["auto", "interpret", "pallas", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas: Dispatch) -> str:
    if use_pallas == "auto":
        return "pallas" if _on_tpu() else "ref"
    return use_pallas


def auto_dispatch() -> str:
    """What ``use_pallas="auto"`` runs in this process: ``"pallas"`` (the
    compiled kernel) on TPU, ``"ref"`` (the jnp reference) elsewhere."""
    return _resolve("auto")


# ---------------------------------------------------------------------------
# chunk digests
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk_bytes", "mode"))
def _chunk_digests_jit(x: jax.Array, chunk_bytes: int, mode: str) -> jax.Array:
    if mode == "ref":
        return _ref.chunk_digests_jnp(x, chunk_bytes)
    if x.dtype.itemsize == 2:
        # 16-bit state goes to the kernel as stored, two halves per word
        units, per_word = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint16), 2
    else:
        units, per_word = _ref.to_u32_words(x), 1
    total_words = -(-units.shape[0] // per_word)
    cw = chunk_bytes // 4
    n = num_chunks(total_words * 4, chunk_bytes)
    row = padded_row_words(cw)
    if row == cw:
        units = jnp.pad(units, (0, n * row * per_word - units.shape[0]))
    else:  # chunks shorter than the kernel's row: pad each one
        units = jnp.pad(units, (0, n * cw * per_word - units.shape[0]))
        units = jnp.pad(units.reshape(n, cw * per_word), ((0, 0), (0, (row - cw) * per_word)))
    return digest_words(
        units.reshape(-1, LANES),
        chunk_words=cw,
        total_words=total_words,
        interpret=(mode == "interpret"),
    )


def chunk_digests(
    x: jax.Array, chunk_bytes: int, *, use_pallas: Dispatch = "auto"
) -> jax.Array:
    """Per-chunk digests of an array's byte stream -> (n_chunks, 2) u32 [hi, lo].

    Bit-identical to ``checkpoint.chunking.chunk_digest_np`` over the same
    chunk bytes (the shadow manager compares them directly).
    """
    if chunk_bytes % 4:
        raise ValueError("chunk_bytes must be a multiple of 4")
    return _chunk_digests_jit(x, chunk_bytes, _resolve(use_pallas))


def digests_to_u64(d: jax.Array | np.ndarray) -> np.ndarray:
    """(n, 2) u32 [hi, lo] -> (n,) python-int-compatible u64 digests."""
    d = np.asarray(d)
    return (d[:, 0].astype(np.uint64) << np.uint64(32)) | d[:, 1].astype(np.uint64)


def tree_chunk_digests(
    state, chunk_bytes: int, *, use_pallas: Dispatch = "auto"
) -> dict[str, list[int]]:
    """Per-chunk u64 digests of every leaf: {path: [digest, ...]}.

    The fused-digest primitive: a step program calls this as its final
    pass so the sync boundary receives ready-made digests instead of
    re-scanning the state (``ShadowStateManager.sync(device_digests=...)``).
    jax leaves go through the :func:`chunk_digests` kernel dispatch
    (Pallas on TPU, jnp reference elsewhere); host leaves hash with the
    bit-identical numpy reference.
    """
    from repro.checkpoint.chunking import chunk_digest_np
    from repro.utils.tree import flatten_with_paths

    flat, _ = flatten_with_paths(state)
    out: dict[str, list[int]] = {}
    for path, leaf in flat.items():
        if isinstance(leaf, jax.Array):
            d = digests_to_u64(
                chunk_digests(leaf, chunk_bytes, use_pallas=use_pallas)
            )
            out[path] = [int(x) for x in d]
            continue
        raw = np.ascontiguousarray(np.asarray(leaf)).reshape(-1).view(np.uint8)
        cb = int(chunk_bytes)
        out[path] = [
            chunk_digest_np(raw[i * cb : min(raw.nbytes, (i + 1) * cb)])
            for i in range(num_chunks(raw.nbytes, cb))
        ]
    return out


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: Dispatch = "auto",
) -> jax.Array:
    """Causal GQA attention. q: (B,Hq,Sq,D); k,v: (B,Hkv,Sk,D)."""
    mode = _resolve(use_pallas)
    if mode == "ref":
        return _ref.mha_reference(q, k, v, causal=causal, scale=scale)
    return flash_attention_pallas(
        q, k, v,
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=(mode == "interpret"),
    )
