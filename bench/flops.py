"""Operations and bytes the benchmark's work needs, computed from shapes.

Training FLOPs per token follow PaLM (arXiv:2204.02311, App. B): 6 N for
the parameter matrix products of the forward and backward passes (the tied
embedding counted once, as the output projection), plus attention's own
products, 12 L H Q T. Nothing recomputed counts.
"""
from __future__ import annotations

import numpy as np


def n_params(cfg: dict) -> int:
    """Parameters of a configuration file, embedding counted once if tied."""
    D, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    emb = V * D * (1 if cfg["tie_word_embeddings"] else 2)
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Q = cfg.get("head_dim") or D // Hq
    per = D * Q * (Hq + 2 * Hkv) + Hq * Q * D + 3 * D * cfg["intermediate_size"] + 2 * D
    if cfg["attention_bias"]:
        per += Q * (Hq + 2 * Hkv)
    return emb + L * per + D


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    n = 6.0 * n_params(cfg)
    L, Hq = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    Q = cfg.get("head_dim") or cfg["hidden_size"] // Hq
    return n + 12.0 * L * Hq * Q * seq_len


def tree_bytes(leaves) -> int:
    """Bytes a pass over these arrays reads once (a digest pass, a copy)."""
    return int(sum(np.dtype(x.dtype).itemsize * int(np.prod(x.shape)) for x in leaves))


def digest_roofline(ctx: dict, passes: int) -> float | None:
    """``passes`` digest passes over the whole device state (each leaf read
    once) against the digest programs' device seconds in the trace, as a
    percentage of the chip's HBM bandwidth."""
    from bench.trace_reduce import module_seconds

    red = ctx["trace"]
    if red is None or ctx["peak"] is None or passes <= 0:
        return None
    seconds, runs = module_seconds(red, "jit__chunk_digests_jit")
    if not runs:
        return None
    least = passes * ctx["state_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
