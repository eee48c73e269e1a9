"""Operations and bytes the benchmark's work needs, computed from shapes.

Training FLOPs per token follow PaLM (arXiv:2204.02311, App. B): 6 N for
the parameter matrix products of the forward and backward passes (the tied
embedding counted once, as the output projection), plus the sequence
mixer's own products. Nothing recomputed counts.

- A dense GQA block (the default) adds attention's 12 L H Q T.
- A Mamba2 block (a file whose ``ssm_cfg`` states ``"layer": "Mamba2"``,
  the published ``state-spaces`` key names) adds the chunked SSD's products
  (arXiv:2405.21060), 3 L (2 Q N G + 2 H Q P + 4 H N P) with Q the
  chunk length: C B^T inside a chunk, its masked decay-weighted product
  with X, the chunk states B^T X and the output from the carried state C h.
  Full chunks count, as attention counts the full T.
"""
from __future__ import annotations

import numpy as np

# Read from ``ssm_cfg``. The published config.json leaves them to mamba_ssm's
# Mamba2 defaults, so a configuration file states them.
_MAMBA2_KEYS = ("d_state", "d_conv", "expand", "headdim", "ngroups", "chunk_size")


def _need(cfg: dict, key: str, where: str = ""):
    if key not in cfg:
        raise ValueError(f"configuration has no key {where + key!r}, which the FLOP count reads")
    return cfg[key]


def _is_mamba2(cfg: dict) -> bool:
    ssm = cfg.get("ssm_cfg")
    return isinstance(ssm, dict) and ssm.get("layer") == "Mamba2"


def _mamba2_sizes(cfg: dict) -> dict:
    """D, L, V and tying from the top level; the SSD's sizes from ``ssm_cfg``."""
    s = {k: _need(cfg["ssm_cfg"], k, "ssm_cfg.") for k in _MAMBA2_KEYS}
    s.update(D=_need(cfg, "d_model"), L=_need(cfg, "n_layer"),
             V=_need(cfg, "vocab_size"), tied=_need(cfg, "tie_embeddings"))
    s["DI"] = s["expand"] * s["D"]
    s["H"] = s["DI"] // s["headdim"]
    return s


def _mamba2_params(cfg: dict) -> int:
    s = _mamba2_sizes(cfg)
    D, DI, H, W = s["D"], s["DI"], s["H"], s["d_conv"]
    GN = s["ngroups"] * s["d_state"]
    per = (D * (2 * DI + 2 * GN + H)    # in_proj: z, x, B, C, dt
           + (W + 1) * (DI + 2 * GN)    # depthwise conv weight and bias
           + 3 * H                      # A_log, D, dt_bias
           + DI + DI * D + D)           # gated norm, out_proj, pre-norm
    emb = s["V"] * D * (1 if s["tied"] else 2)
    return emb + s["L"] * per + D


def _mamba2_ssd_flops(cfg: dict, seq_len: int) -> float:
    s = _mamba2_sizes(cfg)
    Q = min(s["chunk_size"], seq_len)  # the program's chunk: the sequence, if shorter
    N, G, H, P = s["d_state"], s["ngroups"], s["H"], s["headdim"]
    return 3.0 * s["L"] * (2 * Q * N * G + 2 * H * Q * P + 4 * H * N * P)


def n_params(cfg: dict) -> int:
    """Parameters of a configuration file, embedding counted once if tied."""
    if _is_mamba2(cfg):
        return _mamba2_params(cfg)
    D, L, V = (_need(cfg, k) for k in ("hidden_size", "num_hidden_layers", "vocab_size"))
    emb = V * D * (1 if _need(cfg, "tie_word_embeddings") else 2)
    Hq, Hkv = _need(cfg, "num_attention_heads"), _need(cfg, "num_key_value_heads")
    Q = cfg.get("head_dim") or D // Hq
    per = D * Q * (Hq + 2 * Hkv) + Hq * Q * D + 3 * D * _need(cfg, "intermediate_size") + 2 * D
    if _need(cfg, "attention_bias"):
        per += Q * (Hq + 2 * Hkv)
    return emb + L * per + D


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    n = 6.0 * n_params(cfg)
    if _is_mamba2(cfg):
        return n + _mamba2_ssd_flops(cfg, seq_len)
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
