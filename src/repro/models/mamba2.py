"""Mamba2 — SSD (state-space duality) blocks, arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm: within a chunk of length
Q the computation is a (masked, decay-weighted) quadratic form — MXU
friendly; across chunks a small recurrent state (B, H, P, N) carries via a
sequential scan. Decode is the pure SSM recurrence (state update per
token). All SSD math runs in f32.

Sharding note (§Perf zamba2 hillclimb): the projections are stored as
*separate* matrices (w_z/w_x/w_B/w_C/w_dt) and the depthwise conv as three
per-segment kernels rather than one fused (D, 2*DI+2*N+H) block. A fused
layout mixes segments whose natural shard boundaries (DI, N, H) don't
align with the column shards, so GSPMD fell back to replicating the whole
block over the model axis — 16x redundant compute (useful-FLOPs ratio
0.061). Per-segment weights shard cleanly: DI and H divide the model axis
on zamba2 (d_inner 4096, 64 heads / 16).

Shapes: d_inner = expand * d_model; H = d_inner / head_dim (P = head_dim);
N = ssm_state; single B/C group shared across heads (ngroups=1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import gated_rmsnorm, rmsnorm
from repro.runtime.sharding import constrain


def mamba_init(key, cfg: ModelConfig, dtype) -> dict:
    D, DI, N, H, W = (
        cfg.d_model,
        cfg.ssm_d_inner,
        cfg.ssm_state,
        cfg.ssm_heads,
        cfg.ssm_conv,
    )
    ks = jax.random.split(key, 6)
    s = 1.0 / np.sqrt(D)
    return {
        "w_z": (jax.random.normal(ks[0], (D, DI)) * s).astype(dtype),
        "w_x": (jax.random.normal(ks[1], (D, DI)) * s).astype(dtype),
        "w_B": (jax.random.normal(ks[2], (D, N)) * s).astype(dtype),
        "w_C": (jax.random.normal(ks[3], (D, N)) * s).astype(dtype),
        "w_dt": (jax.random.normal(ks[4], (D, H)) * s).astype(dtype),
        "conv_x": (jax.random.normal(ks[5], (W, DI)) / np.sqrt(W)).astype(dtype),
        "conv_xb": jnp.zeros((DI,), dtype),
        "conv_B": (jax.random.normal(ks[5], (W, N)) / np.sqrt(W)).astype(dtype),
        "conv_Bb": jnp.zeros((N,), dtype),
        "conv_C": (jax.random.normal(ks[5], (W, N)) / np.sqrt(W)).astype(dtype),
        "conv_Cb": jnp.zeros((N,), dtype),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)
        ),  # A in [-16, -1]
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01, jnp.float32))),
        "norm_w": jnp.zeros((DI,), dtype),
        "w_out": (jax.random.normal(ks[2], (DI, D)) / np.sqrt(DI)).astype(dtype),
    }


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over time + silu: u (B, S, Ch), w (W, Ch)."""
    W = w.shape[0]
    out = u * w[-1]
    for i in range(1, W):
        shifted = jnp.pad(u, ((0, 0), (i, 0), (0, 0)))[:, : u.shape[1]]
        out = out + shifted * w[-1 - i]
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(u.dtype)


def _feature_model_axis(cfg: ModelConfig):
    """The model axis for feature dims — None when "model" carries batch."""
    return "model" if cfg.tensor_parallel else None


def _project(cfg: ModelConfig, p: dict, x_in: jax.Array):
    """x (B,S,D) -> z (B,S,DI), xr, B_, C_, dt — each shard-aligned."""
    ba = cfg.batch_axes
    fm = _feature_model_axis(cfg)
    z = constrain(x_in @ p["w_z"], (ba, None, fm))
    xr = constrain(x_in @ p["w_x"], (ba, None, fm))
    B_ = x_in @ p["w_B"]
    C_ = x_in @ p["w_C"]
    dt = constrain(x_in @ p["w_dt"], (ba, None, fm))
    return z, xr, B_, C_, dt


def ssd_forward(cfg: ModelConfig, p: dict, x_in: jax.Array):
    """Full-sequence SSD.

    x_in: (B, S, D) -> (y: (B, S, D), state {"h": (B,H,P,N), "conv":
    (B, W-1, Ch)}) — the state continues generation exactly where the
    sequence ended (asserted by tests/models/test_mamba2_ssd.py).
    """
    B, S, D = x_in.shape
    Q = min(cfg.ssm_chunk, S)
    if S % Q:
        raise ValueError(f"S={S} not divisible by ssm_chunk={Q}")
    W = cfg.ssm_conv

    z, xr, B_, C_, dt = _project(cfg, p, x_in)
    # conv state: the last W-1 *pre-conv* rows per segment (decode continues)
    conv_tail = jnp.concatenate(
        [
            jnp.pad(t, ((0, 0), (max(W - 1 - S, 0), 0), (0, 0)))[:, -(W - 1):]
            for t in (xr, B_, C_)
        ],
        axis=-1,
    ).astype(jnp.float32)
    xr = _causal_conv(xr, p["conv_x"], p["conv_xb"])
    B_ = _causal_conv(B_, p["conv_B"], p["conv_Bb"])
    C_ = _causal_conv(C_, p["conv_C"], p["conv_Cb"])

    # the scope names the SSD's device operations, forward and backward
    with jax.named_scope("ssd"):
        y, h_final = _ssd_core(cfg, p, xr, B_, C_, dt)
    y = y.reshape(B, S, cfg.ssm_d_inner).astype(x_in.dtype)
    y = constrain(y, (cfg.batch_axes, None, _feature_model_axis(cfg)))
    y = gated_rmsnorm(y, z, p["norm_w"], cfg.norm_eps)
    return y @ p["w_out"], {"h": h_final, "conv": conv_tail}


def _ssd_core(cfg: ModelConfig, p: dict, xr, B_, C_, dt):
    """The SSD on the conv outputs, in f32: (y (B, S, H, P), final state)."""
    B, S, _ = xr.shape
    N, H, P = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    Q = min(cfg.ssm_chunk, S)
    nc = S // Q
    # f32 SSD quantities; heads shard over model (H % model == 0 on zamba2)
    ba = cfg.batch_axes
    fm = _feature_model_axis(cfg)
    xh = xr.reshape(B, S, H, P).astype(jnp.float32)
    xh = constrain(xh, (ba, None, fm, None))
    Bf = B_.astype(jnp.float32)                      # (B, S, N)
    Cf = C_.astype(jnp.float32)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, S, H)
    dtf = constrain(dtf, (ba, None, fm))
    A = -jnp.exp(p["A_log"])                          # (H,) negative
    dA = dtf * A                                      # (B, S, H) log-decay

    # chunked views
    xc = xh.reshape(B, nc, Q, H, P)
    Bc = Bf.reshape(B, nc, Q, N)
    Cc = Cf.reshape(B, nc, Q, N)
    dAc = dA.reshape(B, nc, Q, H)
    dtc = dtf.reshape(B, nc, Q, H)

    seg = jnp.cumsum(dAc, axis=2)                     # (B, nc, Q, H)
    total = seg[:, :, -1]                             # (B, nc, H)

    # intra-chunk (quadratic, masked decay kernel)
    #   G[t, s] = (C_t . B_s) * exp(seg_t - seg_s) * dt_s   for s <= t
    # masked before the exponential: for s > t the exponent is positive and
    # overflows past a log-decay span of ~88, and inf times the zero
    # cotangent of a masked entry is NaN in the backward pass
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)        # (B, nc, Q, Q)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    gap = seg[:, :, :, None, :] - seg[:, :, None, :, :]
    decay = jnp.exp(jnp.where(mask, gap, -jnp.inf))   # (B,nc,Q,Q,H)
    G = CB[..., None] * decay * dtc[:, :, None, :, :]
    G = constrain(G, (ba, None, None, None, fm))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", G, xc)

    # chunk states: S_c = sum_t exp(total - seg_t) * dt_t * B_t x_t^T
    w_state = jnp.exp(total[:, :, None, :] - seg) * dtc        # (B, nc, Q, H)
    S_c = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", w_state, Bc, xc)

    # inter-chunk recurrence over nc (sequential, tiny state)
    def step(h, inputs):
        S_ci, total_i = inputs                        # (B,H,P,N), (B,H)
        h_new = h * jnp.exp(total_i)[:, :, None, None] + S_ci
        return h_new, h                                # emit state *before* chunk

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        step,
        h0,
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(total, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)             # (B, nc, H, P, N)

    # inter-chunk contribution: y_t += C_t . (exp(seg_t) * h_prev)
    y_inter = jnp.einsum(
        "bcqn,bcqh,bchpn->bcqhp", Cc, jnp.exp(seg), h_prevs
    )

    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y + xh * p["D"][None, None, :, None], h_final


def ssm_decode_step(cfg: ModelConfig, p: dict, state: dict, x_tok: jax.Array):
    """One-token recurrence. x_tok: (B, 1, D); state: {"h": (B,H,P,N),
    "conv": (B, W-1, Ch)} -> (y (B, 1, D), new state)."""
    B = x_tok.shape[0]
    DI, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    W = cfg.ssm_conv
    z, xr, B_, C_, dt = _project(cfg, p, x_tok)
    xBC = jnp.concatenate([xr, B_, C_], axis=-1)[:, 0]          # (B, Ch)

    conv_hist = state["conv"]                                    # (B, W-1, Ch)
    window = jnp.concatenate([conv_hist, xBC[:, None].astype(jnp.float32)], axis=1)
    conv_w = jnp.concatenate(
        [p["conv_x"], p["conv_B"], p["conv_C"]], axis=-1
    ).astype(jnp.float32)
    conv_b = jnp.concatenate(
        [p["conv_xb"], p["conv_Bb"], p["conv_Cb"]]
    ).astype(jnp.float32)
    conv_out = jnp.einsum("bwc,wc->bc", window, conv_w) + conv_b
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    xr = conv_out[:, :DI].reshape(B, H, P)
    Bf = conv_out[:, DI : DI + N]
    Cf = conv_out[:, DI + N :]
    dtf = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B, H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dtf * A)                                     # (B, H)

    h = state["h"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dtf, Bf, xr
    )
    y = jnp.einsum("bn,bhpn->bhp", Cf, h) + xr * p["D"][None, :, None]
    y = y.reshape(B, 1, DI).astype(x_tok.dtype)
    y = gated_rmsnorm(y, z, p["norm_w"], cfg.norm_eps)
    return y @ p["w_out"], {"h": h, "conv": new_conv}


def init_ssm_state(cfg: ModelConfig, batch: int) -> dict:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ch = cfg.ssm_d_inner + 2 * N
    return {
        "h": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, ch), jnp.float32),
    }


# ---------------------------------------------------------------------------
# naive O(S) recurrence oracle (tests only)
# ---------------------------------------------------------------------------

def ssd_reference(cfg: ModelConfig, p: dict, x_in: jax.Array) -> jax.Array:
    """Sequential recurrence — must match ssd_forward to f32 tolerance."""
    B, S, D = x_in.shape
    state = init_ssm_state(cfg, B)
    ys = []
    for t in range(S):
        y, state = ssm_decode_step(cfg, p, state, x_in[:, t : t + 1])
        ys.append(y)
    return jnp.concatenate(ys, axis=1)
