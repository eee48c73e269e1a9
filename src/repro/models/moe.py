"""Mixture-of-Experts MLP with group-local capacity dispatch.

TPU/GSPMD-native formulation, two design points visible in EXPERIMENTS.md
§Perf (arctic-480b hillclimb):

  v1 (baseline, kept for reference in git history): one global scatter into
  (E, C, D). GSPMD cannot keep a scatter local when the operand is sharded
  over `model` and tokens over `data` — every layer moved the full
  (E, C, D) dispatch buffer over ICI (~750 s/step of collectives at 480B).

  v2 (this file): tokens are grouped along the data axis; each group routes
  and scatters *locally* into expert_in (G, E, Cg, D) sharded
  (data, model, -, -). The expert FFN einsum contracts locally; the
  combine gathers only the device-local expert slice and partial-sums over
  `model` (one (T, D)-sized all-reduce per layer — the unavoidable MoE
  combine volume).

Capacity semantics are per-group (Switch-style): Cg = Tg*k/E * factor;
overflow drops. The router aux (load-balance + z) is computed globally.

Arctic's "dense residual": a small dense FFN runs in parallel with the MoE
branch and the two outputs add.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import mlp_apply, mlp_init
from repro.runtime.sharding import constrain


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    E, D, F = cfg.moe_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    p = {
        "router": (jax.random.normal(ks[0], (D, E)) * s_in).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (E, D, F)) * s_in).astype(dtype),
        "wo": (jax.random.normal(ks[2], (E, F, D)) * s_out).astype(dtype),
    }
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["wg"] = (jax.random.normal(ks[3], (E, D, F)) * s_in).astype(dtype)
    if cfg.moe_dense_ff:
        p["dense"] = mlp_init(ks[4], D, cfg.moe_dense_ff, cfg.mlp_type, dtype)
    return p


def _mesh_info():
    """The mesh entered with ``jax.set_mesh``, if any."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def moe_apply(cfg: ModelConfig, params: dict, x: jax.Array):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar f32).

    Dispatch selection: explicit expert-parallel shard_map when the mesh
    has a model axis and shapes divide (the production path — see §Perf:
    GSPMD's scatter/gather partitioning moved ~2.2e15 collective bytes per
    step on arctic; the explicit all_to_all formulation moves the
    information-theoretic minimum); otherwise the GSPMD group-local
    formulation below (single-device tests, ragged decode batches).
    """
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    mesh = _mesh_info()
    if mesh is not None and "model" in mesh.axis_names:
        M = mesh.shape["model"]
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_dp = int(np.prod([mesh.shape[a] for a in dp], dtype=np.int64))
        if M > 1 and E % M == 0 and T % (n_dp * M) == 0:
            return _moe_expert_parallel(cfg, params, x, mesh, dp, M)
    return _moe_gspmd(cfg, params, x)


def _moe_gspmd(cfg: ModelConfig, params: dict, x: jax.Array):
    """GSPMD group-local formulation (fallback path)."""
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    G = math.gcd(cfg.moe_groups, T)
    Tg = T // G
    xt = x.reshape(G, Tg, D)
    xt = constrain(xt, ("data", None, None))

    logits = xt.astype(jnp.float32) @ params["router"]          # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, K)                          # (G, Tg, K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # aux losses: Switch load-balance + router z-loss (global statistics)
    f = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T * K)
    p_mean = probs.reshape(-1, E).mean(axis=0)
    balance = E * jnp.sum(f * p_mean)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux = balance + cfg.router_z_weight * z

    # group-local slot bookkeeping ((token, k) pairs, token-major)
    ids_flat = ids.reshape(G, Tg * K)                            # (G, TgK)
    gate_flat = gate.reshape(G, Tg * K)
    token_idx = jnp.arange(Tg * K, dtype=jnp.int32) // K         # (TgK,)
    onehot = jax.nn.one_hot(ids_flat, E, dtype=jnp.int32)        # (G, TgK, E)
    onehot = constrain(onehot, ("data", None, None))
    pos_all = jnp.cumsum(onehot, axis=1) - onehot                # pos before self
    pos = jnp.take_along_axis(pos_all, ids_flat[..., None], axis=2)[..., 0]
    Cg = int(np.ceil(Tg * K / E * cfg.moe_capacity_factor))
    keep = (pos < Cg).astype(x.dtype)                            # (G, TgK)
    pos_c = jnp.minimum(pos, Cg - 1)

    x_slot = jnp.take(xt, token_idx, axis=1)                     # (G, TgK, D)
    g_idx = jnp.broadcast_to(
        jnp.arange(G, dtype=jnp.int32)[:, None], (G, Tg * K)
    )
    expert_in = jnp.zeros((G, E, Cg, D), x.dtype).at[g_idx, ids_flat, pos_c].add(
        x_slot * keep[..., None]
    )
    expert_in = constrain(expert_in, ("data", "model", None, None))

    if "wg" in params:
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("gecd,edf->gecf", expert_in, params["wg"])) * jnp.einsum(
            "gecd,edf->gecf", expert_in, params["wi"]
        )
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", expert_in, params["wi"]))
    y = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    y = constrain(y, ("data", "model", None, None))

    # combine: gather each slot's expert output (partial over the model-
    # sharded E dim -> one (G, Tg, D) all-reduce, the MoE combine volume)
    y_slot = y[g_idx, ids_flat, pos_c]                           # (G, TgK, D)
    y_slot = y_slot * (gate_flat.astype(x.dtype) * keep)[..., None]
    out = jnp.zeros((G, Tg, D), x.dtype).at[g_idx, token_idx[None, :]].add(y_slot)
    out = constrain(out, ("data", None, None))
    out = out.reshape(B, S, D)

    if "dense" in params:
        out = out + mlp_apply(params["dense"], x, cfg.mlp_type)
    return out, aux


def _moe_expert_parallel(cfg: ModelConfig, params: dict, x: jax.Array,
                         mesh, dp: tuple, M: int):
    """Explicit EP: shard_map with all_to_all over the model axis.

    Per device: route the local token slice, pack per-(peer, local-expert)
    capacity buffers, all_to_all over "model", run the local experts
    (weights ZeRO-gathered over the data axes inside — transpose is a
    reduce-scatter, so grads shard back automatically), all_to_all the
    outputs home, combine locally. Collective volume per layer:
    2 x T*k*cf*D (the dispatch round-trips) + the weight gathers — the
    information-theoretic MoE minimum, vs GSPMD's emergent all-gathers of
    the full (E, C, D) buffer (~30x more on arctic-480b).

    Capacity is per (source device, expert): C_loc = T_loc*k/E * factor.
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    E_loc = E // M
    n_dp = int(np.prod([mesh.shape[a] for a in dp], dtype=np.int64))
    T_loc = T // (n_dp * M)
    C_loc = max(1, int(np.ceil(T_loc * K / E * cfg.moe_capacity_factor)))
    dp_group = dp if len(dp) > 1 else dp[0]
    gated = "wg" in params

    xt = x.reshape(T, D)

    # aux losses from a replicated router pass (cheap; identical decisions)
    logits_g = xt.astype(jnp.float32) @ params["router"]
    probs_g = jax.nn.softmax(logits_g, axis=-1)
    _, ids_g = jax.lax.top_k(probs_g, K)
    f = jnp.zeros((E,), jnp.float32).at[ids_g.reshape(-1)].add(1.0) / (T * K)
    balance = E * jnp.sum(f * probs_g.mean(axis=0))
    z = jnp.mean(jax.scipy.special.logsumexp(logits_g, axis=-1) ** 2)
    aux = balance + cfg.router_z_weight * z

    def local_fn(x_loc, router, wi_s, wg_s, wo_s):
        # x_loc: (T_loc, D); w*_s: (E_loc, D or F slice, ...) fsdp-sharded
        wi = jax.lax.all_gather(wi_s, dp_group, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo_s, dp_group, axis=1, tiled=True)
        wg = (jax.lax.all_gather(wg_s, dp_group, axis=1, tiled=True)
              if gated else None)

        probs = jax.nn.softmax(x_loc.astype(jnp.float32) @ router, axis=-1)
        gate, ids = jax.lax.top_k(probs, K)            # (T_loc, K)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
        s = T_loc * K
        ids_f = ids.reshape(s)
        gate_f = gate.reshape(s)
        token_idx = jnp.arange(s, dtype=jnp.int32) // K
        peer = ids_f // E_loc                           # destination model rank
        exp = ids_f % E_loc                             # expert on that rank
        onehot = jax.nn.one_hot(ids_f, E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(s), ids_f]
        keep = (pos < C_loc).astype(x_loc.dtype)
        pos_c = jnp.minimum(pos, C_loc - 1)

        send = jnp.zeros((M, E_loc, C_loc, D), x_loc.dtype).at[
            peer, exp, pos_c
        ].add(x_loc[token_idx] * keep[:, None])
        recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=True)
        # recv[i]: what peer i sent to my experts (tiled a2a keeps the shape)

        h_in = jnp.einsum("mecd,edf->mecf", recv, wi)
        if gated:
            act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
            h = act(jnp.einsum("mecd,edf->mecf", recv, wg)) * h_in
        else:
            h = jax.nn.gelu(h_in)
        y = jnp.einsum("mecf,efd->mecd", h, wo)

        back = jax.lax.all_to_all(y, "model", 0, 0, tiled=True)
        y_slot = back[peer, exp, pos_c] * (gate_f.astype(x_loc.dtype) * keep)[:, None]
        return jnp.zeros((T_loc, D), x_loc.dtype).at[token_idx].add(y_slot)

    tok_axes = dp + ("model",)
    out_flat = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(tok_axes, None),                  # tokens over all axes
            P(None, None),                      # router replicated
            P("model", dp_group, None),         # experts over model, fsdp data
            P("model", dp_group, None) if gated else P(None),
            P("model", dp_group, None),
        ),
        out_specs=P(tok_axes, None),
        check_vma=False,
    )(
        xt,
        params["router"],
        params["wi"],
        params["wg"] if gated else jnp.zeros((1,), x.dtype),
        params["wo"],
    )
    out = out_flat.reshape(B, S, D)
    if "dense" in params:
        out = out + mlp_apply(params["dense"], x, cfg.mlp_type)
    return out, aux
