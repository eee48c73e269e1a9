"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

The shared transformer block (single parameter set) is applied after every
``attn_every``-th mamba layer — expressed as a ``lax.cond`` inside the
layer scan, so the HLO holds exactly one mamba block + one attention block
regardless of depth, and arbitrary (L, attn_every) combinations work.

Decode carries: per-layer SSM states (stacked L) + a KV cache per shared-
block *application* (n_apps = L // attn_every), indexed by an application
counter that only advances inside the cond's true branch.

The residual stream is carried in float32 (mamba_ssm's
``residual_in_fp32``); each norm's output, which feeds a mixer or the
head, is cast to the parameters' dtype.

Deviation noted in DESIGN §6: Zamba2 concatenates the block input with the
original embeddings before the shared block and applies per-invocation
LoRA deltas; we apply the shared block to the residual stream directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope,
    logits_from_embed,
    attention_init,
    decode_attention,
    dtype_of,
    embed_init,
    mlp_apply,
    mlp_init,
    multihead_attention,
    rmsnorm,
)
from repro.models.mamba2 import (
    init_ssm_state,
    mamba_init,
    ssd_forward,
    ssm_decode_step,
)
from repro.models.transformer import _qkv


def n_shared_apps(cfg: ModelConfig) -> int:
    if not cfg.attn_every:
        return 0
    return sum(1 for i in range(cfg.num_layers) if (i + 1) % cfg.attn_every == 0)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """attn_every == 0 gives the pure-SSM LM (mamba2 family)."""
    dtype = dtype_of(cfg.param_dtype)
    k_embed, k_blocks, k_shared_a, k_shared_m = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_blocks, cfg.num_layers)
    blocks = jax.vmap(
        lambda k: {
            "ln": jnp.zeros((cfg.d_model,), dtype),
            "ssm": mamba_init(k, cfg, dtype),
        }
    )(layer_keys)
    params = {
        "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": blocks,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.attn_every:
        params["shared"] = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": attention_init(k_shared_a, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": mlp_init(k_shared_m, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
        }
    return params


def _shared_block(cfg: ModelConfig, shared: dict, x: jax.Array, positions):
    from repro.runtime.sharding import constrain

    dtype = shared["attn"]["wo"].dtype
    h = rmsnorm(x, shared["ln1"], cfg.norm_eps).astype(dtype)
    q, k, v = _qkv(cfg, shared["attn"], h)
    # pin head sharding: propagation through the reshape chose replication
    fm = "model" if cfg.tensor_parallel else None
    q = constrain(q, (cfg.batch_axes, fm, None, None))
    k = constrain(k, (cfg.batch_axes, fm, None, None))
    v = constrain(v, (cfg.batch_axes, fm, None, None))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    a = multihead_attention(
        q, k, v, causal=True,
        chunked_threshold=cfg.attn_chunked_threshold,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
    )
    B, S = x.shape[0], x.shape[1]
    a = a.transpose(0, 2, 1, 3).reshape(B, S, cfg.q_dim) @ shared["attn"]["wo"]
    x = x + a
    h2 = rmsnorm(x, shared["ln2"], cfg.norm_eps).astype(dtype)
    return x + mlp_apply(shared["mlp"], h2, cfg.mlp_type)


def hidden_forward(cfg: ModelConfig, params: dict, tokens: jax.Array):
    """tokens (B, S) -> (hidden (B, S, D), aux=0).

    Structured as a python loop over shared-block applications with a
    lax.scan over the mamba span in between (static bounds) — NOT a
    lax.cond inside one scan: GSPMD's sharding propagation into
    conditional branches replicated the shared attention over the model
    axis (16x redundant compute, §Perf zamba2 hillclimb), and cost
    attribution through conditionals is max-branch (inexact). HLO size is
    one mamba block + n_apps attention blocks.
    """
    from repro.runtime.sharding import constrain

    B, S = tokens.shape
    dtype = params["embed"].dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    positions = jnp.arange(S)
    shared = params.get("shared")
    apps = _app_layers(cfg) if shared is not None else []

    def mamba_span(x, lo, hi):
        span = jax.tree.map(lambda p: p[lo:hi], params["blocks"])

        def body(x, block):
            x = constrain(x, (cfg.batch_axes, None, None))
            h = rmsnorm(x, block["ln"], cfg.norm_eps).astype(dtype)
            y, _ = ssd_forward(cfg, block["ssm"], h)
            return x + y, None

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, span)
        return x

    shared_fn = lambda xx: _shared_block(cfg, shared, xx, positions)
    if cfg.remat != "none" and shared is not None:
        shared_fn = jax.checkpoint(shared_fn)

    prev = 0
    for a in apps:
        x = mamba_span(x, prev, a + 1)
        x = shared_fn(x)
        prev = a + 1
    if prev < cfg.num_layers:
        x = mamba_span(x, prev, cfg.num_layers)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
    return x, jnp.zeros((), jnp.float32)


def lm_forward(cfg: ModelConfig, params: dict, tokens: jax.Array):
    """tokens (B, S) -> (logits (B, S, V) f32, aux=0)."""
    h, aux = hidden_forward(cfg, params, tokens)
    return logits_from_embed(params["embed"], h), aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _app_layers(cfg: ModelConfig) -> list[int]:
    """Layer indices after which the shared block applies."""
    if not cfg.attn_every:
        return []
    return [i for i in range(cfg.num_layers) if (i + 1) % cfg.attn_every == 0]


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, cache_len: int):
    """Run the prompt, building SSM states + shared-attn KV caches.

    Structured as a python loop over shared-block *applications* with a
    lax.scan over the mamba layers in between (static group bounds), so the
    per-application KV cache is produced only where the block actually runs.
    Returns (last-token logits (B, 1, V), cache).
    """
    B, S = tokens.shape
    dtype = params["embed"].dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    positions = jnp.arange(S)
    shared = params.get("shared")
    apps = _app_layers(cfg)

    def mamba_span(x, lo, hi):
        span = jax.tree.map(lambda p: p[lo:hi], params["blocks"])

        def body(x, block):
            h = rmsnorm(x, block["ln"], cfg.norm_eps).astype(dtype)
            y, st = ssd_forward(cfg, block["ssm"], h)
            return x + y, st

        return jax.lax.scan(body, x, span)

    k_caches, v_caches, ssm_states = [], [], []
    prev = 0
    for a in apps:
        x, st = mamba_span(x, prev, a + 1)
        ssm_states.append(st)
        h = rmsnorm(x, shared["ln1"], cfg.norm_eps).astype(dtype)
        q, k, v = _qkv(cfg, shared["attn"], h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        att = multihead_attention(
            q, k, v, causal=True,
            chunked_threshold=cfg.attn_chunked_threshold,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        )
        att = att.transpose(0, 2, 1, 3).reshape(B, S, cfg.q_dim) @ shared["attn"]["wo"]
        x = x + att
        h2 = rmsnorm(x, shared["ln2"], cfg.norm_eps).astype(dtype)
        x = x + mlp_apply(shared["mlp"], h2, cfg.mlp_type)
        pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0)]
        k_caches.append(jnp.pad(k.astype(dtype), pad))
        v_caches.append(jnp.pad(v.astype(dtype), pad))
        prev = a + 1
    if prev < cfg.num_layers:
        x, st = mamba_span(x, prev, cfg.num_layers)
        ssm_states.append(st)

    ssm = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *ssm_states)
    x = rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps).astype(dtype)
    logits = logits_from_embed(params["embed"], x)
    cache = {"ssm": ssm, "pos": jnp.asarray(S, jnp.int32)}
    if apps:
        cache["k"] = jnp.stack(k_caches)
        cache["v"] = jnp.stack(v_caches)
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None) -> dict:
    dtype = dtype or dtype_of(cfg.param_dtype)
    L = cfg.num_layers
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ch = cfg.ssm_d_inner + 2 * N
    cache = {
        "ssm": {
            "h": jnp.zeros((L, batch, H, P, N), jnp.float32),
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, ch), jnp.float32),
        },
        "pos": jnp.asarray(0, jnp.int32),
    }
    apps = n_shared_apps(cfg)
    if apps:
        kv_shape = (apps, batch, cfg.num_kv_heads, cache_len, cfg.head_dim)
        cache["k"] = jnp.zeros(kv_shape, dtype)
        cache["v"] = jnp.zeros(kv_shape, dtype)
    return cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict, tokens: jax.Array):
    """tokens (B,) -> (logits (B, V) f32, new cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    dtype = params["embed"].dtype
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :].astype(jnp.float32)  # (B, 1, D)
    positions = pos[None]
    shared = params.get("shared")
    every = cfg.attn_every

    has_attn = shared is not None and n_shared_apps(cfg) > 0
    kc = cache.get("k", jnp.zeros((1, B, 1, 1, 1), dtype))
    vc = cache.get("v", jnp.zeros((1, B, 1, 1, 1), dtype))

    def shared_branch(args):
        x, app_idx, kc, vc = args
        h = rmsnorm(x, shared["ln1"], cfg.norm_eps).astype(dtype)
        q, k, v = _qkv(cfg, shared["attn"], h)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        k_app = jax.lax.dynamic_index_in_dim(kc, app_idx, 0, keepdims=False)
        v_app = jax.lax.dynamic_index_in_dim(vc, app_idx, 0, keepdims=False)
        k_app = jax.lax.dynamic_update_slice(k_app, k.astype(k_app.dtype), (0, 0, pos, 0))
        v_app = jax.lax.dynamic_update_slice(v_app, v.astype(v_app.dtype), (0, 0, pos, 0))
        kc = jax.lax.dynamic_update_index_in_dim(kc, k_app, app_idx, 0)
        vc = jax.lax.dynamic_update_index_in_dim(vc, v_app, app_idx, 0)
        a = decode_attention(q, k_app, v_app, pos)
        a = a.transpose(0, 2, 1, 3).reshape(B, 1, cfg.q_dim) @ shared["attn"]["wo"]
        x = x + a
        h2 = rmsnorm(x, shared["ln2"], cfg.norm_eps).astype(dtype)
        x = x + mlp_apply(shared["mlp"], h2, cfg.mlp_type)
        return x, app_idx + 1, kc, vc

    def body(carry, scanned):
        x, app_idx, kc, vc = carry
        block, ssm_state, idx = scanned
        h = rmsnorm(x, block["ln"], cfg.norm_eps).astype(dtype)
        y, ssm_new = ssm_decode_step(cfg, block["ssm"], ssm_state, h)
        x = x + y
        if has_attn:
            x, app_idx, kc, vc = jax.lax.cond(
                (idx + 1) % every == 0,
                shared_branch,
                lambda args: args,
                (x, app_idx, kc, vc),
            )
        return (x, app_idx, kc, vc), ssm_new

    (x, _, kc, vc), ssm_states = jax.lax.scan(
        body,
        (x, jnp.asarray(0, jnp.int32), kc, vc),
        (params["blocks"], cache["ssm"], jnp.arange(cfg.num_layers)),
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps).astype(dtype)
    logits = logits_from_embed(params["embed"], x)[:, 0]
    new_cache = {"ssm": ssm_states, "pos": pos + 1}
    if has_attn:
        new_cache["k"], new_cache["v"] = kc, vc
    return logits, new_cache
