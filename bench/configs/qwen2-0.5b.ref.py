"""Plain float32 reference of qwen2-0.5b as the benchmark runs it.

Imports nothing of the program. It states the parameter layout that the
program trains, makes the initial weights from a key, and gives the
training loss in straightforward ``jax.numpy`` at float32 and
``precision="highest"``, one row of the batch at a time:

    embed -> 24 x [x + Attn(RMSNorm(x)); x + SwiGLU(RMSNorm(x))] -> RMSNorm
          -> logits against the tied embedding -> cross-entropy + z-loss

Attention is causal grouped-query attention (14 query heads over 2 key and
value heads, head size 64) with biases on q, k and v and rotary position
embeddings (half-split, theta 1e6). Norm weights follow the ``(1 + w)``
convention, so zeros are the identity.

``dot`` is every matrix product of the model: the float32 one by default,
or the control's lower-precision one (``lowp_dot``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    return {
        "D": cfg["hidden_size"], "F": cfg["intermediate_size"],
        "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
        "Hq": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg["head_dim"], "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"], "z_loss": cfg["z_loss"],
        "dtype": jnp.dtype(cfg["param_dtype"]),
    }


def param_shapes(cfg: dict) -> dict:
    """The parameter tree the program trains: nested {name: (shape, dtype)}."""
    s = sizes(cfg)
    D, F, L, V = s["D"], s["F"], s["L"], s["V"]
    Q, KV, p = s["Hq"] * s["Dh"], s["Hkv"] * s["Dh"], s["dtype"]
    return {
        "embed": ((V, D), p),
        "final_norm": ((D,), p),
        "blocks": {
            "ln1": ((L, D), p), "ln2": ((L, D), p),
            "attn": {"wq": ((L, D, Q), p), "wk": ((L, D, KV), p),
                     "wv": ((L, D, KV), p), "wo": ((L, Q, D), p),
                     "bq": ((L, Q), p), "bk": ((L, KV), p), "bv": ((L, KV), p)},
            "mlp": {"wi": ((L, D, F), p), "wg": ((L, D, F), p), "wo": ((L, F, D), p)},
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], np.dtype)


def init_params(key: jax.Array, cfg: dict) -> dict:
    """Seeded weights in the stored dtype (jit this: one call on device)."""
    s = sizes(cfg)
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(flat))
    fan_in = {"wq": s["D"], "wk": s["D"], "wv": s["D"], "wi": s["D"], "wg": s["D"],
              "wo": None}
    out = []
    for k, (path, (shape, dtype)) in zip(keys, flat):
        name = path[-1].key
        if name == "embed":
            v = jax.random.normal(k, shape, jnp.float32) * 0.02
        elif name in fan_in:  # wo: attention's (L, Q, D) or the MLP's (L, F, D)
            v = jax.random.normal(k, shape, jnp.float32) * (shape[-2] ** -0.5)
        else:  # norm weights and biases
            v = jnp.zeros(shape, jnp.float32)
        out.append(v.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# -- matrix products --------------------------------------------------------

def f32_dot(eq: str, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HI, preferred_element_type=jnp.float32)


def _round(x, dtype, top: float):
    """Round through a float8 type with one scale per tensor (amax to top)."""
    x = x.astype(jnp.float32)
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8(x):
    """A product's operand rounded through e4m3; its gradient passes as is."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_grad(y):
    """The identity; the gradient arriving at a product is rounded through
    e5m2, so the backward pass's products take float8 operands too."""
    return y


_fp8_grad.defvjp(lambda y: (y, None), lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def lowp_dot(eq: str, a, b):
    """The control: every product computed in float8 as float8 training does
    it, forward operands in e4m3 and the gradients they meet in e5m2, each
    with one scale per tensor, accumulated in float32."""
    return _fp8_grad(f32_dot(eq, _fp8(a), _fp8(b)))


# -- the model ----------------------------------------------------------------

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x (T, H, Dh): rotate the two halves of each head by position."""
    T, _, Dh = x.shape
    freqs = 1.0 / theta ** (np.arange(0, Dh // 2, dtype=np.float32) * 2 / Dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, h, s, dot):
    T = h.shape[0]
    Hq, Hkv, Dh = s["Hq"], s["Hkv"], s["Dh"]
    q = (dot("td,de->te", h, a["wq"]) + a["bq"]).reshape(T, Hkv, Hq // Hkv, Dh)
    k = (dot("td,de->te", h, a["wk"]) + a["bk"]).reshape(T, Hkv, Dh)
    v = (dot("td,de->te", h, a["wv"]) + a["bv"]).reshape(T, Hkv, Dh)
    q = _rope(q.reshape(T, Hq, Dh), s["theta"]).reshape(T, Hkv, Hq // Hkv, Dh)
    k = _rope(k, s["theta"])
    scores = dot("tkgd,ukd->kgtu", q, k) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = dot("kgtu,ukd->tkgd", p, v).reshape(T, Hq * Dh)
    return dot("te,ed->td", o, a["wo"])


def _mlp(m, h, dot):
    g = jax.nn.silu(dot("td,df->tf", h, m["wg"])) * dot("td,df->tf", h, m["wi"])
    return dot("tf,fd->td", g, m["wo"])


def row_loss(params, tokens, targets, cfg, dot=f32_dot):
    """Summed (cross-entropy, z) over one row of tokens (T,)."""
    s = sizes(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(x, blk):
        x = x + _attention(blk["attn"], _rmsnorm(x, blk["ln1"], s["eps"]), s, dot)
        return x + _mlp(blk["mlp"], _rmsnorm(x, blk["ln2"], s["eps"]), dot), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = _rmsnorm(x, params["final_norm"], s["eps"])
    logits = dot("td,vd->tv", h, params["embed"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold), jnp.sum(lse * lse)


def loss_and_grads(params, batch, cfg, dot=f32_dot):
    """Mean loss over the batch and its float32 gradients, row by row."""
    inputs, targets = batch["inputs"], batch["targets"]
    n = inputs.size
    zw = sizes(cfg)["z_loss"]

    def one(params, row):
        ce, z = row_loss(params, row[0], row[1], cfg, dot)
        return (ce + zw * z) / n

    grad_fn = jax.value_and_grad(one)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)

    def body(acc, row):
        l, g = grad_fn(params, row)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                    (inputs, targets))
    return loss, grads


def small(cfg: dict) -> dict:
    """A small copy of the configuration, for tests off the chip."""
    import copy

    t = copy.deepcopy(cfg)
    t.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256, batch=4, seq_len=32)
    t["program"]["fields"].update(d_model=64, d_ff=128, num_layers=2, num_heads=4,
                                  num_kv_heads=2, head_dim=16, vocab_size=256)
    t["checkpoint"]["chunk_bytes"] = 4096
    return t
