"""Plain float32 reference of mamba2-130m as the benchmark runs it.

Imports nothing of the program. It states the parameter layout that the
program trains, makes the initial weights from a key, and gives the
training loss in straightforward ``jax.numpy`` at float32 and
``precision="highest"``, one row of the batch at a time:

    embed -> 24 x [x + Mamba2(RMSNorm(x))] -> RMSNorm
          -> logits against the tied embedding -> cross-entropy + z-loss

Mamba2 (arXiv:2405.21060) on a row h (T, D): the projections z, x, B, C
and dt; a depthwise causal convolution of width 4 with silu over x, B and
C; dt = softplus(dt + dt_bias) and A = -exp(A_log) per head; then the SSD
in its quadratic dual form, not the chunked one,

    y = (L o C B^T)(dt x) + D x,   L[t, s] = exp(sum_{r=s+1..t} dt_r A),

with L's upper triangle masked to -inf before the exponential; then the
gated norm RMSNorm(y * silu(z)) and the out projection. One group of B and
C is shared by the 24 heads of size 64. Norm weights follow the ``(1 + w)``
convention, so zeros are the identity.

``dot`` is every matrix product of the model, the SSD's two included: the
float32 one by default, or the control's lower-precision one (``lowp_dot``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    ssm = cfg["ssm_cfg"]
    D = cfg["d_model"]
    DI = ssm["expand"] * D
    return {
        "D": D, "DI": DI, "L": cfg["n_layer"], "V": cfg["vocab_size"],
        "N": ssm["ngroups"] * ssm["d_state"], "W": ssm["d_conv"],
        "H": DI // ssm["headdim"], "P": ssm["headdim"],
        "eps": cfg["norm_epsilon"], "z_loss": cfg["z_loss"],
        "dtype": jnp.dtype(cfg["param_dtype"]),
    }


def param_shapes(cfg: dict) -> dict:
    """The parameter tree the program trains: nested {name: (shape, dtype)}."""
    s = sizes(cfg)
    D, DI, L, N, W, H, p = s["D"], s["DI"], s["L"], s["N"], s["W"], s["H"], s["dtype"]
    f32 = np.dtype(np.float32)
    return {
        "embed": ((s["V"], D), p),
        "final_norm": ((D,), p),
        "blocks": {
            "ln": ((L, D), p),
            "ssm": {
                "w_z": ((L, D, DI), p), "w_x": ((L, D, DI), p),
                "w_B": ((L, D, N), p), "w_C": ((L, D, N), p), "w_dt": ((L, D, H), p),
                "conv_x": ((L, W, DI), p), "conv_xb": ((L, DI), p),
                "conv_B": ((L, W, N), p), "conv_Bb": ((L, N), p),
                "conv_C": ((L, W, N), p), "conv_Cb": ((L, N), p),
                "A_log": ((L, H), f32), "D": ((L, H), f32), "dt_bias": ((L, H), f32),
                "norm_w": ((L, DI), p), "w_out": ((L, DI, D), p),
            },
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], np.dtype)


def init_params(key: jax.Array, cfg: dict) -> dict:
    """Seeded weights in the stored dtype (jit this: one call on device)."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, (shape, dtype)) in zip(keys, flat):
        name = path[-1].key
        if name == "embed":
            v = jax.random.normal(k, shape, jnp.float32) * 0.02
        elif name.startswith("w_") or name in ("conv_x", "conv_B", "conv_C"):
            # (L, fan-in, fan-out): projections over D or DI, conv kernels over W
            v = jax.random.normal(k, shape, jnp.float32) * (shape[-2] ** -0.5)
        elif name == "A_log":  # A uniform in [1, 16]
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":  # dt log-uniform in [1e-3, 0.1]; softplus^-1(dt)
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            np.log(1e-3), np.log(0.1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "D":
            v = jnp.ones(shape, jnp.float32)
        else:  # norm weights and conv biases
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


def _conv(u, w, b):
    """Depthwise causal convolution over time, then silu: u (T, C), w (W, C)."""
    W, T = w.shape[0], u.shape[0]
    padded = jnp.pad(u, ((W - 1, 0), (0, 0)))
    out = sum(padded[i:i + T] * w[i].astype(jnp.float32) for i in range(W))
    return jax.nn.silu(out + b.astype(jnp.float32))


def _mamba2(m, h, s, dot):
    T, H, P = h.shape[0], s["H"], s["P"]
    z = dot("td,de->te", h, m["w_z"])
    x = _conv(dot("td,de->te", h, m["w_x"]), m["conv_x"], m["conv_xb"])
    B = _conv(dot("td,dn->tn", h, m["w_B"]), m["conv_B"], m["conv_Bb"])
    C = _conv(dot("td,dn->tn", h, m["w_C"]), m["conv_C"], m["conv_Cb"])
    dt = jax.nn.softplus(dot("td,dh->th", h, m["w_dt"]) + m["dt_bias"])  # (T, H)
    cum = jnp.cumsum(dt * -jnp.exp(m["A_log"]), axis=0)                # (T, H)
    causal = jnp.tril(jnp.ones((T, T), bool))[:, :, None]
    L = jnp.exp(jnp.where(causal, cum[:, None, :] - cum[None, :, :], -jnp.inf))
    xh = x.reshape(T, H, P)
    y = dot("tsh,shp->thp", L * dot("tn,sn->ts", C, B)[:, :, None], dt[:, :, None] * xh)
    y = (y + m["D"][None, :, None] * xh).reshape(T, H * P)
    y = _rmsnorm(y * jax.nn.silu(z), m["norm_w"], s["eps"])
    return dot("te,ed->td", y, m["w_out"])


def row_loss(params, tokens, targets, cfg, dot=f32_dot):
    """Summed (cross-entropy, z) over one row of tokens (T,)."""
    s = sizes(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(x, blk):
        return x + _mamba2(blk["ssm"], _rmsnorm(x, blk["ln"], s["eps"]), s, dot), None

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
    t.update(d_model=64, n_layer=2, vocab_size=256, batch=4, seq_len=64)
    t["ssm_cfg"].update(d_state=16, headdim=16, chunk_size=32)
    t["program"]["fields"].update(d_model=64, num_layers=2, vocab_size=256,
                                  ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
    t["checkpoint"]["chunk_bytes"] = 4096
    return t
