"""The training reference and the numbers that decide ``correct``.

A configuration's reference module (``configs/<name>.ref.py``) gives the
model's loss and float32 gradients; this module drives it through the
optimizer the configuration states, in the stored dtypes the configuration
states, and compares its readings with the program's.

Three readings are compared, each against the reference:

- ``loss_gap``: the widest relative gap of a step's loss;
- ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer gets it (clipped), over the larger of that
  leaf's reference norm and the median leaf's;
- ``change_gap``: the worst leaf's gap for the parameters' change over the
  first three steps.

A leaf is one tensor of the published checkpoint: each layer's slice of a
layer-stacked leaf counts alone. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of the leaf readings.
"""
from __future__ import annotations

import importlib.util
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
CHECK_STEPS = 3
EXCLUDE_BELOW = 1e-3  # of the median leaf's reference gradient norm


def load_config(name: str) -> tuple[dict, object]:
    """The configuration's sizes and its reference module, by name."""
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    path = HERE / "configs" / f"{name}.ref.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return cfg, mod


def load_limits(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.limits.json").read_text())


def base_key(seed: int) -> jax.Array:
    """A key for any whole number, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def batch_at(key: jax.Array, step: int, batch: int, seq: int, vocab: int) -> dict:
    """The batch of one step: uniform tokens, every row its own."""
    toks = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


def leaf_norms(tree) -> jax.Array:
    """Float32 norms of the model's tensors as the published checkpoint
    holds them, in tree order: a leaf under ``blocks`` stacks one tensor per
    layer on its first axis and gives one norm per layer."""
    out = []
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        sq = jnp.square(x.astype(jnp.float32))
        if any(getattr(k, "key", None) == "blocks" for k in path):
            out.append(jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(sq)).reshape(1))
    return jnp.concatenate(out)


def adamw_step(params, opt, grads, t, o: dict):
    """One AdamW step as the configuration states it; float32 math,
    parameters stored back in their own dtype. Returns (params, opt,
    clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["max_grad_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    bc1, bc2 = 1.0 - o["b1"] ** t, 1.0 - o["b2"] ** t

    def upd(p, g, m, v):
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        u = u + o["weight_decay"] * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - o["lr"] * u).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, opt["m"], opt["v"])
    pick = lambda i: jax.tree.map(lambda x: x[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"m": pick(1), "v": pick(2)}, grads


def reference_readings(cfg: dict, ref, seed: int, *, dot=None,
                       half_batch: bool = False) -> dict:
    """The reference's three steps from the seed: losses, per-leaf norms of
    the first clipped gradient, per-leaf norms of the change after three.

    ``dot`` replaces the float32 products (the control); ``half_batch``
    plants the fault that drops half of every batch and takes the mean
    over the rest.
    """
    dot = dot or ref.f32_dot
    key = base_key(seed)
    init = jax.jit(lambda k: ref.init_params(k, cfg))
    params = init(jax.random.fold_in(key, 0))
    opt = {k: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
           for k in ("m", "v")}
    rows = cfg["batch"] // 2 if half_batch else cfg["batch"]

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, batch, t):
        loss, grads = ref.loss_and_grads(params, batch, cfg, dot)
        params, opt, clipped = adamw_step(params, opt, grads, t, cfg["optimizer"])
        return params, opt, loss, leaf_norms(clipped)

    losses, grad_norms = [], None
    for t in range(1, CHECK_STEPS + 1):
        b = batch_at(jax.random.fold_in(key, 1), t, cfg["batch"], cfg["seq_len"],
                     cfg["vocab_size"])
        b = jax.tree.map(lambda x: x[:rows], b)
        params, opt, loss, gn = step(params, opt, b, jnp.float32(t))
        losses.append(float(loss))
        if t == 1:
            grad_norms = np.asarray(gn).tolist()
        del b
    del opt
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": np.asarray(change(params, init(jax.random.fold_in(key, 0)))).tolist()}


def compare(prog: dict, ref: dict) -> dict:
    """The three gaps of a program's readings against the reference's."""
    rl = np.asarray(ref["losses"])
    pl = np.full(len(rl), np.nan)  # a step the program never ran reads NaN
    pl[: len(prog["losses"][: len(rl)])] = prog["losses"][: len(rl)]
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    if len(prog["losses"]) > len(rl):  # later readings of the last step
        extra = np.asarray(prog["losses"][len(rl):])
        loss_gap = max(loss_gap, float(np.max(np.abs(extra - rl[-1]) / abs(rl[-1]))))
    rg = np.asarray(ref["grad_norms"])
    keep = rg >= EXCLUDE_BELOW * np.median(rg)

    def gaps(p, r):
        p, r = np.asarray(p)[keep], np.asarray(r)[keep]
        return np.abs(p - r) / np.maximum(r, np.median(r))

    return {
        "loss_gap": loss_gap,
        "grad_gap": float(np.max(gaps(prog["grad_norms"], rg))),
        "change_gap": float(np.max(gaps(prog["change_norms"], ref["change_norms"]))),
        "leaves_left_out": int(np.sum(~keep)),
    }
