"""Step builders: jitted train / prefill / decode functions with shardings.

``make_train_step`` assembles loss -> grad -> (optional microbatch
accumulation) -> optimizer into one jitted function with explicit
in/out shardings from the ShardingRules. Gradient accumulation runs as a
``lax.scan`` over microbatch slices with f32 accumulators; the per-
microbatch reduce-scatter of grads overlaps the next microbatch's compute
under XLA's latency-hiding scheduler (§Perf lever).

Each step is traced under its own mesh's axis names, so the activation
constraints inside the model hold wherever the caller calls it from: a
caller needs no mesh context, and one-device work around the step (chunk
digests of single shards, restore) runs outside any.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.zoo import Model
from repro.optim import Optimizer, global_norm
from repro.runtime.sharding import ShardingRules, fit_spec
from repro.utils.tree import map_with_paths


def make_train_state_specs(model: Model, rules: ShardingRules, optimizer: Optimizer):
    """Abstract shapes + PartitionSpecs for {"params", "opt", "step"}."""
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    opt_shape = jax.eval_shape(lambda: optimizer.init(params_shape))
    p_spec = rules.params_specs(params_shape)

    def spec_for_opt(path: str, leaf) -> P:
        # moments mirror the param sharding: strip the m/v/f prefix and any
        # quantization/factoring suffix, then apply the param rule; leaves
        # whose rank changed (q8 blocks, factored rows/cols) fall back to
        # replication via fit_spec.
        inner = path
        for prefix in ("m/", "v/", "f/"):
            if inner.startswith(prefix):
                inner = inner[len(prefix):]
                break
        for suffix in ("/q", "/s", "/vr", "/vc", "/v"):
            if inner.endswith(suffix):
                inner = inner[: -len(suffix)]
                break
        spec = rules.spec_for(inner, tuple(leaf.shape))
        return fit_spec(rules.mesh, spec, tuple(leaf.shape))

    o_spec = map_with_paths(spec_for_opt, opt_shape)
    return params_shape, opt_shape, p_spec, o_spec


def _on_mesh(fn, mesh):
    """``fn`` traced with ``mesh`` as the context mesh."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return traced


def _split_microbatches(batch: Any, n: int) -> Any:
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch
    )


def make_train_step(
    model: Model,
    rules: ShardingRules,
    optimizer: Optimizer,
    *,
    microbatches: int | None = None,
    donate: bool = True,
):
    """Returns (jitted_step, state_shardings, batch_shardings_fn)."""
    mb = microbatches or model.cfg.microbatches
    mesh = rules.mesh
    params_shape, opt_shape, p_spec, o_spec = make_train_state_specs(
        model, rules, optimizer
    )

    def to_sharding(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    state_shardings = {
        "params": to_sharding(p_spec),
        "opt": to_sharding(o_spec),
        "step": NamedSharding(mesh, P()),
    }

    def batch_shardings(batch_shape: Any):
        return jax.tree.map(
            lambda l: rules.batch_sharding_for(tuple(l.shape)), batch_shape
        )

    def step_fn(state, batch):
        params, opt, step = state["params"], state["opt"], state["step"]
        if mb > 1:
            micro = _split_microbatches(batch, mb)

            acc_dt = jnp.dtype(model.cfg.accum_dtype)

            def accum(carry, mb_batch):
                g_acc, l_acc = carry
                (l, _), g = jax.value_and_grad(model.loss, has_aux=True)(
                    params, mb_batch
                )
                g = jax.tree.map(lambda a, b: a + b.astype(acc_dt), g_acc, g)
                return (g, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
            (grads, loss_sum), _ = jax.lax.scan(accum, (g0, 0.0), micro)
            # keep the accumulation dtype: optimizers upcast per-leaf inside
            # their update (a tree-wide f32 cast doubled peak grad memory)
            grads = jax.tree.map(lambda g: g / mb, grads)
            metrics = {"loss": loss_sum / mb}
        else:
            (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch
            )
            metrics = dict(metrics)
            metrics["loss"] = loss
        new_params, new_opt = optimizer.update(grads, opt, params, step)
        metrics["grad_norm"] = global_norm(grads)
        return {"params": new_params, "opt": new_opt, "step": step + 1}, metrics

    jitted = jax.jit(
        _on_mesh(step_fn, mesh),
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )
    return jitted, state_shardings, batch_shardings


def make_prefill_step(model: Model, rules: ShardingRules, cache_len: int):
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    p_shard = rules.params_shardings(params_shape)

    def fn(params, batch):
        return model.prefill(params, batch, cache_len)

    return jax.jit(_on_mesh(fn, rules.mesh), in_shardings=(p_shard, None)), p_shard


def make_decode_step(model: Model, rules: ShardingRules, *, donate_cache: bool = True):
    """serve_step: (params, cache, tokens) -> (logits, cache)."""
    mesh = rules.mesh
    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    p_shard = rules.params_shardings(params_shape)

    def cache_shardings(cache_shape: Any):
        def per_leaf(path: str, leaf):
            shape = tuple(leaf.shape)
            name = path.split("/")[-1]
            if name in ("k", "v") and leaf.ndim == 5:
                spec = rules.cache_spec()
            elif path.startswith("ssm") and leaf.ndim == 5:
                spec = rules.ssm_state_spec()
            elif path.startswith("ssm") and leaf.ndim >= 2:
                batch_ax = (
                    rules.data_axes if len(rules.data_axes) > 1 else rules.data_axes[0]
                )
                spec = P(None, batch_ax)
            else:
                spec = P()
            return NamedSharding(mesh, fit_spec(mesh, spec, shape))

        return map_with_paths(per_leaf, cache_shape)

    def token_sharding(tok_shape) -> NamedSharding:
        axes = rules.decode_batch_axes()
        shape = tuple(tok_shape.shape)
        first = axes if len(axes) > 1 else (axes[0] if axes else None)
        spec = P(*([first] + [None] * (len(shape) - 1))) if shape else P()
        return NamedSharding(mesh, fit_spec(mesh, spec, shape))

    def fn(params, cache, tokens):
        return model.decode(params, cache, tokens)

    jitted = jax.jit(
        _on_mesh(fn, mesh),
        in_shardings=(p_shard, None, None),
        donate_argnums=(1,) if donate_cache else (),
    )
    return jitted, p_shard, cache_shardings, token_sharding
