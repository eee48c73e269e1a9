"""Sharding rules: param/activation PartitionSpecs per architecture family.

Mesh axes (launch/mesh.py): single-pod ("data", "model") = (16, 16);
multi-pod ("pod", "data", "model") = (2, 16, 16). The pod axis extends
data parallelism across the DCN (gradient all-reduce is the only
cross-pod collective; checkpoint I/O is per-host by construction).

Param rules are (regex over path) -> logical spec, resolved bottom-up per
leaf; FSDP additionally shards the first replicated non-trivial dim over
("pod","data"). GQA archs whose kv_heads don't divide the model axis
replicate KV projections and shard the *head_dim* of the KV cache instead
(DESIGN §4).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.utils.tree import flatten_with_paths, map_with_paths


# ---------------------------------------------------------------------------
# activation constraint helper (no-op outside a mesh context)
# ---------------------------------------------------------------------------

def _current_mesh_names() -> tuple[str, ...] | None:
    """Axis names of the mesh entered with ``jax.set_mesh``, if any."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else tuple(m.axis_names)


def _filter_axes(spec: tuple, names: tuple[str, ...]) -> tuple:
    out = []
    for a in spec:
        if a is None:
            out.append(None)
        elif isinstance(a, str):
            out.append(a if a in names else None)
        elif isinstance(a, (tuple, list)):
            kept = tuple(s for s in a if s in names)
            out.append(kept if kept else None)
        else:
            out.append(None)
    return tuple(out)


def fit_spec(mesh: Mesh, spec: P, shape: tuple[int, ...]) -> P:
    """Drop spec axes that over-index or don't divide the dim (replicate)."""
    if len(spec) > len(shape):
        spec = P(*tuple(spec)[: len(shape)])
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a not in mesh.axis_names for a in axes):
            out.append(None)
            continue
        n = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))
        out.append(ax if (n and dim % n == 0) else None)
    return P(*out)


def constrain(x: jax.Array, spec: tuple) -> jax.Array:
    """with_sharding_constraint that degrades to a no-op without a mesh."""
    names = _current_mesh_names()
    if not names:
        return x
    clean = _filter_axes(spec, names)
    if all(a is None for a in clean):
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardingRules:
    """Resolved rule set for one (config, mesh) pair."""

    cfg: ModelConfig
    mesh: Mesh
    fsdp: bool = True

    @property
    def data_axes(self) -> tuple[str, ...]:
        """Axes carrying the batch. Without tensor parallelism the "model"
        axis joins them (DP over the full mesh)."""
        names = ("pod", "data") if self.cfg.tensor_parallel else ("pod", "data", "model")
        return tuple(a for a in names if a in self.mesh.axis_names)

    @property
    def model_axis(self) -> str | None:
        if not self.cfg.tensor_parallel:
            return None
        return "model" if "model" in self.mesh.axis_names else None

    def _model_size(self) -> int:
        return self.mesh.shape["model"] if self.model_axis else 1

    # -- core decisions -------------------------------------------------------
    def kv_heads_shardable(self) -> bool:
        return self.cfg.num_kv_heads % max(self._model_size(), 1) == 0

    def ssm_heads_shardable(self) -> bool:
        return (
            self.cfg.ssm_heads % max(self._model_size(), 1) == 0
            and self.cfg.ssm_heads > 0
        )

    def _fsdp_axis(self, dim: int) -> Any:
        """Axis group for FSDP-sharding a dim, or None if not divisible."""
        if not self.fsdp:
            return None
        n = int(np.prod([self.mesh.shape[a] for a in self.data_axes], dtype=np.int64))
        if n > 1 and dim % n == 0:
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        return None

    def param_rules(self) -> list[tuple[str, Any]]:
        """(regex, spec-maker) pairs; first match wins.

        spec-maker is a callable (shape) -> PartitionSpec so FSDP can check
        divisibility per-leaf.
        """
        model = self.model_axis
        cfg = self.cfg
        if cfg.attn_over_model:
            attn_model = None   # attention runs batch-parallel over model
        else:
            attn_model = model
        kv_model = attn_model if self.kv_heads_shardable() else None
        ssm_model = model if self.ssm_heads_shardable() else None

        L = "LAYER"  # sentinel: stacked-layer axis — never sharded, never FSDP'd

        def _clean(ax):
            return [None if a == L else a for a in ax]

        def s(*axes):
            return lambda shape: P(*_clean(list(axes[: len(shape)])))

        def fsdp_last(*axes):
            # FSDP: shard the first unsharded (non-layer) dim over data axes
            def mk(shape):
                ax = list(axes[: len(shape)])
                for i, a in enumerate(ax):
                    if a is None and shape[i] > 1:
                        f = self._fsdp_axis(shape[i])
                        if f is not None:
                            ax[i] = f
                            break
                return P(*_clean(ax))

            return mk
        rules: list[tuple[str, Any]] = [
            # embeddings / lm head: vocab over model, d_model over fsdp
            (r".*(embed|lm_head|codebook_embed|codebook_head).*", fsdp_last(model, None)),
            # attention projections
            (r".*attn/wq$", fsdp_last(L, None, attn_model)),
            (r".*attn/wk$", fsdp_last(L, None, kv_model)),
            (r".*attn/wv$", fsdp_last(L, None, kv_model)),
            (r".*attn/wo$", fsdp_last(L, attn_model, None)),
            (r".*attn/b(q)$", s(L, model)),
            (r".*attn/b(k|v)$", s(L, kv_model)),
            # shared attention block (hybrid): no leading L
            (r".*shared/attn/wq$", fsdp_last(None, model)),
            (r".*shared/attn/w(k|v)$", fsdp_last(None, kv_model)),
            (r".*shared/attn/wo$", fsdp_last(model, None)),
            (r".*shared/mlp/w(i|g)$", fsdp_last(None, model)),
            (r".*shared/mlp/wo$", fsdp_last(model, None)),
            # dense MLP
            (r".*mlp/w(i|g)$", fsdp_last(L, None, model)),
            (r".*mlp/wo$", fsdp_last(L, model, None)),
            # MoE: experts over model; expert matrices fsdp over D
            (r".*moe/router$", s(L, None, None)),
            (r".*moe/w(i|g)$", fsdp_last(L, model, None, None)),
            (r".*moe/wo$", fsdp_last(L, model, None, None)),
            (r".*moe/dense/w(i|g)$", fsdp_last(L, None, model)),
            (r".*moe/dense/wo$", fsdp_last(L, model, None)),
            # mamba2: per-segment projections shard on their own dims
            (r".*ssm/w_(z|x)$", fsdp_last(L, None, ssm_model)),
            (r".*ssm/w_(B|C)$", fsdp_last(L, None, None)),
            (r".*ssm/w_dt$", fsdp_last(L, None, ssm_model)),
            (r".*ssm/w_out$", fsdp_last(L, ssm_model, None)),
            (r".*ssm/conv_x$", s(L, None, ssm_model)),
            (r".*ssm/conv_(B|C)$", s(L, None, None)),
            (r".*ssm/conv_xb$", s(L, ssm_model)),
            (r".*ssm/(conv_Bb|conv_Cb|norm_w)$", s(L, None)),
            (r".*ssm/(A_log|D|dt_bias)$", s(L, None)),
            # vision stub projection
            (r".*vision_proj$", fsdp_last(None, model)),
            # norms & everything else: replicated
            (r".*", s(L, None, None, None, None)),
        ]
        return rules

    # -- public API ---------------------------------------------------------------
    def spec_for(self, path: str, shape: tuple[int, ...]) -> P:
        for pat, mk in self.param_rules():
            if re.fullmatch(pat, path):
                return mk(shape)
        return P()

    def params_specs(self, params_shape: Any) -> Any:
        return map_with_paths(
            lambda p, leaf: fit_spec(
                self.mesh, self.spec_for(p, tuple(leaf.shape)), tuple(leaf.shape)
            ),
            params_shape,
        )

    def params_shardings(self, params_shape: Any) -> Any:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.params_specs(params_shape),
            is_leaf=lambda x: isinstance(x, P),
        )

    # -- activations / batch / cache ----------------------------------------------
    def batch_spec(self) -> P:
        return P(self.data_axes if len(self.data_axes) > 1 else self.data_axes[0])

    def batch_sharding_for(self, leaf_shape: tuple[int, ...]) -> NamedSharding:
        n = int(np.prod([self.mesh.shape[a] for a in self.data_axes], dtype=np.int64))
        if leaf_shape and leaf_shape[0] % n == 0:
            first = self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
            spec = [first] + [None] * (len(leaf_shape) - 1)
            return NamedSharding(self.mesh, P(*spec))
        return NamedSharding(self.mesh, P())

    def cache_spec(self) -> P:
        """KV cache (L, B, Hkv, S, Dh).

        Batch shards over "data" only (serve batches rarely divide the full
        DP group — an unshardable axis would replicate the entire cache:
        observed 1.3 TiB/device on musicgen decode_32k). The model axis
        takes kv-heads when divisible, else head_dim (partial-sum attention
        scores, one small all-reduce per step) — this applies even for
        tensor_parallel=False archs, where weights replicate over "model"
        but the cache must still shard.
        """
        model = "model" if "model" in self.mesh.axis_names else None
        data = "data" if "data" in self.mesh.axis_names else None
        n_kv = self.cfg.num_kv_heads
        msize = self.mesh.shape.get("model", 1) if model else 1
        if n_kv and msize > 1 and n_kv % msize == 0:
            return P(None, data, model, None, None)
        if self.cfg.head_dim and msize > 1 and self.cfg.head_dim % msize == 0:
            return P(None, data, None, None, model)
        return P(None, data, None, None, None)

    def decode_batch_axes(self) -> tuple[str, ...]:
        """Token batch for decode: data axes only (see cache_spec)."""
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    def ssm_state_spec(self) -> P:
        """SSM decode state (L, B, H, P, N): batch over data only (see
        cache_spec); heads over model when divisible."""
        data = "data" if "data" in self.mesh.axis_names else None
        model = "model" if "model" in self.mesh.axis_names else None
        msize = self.mesh.shape.get("model", 1) if model else 1
        h = self.cfg.ssm_heads
        model = model if (h and msize > 1 and h % msize == 0) else None
        return P(None, data, model, None, None)

    def opt_state_specs(self, params_shape: Any) -> Any:
        """Optimizer moments mirror param specs (ZeRO via fsdp=True)."""
        return self.params_specs(params_shape)
