"""The training job the benchmark runs through the system under test.

The benchmark acts as CRUM's user: it builds a training job from a
configuration file (the program's model and jitted step, at the sizes the
file states), makes the weights and every batch itself from the seed, and
hands the job's state to the program's ``CheckpointedTrainer``. From the
program it takes only the system under test: the model and step builders,
the optimizer it states, the trainer and its restore manager.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as refmod

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.configs import get_config  # noqa: E402
from repro.core import CheckpointedTrainer, CheckpointPolicy  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime.sharding import ShardingRules  # noqa: E402
from repro.runtime.steps import make_train_step  # noqa: E402
from repro.utils.tree import flatten_with_paths  # noqa: E402

FP_MULT = (0x9E3779B1, 0x85EBCA77)  # odd, so one changed word always shows


def fingerprint_leaf(x: jax.Array) -> jax.Array:
    """Two 32-bit weighted sums of a leaf's stored bits: (2,) u32."""
    flat = x.reshape(-1)
    if flat.dtype.itemsize == 2:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    elif flat.dtype.itemsize == 4:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    else:
        w = flat.astype(jnp.uint32)
    i = jnp.arange(w.shape[0], dtype=jnp.uint32) * jnp.uint32(2) + jnp.uint32(1)
    return jnp.stack([jnp.sum(w * (i * jnp.uint32(m)), dtype=jnp.uint32)
                      for m in FP_MULT])


@jax.jit
def fingerprint(tree) -> jax.Array:
    """(leaves, 2) u32 fingerprints of a state, in tree order."""
    return jnp.stack([fingerprint_leaf(x) for x in jax.tree.leaves(tree)])


class Job:
    """One configuration's training job on ``chips`` devices, from a seed."""

    def __init__(self, cfg: dict, ref, *, seed: int, chips: int, store_root: str):
        self.cfg, self.ref = cfg, ref
        prog = cfg["program"]
        model_cfg = get_config(prog["arch"]).with_overrides(**prog["fields"])
        model = build(model_cfg)
        o = cfg["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"optimizer {o['name']!r}: the job states AdamW only")
        self.optimizer = adamw(
            o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], max_grad_norm=o["max_grad_norm"],
        )
        rules = ShardingRules(cfg=model_cfg, mesh=make_host_mesh((chips,), ("data",)))
        # the step builder's default: the old state's buffers are donated to
        # the new one (two undonated 4.9 GB states and the step's temporaries
        # do not fit one 16 GB chip)
        self.step_fn, self.shardings, batch_sh = make_train_step(model, rules, self.optimizer)
        self._check_layout(model)
        B, S, V = cfg["batch"], cfg["seq_len"], cfg["vocab_size"]
        bsh = batch_sh(jax.ShapeDtypeStruct((B, S), jnp.int32))
        key = refmod.base_key(seed)
        self._wkey, self._dkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
        self._batch = jax.jit(
            lambda k, t: refmod.batch_at(k, t, B, S, V),
            out_shardings={"inputs": bsh, "targets": bsh},
        )
        self._init = jax.jit(self._make_state, out_shardings=self.shardings)
        self._init_params = jax.jit(lambda k: ref.init_params(k, cfg),
                                    out_shardings=self.shardings["params"])
        self.tokens_per_step = B * S
        ck = cfg["checkpoint"]
        self.trainer = CheckpointedTrainer(
            self.step_fn,
            store_root=store_root,
            policy=CheckpointPolicy(interval_steps=1 << 30, keep_last=ck["keep_last"]),
            codec=ck["codec"],
            chunk_bytes=ck["chunk_bytes"],
            incremental=ck["incremental"],
            backend=ck["backend"],
        )
        flat_sh, _ = flatten_with_paths({"device": self.shardings, "host": None})
        self.sharding_for = lambda path, shape: flat_sh.get(path)

    def _make_state(self, key):
        params = self.ref.init_params(key, self.cfg)
        mdt = jnp.dtype(self.cfg["moment_dtype"])
        zeros = lambda p: jnp.zeros(p.shape, mdt)  # noqa: E731
        return {"params": params,
                "opt": {"m": jax.tree.map(zeros, params),
                        "v": jax.tree.map(zeros, params)},
                "step": jnp.zeros((), jnp.int32)}

    def _check_layout(self, model) -> None:
        """The reference's layout is the program's, leaf for leaf."""
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        want = jax.eval_shape(lambda: self._make_state(jax.random.key(0)))
        have = {"params": params,
                "opt": jax.eval_shape(lambda: self.optimizer.init(params)),
                "step": jax.ShapeDtypeStruct((), jnp.int32)}
        sig = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)  # noqa: E731
        if sig(want) != sig(have):
            raise ValueError("the reference's parameter layout differs from the program's")

    # -- the job -------------------------------------------------------------
    def init_state(self) -> dict:
        return self._init(self._wkey)

    def init_params(self) -> dict:
        """The initial weights again, from the seed (not kept on the device:
        one step's two states leave no room for them)."""
        return self._init_params(self._wkey)

    def batch(self, t: int) -> dict:
        return self._batch(self._dkey, jnp.int32(t))

    def state_shapes(self) -> list:
        """The device state's leaves as shapes (a digest pass reads them once)."""
        return jax.tree.leaves(jax.eval_shape(self._make_state, self._wkey))

    def tree(self, state: dict, step: int) -> dict:
        """What the trainer checkpoints: device state and host step."""
        return {"device": state, "host": {"step": np.int64(step)}}

    def device_peak_bytes(self) -> int | None:
        stats = [d.memory_stats() for d in jax.local_devices()]
        peaks = [s["peak_bytes_in_use"] for s in stats if s and "peak_bytes_in_use" in s]
        return max(peaks) if peaks else None


def evict_page_cache(root: str) -> None:
    """Drop the image files' pages from the page cache (they are clean)."""
    for dirpath, _, files in os.walk(root):
        for name in files:
            fd = os.open(os.path.join(dirpath, name), os.O_RDONLY)
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)
