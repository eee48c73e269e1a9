"""Elastic restart: checkpoint on a (4, 2) mesh, restore onto an (8,) mesh.

The CRUM principle (§3.1): no device state in the image means the same
checkpoint restores onto any topology — here demonstrated with 8 forced
host devices standing in for two different cluster shapes.

    PYTHONPATH=src python examples/elastic_reshard.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

from repro.checkpoint import ChunkStore
from repro.core import ForkedCheckpointer, RestoreManager
from repro.models import ModelConfig, build
from repro.optim import get_optimizer
from repro.runtime.sharding import ShardingRules
from repro.runtime.steps import make_train_step
from repro.utils.tree import flatten_with_paths

cfg = ModelConfig(
    name="elastic-demo", family="dense", num_layers=2, d_model=128,
    vocab_size=512, num_heads=8, num_kv_heads=8, head_dim=16, d_ff=256,
    param_dtype="float32", compute_dtype="float32",
)
model = build(cfg)
opt = get_optimizer("adamw", 1e-3)
rngb = np.random.default_rng(0)
batch = {
    "inputs": jnp.asarray(rngb.integers(0, 512, (8, 32)), jnp.int32),
    "targets": jnp.asarray(rngb.integers(0, 512, (8, 32)), jnp.int32),
}

# ---- phase 1: train 3 steps on mesh A = (data=4, model=2), checkpoint ----
mesh_a = make_mesh((4, 2), ("data", "model"))
with jax.set_mesh(mesh_a):
    rules_a = ShardingRules(cfg=cfg, mesh=mesh_a)
    step_a, sh_a, _ = make_train_step(model, rules_a, opt, donate=False)
    params = model.init(jax.random.key(0))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    state = jax.device_put(state, sh_a)
    for _ in range(3):
        state, m = step_a(state, batch)
    print(f"[mesh A 4x2] step 3 loss={float(m['loss']):.4f}")
    tmp = tempfile.mkdtemp()
    ck = ForkedCheckpointer(ChunkStore(tmp), chunk_bytes=1 << 18)
    ck.save_async(3, {"device": state}).wait()
    ck.close()

# ---- phase 2: restore onto mesh B = (data=8,) and continue ----
mesh_b = make_mesh((8,), ("data",))
with jax.set_mesh(mesh_b):
    rules_b = ShardingRules(cfg=cfg, mesh=mesh_b)
    step_b, sh_b, _ = make_train_step(model, rules_b, opt, donate=False)
    flat_sh, _ = flatten_with_paths({"device": sh_b})

    restored, manifest = RestoreManager(ChunkStore(tmp)).restore(
        sharding_for=lambda path, shape: flat_sh.get(path), verify="store"
    )
    state_b = restored["device"]
    for _ in range(2):
        state_b, m = step_b(state_b, batch)
    print(f"[mesh B 8x1] resumed from step {manifest.step}, "
          f"step 5 loss={float(m['loss']):.4f}")
    print("elastic reshard OK: same checkpoint, different topology")
