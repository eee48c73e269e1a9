"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, unsupported vector ops, programs larger than HBM), so the
main path's kernel and train step are compiled here at published
qwen2-0.5b widths. Nothing runs: these say nothing about results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.data import SyntheticBatches
from repro.kernels import ops
from repro.models import build
from repro.optim import get_optimizer, warmup_cosine
from repro.runtime.sharding import ShardingRules
from repro.runtime.steps import make_train_step

HBM_BYTES = 16 << 30  # one v5e chip
QWEN = get_config("qwen2-0.5b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,dtype,chunk_bytes", [
    # the tied embedding at the train CLI's chunk size
    ((QWEN.vocab_size, QWEN.d_model), jnp.bfloat16, 1 << 20),
    # an AdamW moment of it at the trainer's default chunk size
    ((QWEN.vocab_size, QWEN.d_model), jnp.float32, 4 << 20),
    # blocks/attn/wk: 5.25 chunks, so the last chunk is a padded tail
    ((QWEN.num_layers, QWEN.d_model, QWEN.num_kv_heads * QWEN.head_dim),
     jnp.bfloat16, 1 << 20),
], ids=["embed-bf16-1MiB", "moment-f32-4MiB", "wk-bf16-tail"])
def test_digest_kernel_compiles_for_v5e(one_chip, shape, dtype, chunk_bytes):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = jax.jit(lambda a: ops.chunk_digests(a, chunk_bytes, use_pallas="pallas"))
    compiled = fn.lower(x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's device op carries its own name, as the profiler shows it
    assert any(line.lstrip().startswith("%chunk_digest") and "tpu_custom_call" in line
               for line in text.splitlines())
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    assert compiled.out_info.shape == (-(-nbytes // chunk_bytes), 2)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_full_width_train_step_compiles_for_v5e(topo):
    """One layer of qwen2-0.5b at published widths, the train CLI's step
    and its chip batch (4 x 1024), on a one-device mesh."""
    cfg = QWEN.with_overrides(num_layers=1)
    model = build(cfg)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    optimizer = get_optimizer(cfg.optimizer, warmup_cosine(3e-4, 10, 100))
    with jax.set_mesh(mesh):
        step, shardings, _ = make_train_step(
            model, ShardingRules(cfg=cfg, mesh=mesh), optimizer, donate=False
        )
        params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        state = {
            "params": params,
            "opt": jax.eval_shape(lambda: optimizer.init(params)),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state, shardings,
        )
        batch = {
            k: jax.ShapeDtypeStruct(
                np.shape(v), np.asarray(v).dtype,
                sharding=NamedSharding(mesh, P("data")),
            )
            for k, v in next(SyntheticBatches(cfg, batch=4, seq_len=1024)).items()
        }
        compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES
