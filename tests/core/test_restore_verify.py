"""Restored state checked against the digests its save recorded, and the
process-level JAX setup every entry point shares."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForkedCheckpointer, RestoreManager
from repro.core.restore import verify_restored_digests
from repro.runtime import env


def _saved(tmp_store, rng, verify=False):
    state = {
        "device": {
            # 1001 bf16 values: not a whole number of words or chunks
            "w": jnp.asarray(rng.standard_normal(1001), jnp.bfloat16),
            "m": jnp.asarray(rng.standard_normal((64, 33)), jnp.float32),
            "step": jnp.asarray(7, jnp.int32),
        },
        "host": {"step": np.int64(7)},
    }
    ck = ForkedCheckpointer(tmp_store, chunk_bytes=1024)
    try:
        ck.save_async(7, state).wait()
    finally:
        ck.close()
    rm = RestoreManager(tmp_store)
    state, manifest = rm.restore(
        sharding_for=lambda path, shape: (
            jax.sharding.SingleDeviceSharding(jax.devices()[0])
            if path.startswith("device/") else None
        ),
        verify=verify,
    )
    return state, manifest, rm


def test_restored_state_matches_saved_digests(tmp_store, rng):
    state, manifest, rm = _saved(tmp_store, rng, verify="device")
    assert isinstance(state["device"]["w"], jax.Array)
    # w: 2002 B -> 2 chunks, m: 8448 B -> 9, two 4-byte/8-byte scalars
    assert rm.last_check == {"chunks": 2 + 9 + 1 + 1, "unmatched": 0}
    assert rm.timings.counts["restore/verify_device"] == 1
    assert "restore/verify" not in rm.timings.counts  # the store was not re-read
    assert verify_restored_digests(state, manifest) == rm.last_check


@pytest.mark.parametrize("verify", [True, "device-lazy"])
def test_restore_refuses_a_check_it_cannot_make(tmp_store, rng, verify):
    _saved(tmp_store, rng)
    rm = RestoreManager(tmp_store)
    with pytest.raises(ValueError, match="verify"):
        if verify == "device-lazy":
            rm.restore(lazy=True, verify="device")
        else:
            rm.restore(verify=verify)


def test_restored_state_that_differs_is_named(tmp_store, rng):
    state, manifest, _ = _saved(tmp_store, rng)
    state["device"]["m"] = state["device"]["m"].at[40, 0].add(1.0)
    with pytest.raises(ValueError, match=r"device/m .* chunk 5 "):
        verify_restored_digests(state, manifest)


def test_compile_cache_follows_the_environment(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert env.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads the variable
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert env.enable_compile_cache() == str(env.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(env.CACHE_DIR)
        assert env.CACHE_DIR.name == ".jax_cache"
        assert (env.CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
