"""chunk_digest kernel: oracle equality across shapes/dtypes + properties."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from repro.utils.testing import given, settings, st

from repro.checkpoint.chunking import chunk_digest_np
from repro.kernels import ops, ref

DTYPES = [np.float32, np.int32, np.int8, np.uint8, np.float16, ml_dtypes.bfloat16]
SHAPES = [(17,), (1024,), (257, 33), (1, 1), (4096,), (63, 7, 5)]
CHUNKS = [64, 256, 4096]


def _rand(rng, dtype, shape):
    dt = np.dtype(dtype)
    if dt.kind == "f" or dt == np.dtype(ml_dtypes.bfloat16):
        return rng.standard_normal(shape).astype(np.float32).astype(dt)
    return rng.integers(0, 100, shape).astype(dt)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_jnp_fallback_matches_numpy_oracle(rng, dtype, shape):
    x = _rand(rng, dtype, shape)
    for cb in CHUNKS:
        want = ref.chunk_digests_np(x, cb)
        got = np.asarray(ops.chunk_digests(jnp.asarray(x), cb, use_pallas="ref"))
        assert np.array_equal(want, got), (dtype, shape, cb)


@pytest.mark.parametrize("shape,cb", [
    ((1024,), 256), ((100_000,), 4096), ((7, 130), 512),
    ((2**20,), 4 << 20), ((2**18 + 3,), 65536),
])
def test_pallas_interpret_matches_oracle(rng, shape, cb):
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    want = ref.chunk_digests_np(np.asarray(x), cb)
    got = np.asarray(ops.chunk_digests(x, cb, use_pallas="interpret"))
    assert np.array_equal(want, got)


@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float16, np.int16, np.uint16])
@pytest.mark.parametrize("shape,cb", [
    ((2048,), 256), ((100_001,), 4096), ((7, 131), 512), ((2**19 + 5,), 1 << 20),
])
def test_pallas_interpret_16bit_matches_oracle(rng, dtype, shape, cb):
    # the kernel pairs 16-bit halves into words itself; odd lengths leave
    # a half-filled last word
    x = _rand(rng, dtype, shape)
    want = ref.chunk_digests_np(x, cb)
    got = np.asarray(ops.chunk_digests(jnp.asarray(x), cb, use_pallas="interpret"))
    assert np.array_equal(want, got)


def test_digest_detects_single_byte_change(rng):
    x = rng.integers(0, 255, 8192).astype(np.uint8)
    d1 = ref.chunk_digests_np(x, 1024)
    y = x.copy()
    y[5000] ^= 1
    d2 = ref.chunk_digests_np(y, 1024)
    changed = [i for i in range(len(d1)) if tuple(d1[i]) != tuple(d2[i])]
    assert changed == [5000 // 1024]


def test_digest_is_order_sensitive():
    a = np.arange(64, dtype=np.uint32)
    b = a[::-1].copy()
    assert chunk_digest_np(a) != chunk_digest_np(b)


@settings(max_examples=50, deadline=None)
@given(
    data=st.binary(min_size=0, max_size=2048),
    cb=st.sampled_from([64, 128, 1024]),
)
def test_property_digest_deterministic_and_change_sensitive(data, cb):
    d1 = chunk_digest_np(data)
    d2 = chunk_digest_np(data)
    assert d1 == d2
    if len(data) >= 1:
        mutated = bytearray(data)
        mutated[0] ^= 0xFF
        assert chunk_digest_np(bytes(mutated)) != d1


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=5000),
    seed=st.integers(min_value=0, max_value=2**31),
    cb=st.sampled_from([64, 256]),
)
def test_property_device_equals_host(n, seed, cb):
    r = np.random.default_rng(seed)
    x = r.standard_normal(n).astype(np.float32)
    want = ref.chunk_digests_np(x, cb)
    got = np.asarray(ops.chunk_digests(jnp.asarray(x), cb, use_pallas="ref"))
    assert np.array_equal(want, got)
