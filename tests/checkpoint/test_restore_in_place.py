"""Restore decodes each stored shard chunk by chunk straight into one array
(``ChunkStore.read_chunk_into``), returned itself for a window that is that
shard; the store keeps read-only views of those decodes, bounded by the
bytes of the arrays they keep alive."""
import gc
import json
import os
import subprocess
import sys
import textwrap
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ChunkStore, save_pytree
from repro.checkpoint.codecs import Codec, has_codec, register_codec, unregister_codec
from repro.checkpoint.manifest import LeafRecord, ShardRecord
from repro.checkpoint.sharded import restore_leaf
from repro.core.restore import RestoreManager
from repro.utils.tree import flatten_with_paths

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a codec registered with no decode_into: restore decompresses and copies
_PLAIN = Codec("test-reversed", lambda b: bytes(b)[::-1], lambda b: bytes(b)[::-1])


@pytest.fixture
def plain_codec():
    register_codec(_PLAIN, replace=True)
    yield _PLAIN.name
    unregister_codec(_PLAIN.name)


def _state(seed=0):
    """Sizes that leave a short last chunk at a 1000-byte chunk size, and a
    0-d leaf."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((37, 29)), jnp.bfloat16),
        "m": jnp.asarray(rng.standard_normal((33, 17)), jnp.float32),
        "ids": jnp.asarray(rng.integers(-9, 9, (250,)), jnp.int32),
        "one": jnp.ones((3,), jnp.float32),
        "step": jnp.asarray(seed + 7, jnp.int32),
    }


def _bits(x) -> bytes:
    a = np.asarray(x)
    return a.dtype.str.encode() + str(a.shape).encode() + a.tobytes()


def _n_chunks(manifest) -> int:
    return sum(len(s.chunks) for lv in manifest.leaves.values() for s in lv.shards)


def _single_device(path, shape):
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


@pytest.mark.parametrize("codec", [
    *(c for c in ("none", "gzip", "pgzip", "zstd1") if has_codec(c)), "registered-plain",
])
def test_single_device_restore_is_bit_identical_and_all_in_place(tmp_path, codec, request):
    if codec == "registered-plain":
        codec = request.getfixturevalue("plain_codec")
    state = _state()
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec=codec, chunk_bytes=1000)
    for sharding_for in (_single_device, None):  # device leaves, host leaves
        store = ChunkStore(str(tmp_path))
        rm = RestoreManager(store)
        for _ in range(2):  # the second restore is served by the chunk cache
            restored, m = rm.restore(sharding_for=sharding_for)
            for path, leaf in flatten_with_paths(state)[0].items():
                got = flatten_with_paths(restored)[0][path]
                assert isinstance(got, jax.Array) == (sharding_for is not None)
                assert _bits(got) == _bits(leaf), path
            assert store.chunks_in_place == store.chunks_read == _n_chunks(m) > len(m.leaves)


def test_cached_view_of_a_leaf_written_since_is_decoded_again(tmp_path):
    state = _state()
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec="gzip", chunk_bytes=1000)
    store = ChunkStore(str(tmp_path))
    rm = RestoreManager(store)
    first, m = rm.restore()
    first["m"][0, 0] += 1.0  # the cache holds views of this very array
    second, _ = rm.restore()
    for path, leaf in flatten_with_paths(state)[0].items():
        assert _bits(flatten_with_paths(second)[0][path]) == _bits(leaf), path
    # only the written chunk of "m" missed the cache
    assert store.chunks_read == store.chunks_in_place == _n_chunks(m) + 1


@pytest.mark.parametrize("cache_bytes", [0, 16 * 65536])
def test_chunk_cache_keeps_restored_arrays_alive_only_within_its_bytes(tmp_path, cache_bytes):
    w = np.arange(16384, dtype=np.float32)  # 64 KiB in 16 chunks
    save_pytree({"w": w}, ChunkStore(str(tmp_path)), 1, codec="gzip", chunk_bytes=4096)
    store = ChunkStore(str(tmp_path), cache_bytes=cache_bytes)
    rm = RestoreManager(store)
    first, _ = rm.restore()
    alive = weakref.ref(first["w"])
    del first
    gc.collect()
    # each of the 16 views is charged the whole 64 KiB array it keeps alive
    assert (alive() is not None) == (cache_bytes > 0)
    second, _ = rm.restore()
    np.testing.assert_array_equal(second["w"], w)
    assert store.chunks_read == (16 if cache_bytes else 32)


def _faulty_leaf(store: ChunkStore, codec: str, fault: str):
    """A 2-chunk f32 leaf whose first chunk's frame decodes 4 bytes short
    of, or past, the 1024 bytes its record (and the leaf) says it holds."""
    data = np.arange(512, dtype=np.float32).tobytes()
    w = store.writer(1)
    first = {"short": data[:1020], "long": data[:1028]}[fault]
    recs = [w.append(first, codec, index=0, digest=0),
            w.append(data[1024:], codec, index=1, digest=0)]
    w.close()
    recs[0].raw_len = 1024
    return LeafRecord("x", [512], "float32", [ShardRecord([0], [512], recs)])


@pytest.mark.parametrize("fault", ["short", "long"])
@pytest.mark.parametrize("codec", [c for c in ("zstd1", "gzip") if has_codec(c)])
def test_frame_of_the_wrong_length_raises_ioerror_in_place(tmp_path, codec, fault):
    store = ChunkStore(str(tmp_path))
    lrec = _faulty_leaf(store, codec, fault)
    with pytest.raises(IOError, match="length mismatch"):
        restore_leaf(store, lrec, None)
    assert store.chunks_in_place == 0


@pytest.mark.skipif(not has_codec("zstd1"), reason="zstandard not installed")
def test_truncated_zstd_frame_raises_ioerror_in_place(tmp_path):
    store = ChunkStore(str(tmp_path))
    data = np.arange(256, dtype=np.float32).tobytes()
    w = store.writer(1)
    rec = w.append(data, "zstd1", index=0, digest=0)
    w.close()
    rec.comp_len //= 2  # the frame's second half is missing
    lrec = LeafRecord("x", [256], "float32", [ShardRecord([0], [256], [rec])])
    with pytest.raises(IOError, match="length mismatch"):
        restore_leaf(store, lrec, None)


def test_concurrent_lazy_first_accesses_decode_each_chunk_once(tmp_path):
    state = {f"l{i}": _state(i) for i in range(3)}
    codec = "zstd1" if has_codec("zstd1") else "gzip"
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec=codec, chunk_bytes=1000)
    want = {p: _bits(v) for p, v in flatten_with_paths(state)[0].items()}
    store = ChunkStore(str(tmp_path))
    lazy, m = RestoreManager(store).restore(lazy=True, sharding_for=_single_device)
    got, errors = [], []
    barrier = threading.Barrier(6)

    def reader(order):
        barrier.wait()
        try:
            got.append({p: _bits(lazy[p]) for p in order})
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    keys = lazy.keys()
    threads = [threading.Thread(target=reader, args=(keys[k:] + keys[:k],))
               for k in range(0, 12, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    lazy.close()
    assert not errors, errors
    assert len(got) == 6 and all(g == want for g in got)
    assert store.chunks_in_place == store.chunks_read == _n_chunks(m)


REPLICATED = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import ChunkStore, save_pytree
    from repro.core.restore import RestoreManager
    from repro.launch.mesh import make_mesh

    root = tempfile.mkdtemp()
    # rows split over x, each half replicated over the four devices of y
    sh = NamedSharding(make_mesh((2, 4), ("x", "y")), P("x"))
    w = jnp.arange(64 * 24, dtype=jnp.float32).reshape(64, 24)
    m = save_pytree({"w": jax.device_put(w, sh)}, ChunkStore(root), 1,
                    codec="gzip", chunk_bytes=1000)
    store = ChunkStore(root)
    got, _ = RestoreManager(store).restore(sharding_for=lambda p, s: sh)
    print(json.dumps({
        "equal": bool(np.array_equal(np.asarray(got["w"]), np.asarray(w))),
        "stored_shards": len(m.leaves["w"].shards),
        "device_shards": len(got["w"].addressable_shards),
        "chunks": sum(len(s.chunks) for s in m.leaves["w"].shards),
        "chunks_read": store.chunks_read,
        "chunks_in_place": store.chunks_in_place,
    }))
    """
)


def test_leaf_replicated_over_devices_is_decoded_once():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", REPLICATED], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["equal"] and r["stored_shards"] == 2 and r["device_shards"] == 8
    assert r["chunks_read"] == r["chunks_in_place"] == r["chunks"]
