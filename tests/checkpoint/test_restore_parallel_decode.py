"""Restore decodes a stored shard's chunks on a pool of threads while the
restoring thread reads on (``ChunkStore.read_chunks_into``): the bytes are
those saved, the store's timers stay on the restoring thread, an error
names its chunk, and no decode thread outlives the restore call."""
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ChunkStore, save_pytree
from repro.checkpoint.codecs import Codec, get_codec, has_codec, register_codec, unregister_codec
from repro.checkpoint.manifest import load_manifest
from repro.core.restore import RestoreManager
from repro.utils.timing import Timings
from repro.utils.tree import flatten_with_paths

CHUNK = 1024
ZSTD_OR_GZIP = "zstd1" if has_codec("zstd1") else "gzip"

# a codec registered with no decode_into: the pool decompresses and copies
_PLAIN = Codec("test-reversed-pool", lambda b: bytes(b)[::-1], lambda b: bytes(b)[::-1])


@pytest.fixture
def codec(request):
    if request.param != _PLAIN.name:
        yield request.param
        return
    register_codec(_PLAIN, replace=True)
    yield _PLAIN.name
    unregister_codec(_PLAIN.name)


CODECS = pytest.mark.parametrize(
    "codec", [*(["zstd1"] if has_codec("zstd1") else []), _PLAIN.name], indirect=True)


@pytest.fixture
def cpus(request, monkeypatch):
    """The CPUs the process may run on, as the decode pool sees them."""
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(request.param)))
    return request.param


def _leaf(dtype, seed: int) -> jax.Array:
    """64 whole chunks and a short tail."""
    n = 64 * CHUNK // np.dtype(dtype).itemsize + 37
    return jnp.asarray(np.random.default_rng(seed).standard_normal(n), dtype)


def _state() -> dict:
    return {"w": _leaf(jnp.bfloat16, 0), "m": _leaf(jnp.float32, 1),
            "v": _leaf(jnp.float32, 2).reshape(-1, 1)}


def _bits(x) -> bytes:
    a = np.asarray(x)
    return a.dtype.str.encode() + str(a.shape).encode() + a.tobytes()


def _n_chunks(manifest) -> int:
    return sum(len(s.chunks) for lv in manifest.leaves.values() for s in lv.shards)


def _decode_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name.startswith("crum-decode")]


def _single_device(path, shape):
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


class _ThreadTimings(Timings):
    """Timings that also note the thread each timer was recorded on."""

    def __init__(self):
        super().__init__()
        self.threads: dict[str, set[int]] = {}

    def add(self, name: str, seconds: float) -> None:
        super().add(name, seconds)
        self.threads.setdefault(name, set()).add(threading.get_ident())


@CODECS
@pytest.mark.parametrize("cpus", [1, 4, 64], indirect=True)
@pytest.mark.parametrize("sharding_for", [None, _single_device], ids=["host", "device"])
def test_many_chunk_restore_is_byte_identical(tmp_path, codec, cpus, sharding_for):
    state = _state()
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec=codec, chunk_bytes=CHUNK)
    store = ChunkStore(str(tmp_path))  # cold: nothing in the chunk cache
    restored, m = RestoreManager(store).restore(sharding_for=sharding_for)
    got = flatten_with_paths(restored)[0]
    for path, leaf in flatten_with_paths(state)[0].items():
        assert _bits(got[path]) == _bits(leaf), path
    n = _n_chunks(m)
    assert n == 3 * 65
    assert store.chunks_in_place == store.chunks_read == n
    assert store.decode_s > 0
    assert not _decode_threads()


@CODECS
@pytest.mark.parametrize("cpus", [1, 4], indirect=True)
def test_store_timers_divide_the_restoring_threads_assemble(tmp_path, codec, cpus):
    save_pytree(_state(), ChunkStore(str(tmp_path)), 1, codec=codec, chunk_bytes=CHUNK)
    timings = _ThreadTimings()
    store = ChunkStore(str(tmp_path), timings=timings)
    _, m = RestoreManager(store, timings=timings).restore(sharding_for=_single_device)
    n = _n_chunks(m)
    assert timings.counts["store/read"] == timings.counts["store/decode"] == n
    me = {threading.get_ident()}
    for name in ("store/read", "store/decode", "restore/assemble"):
        assert timings.threads[name] == me, name
    # what bench/metrics/restore_assemble_s.py reads stays non-negative
    nested = timings.totals["store/read"] + timings.totals["store/decode"]
    assert nested <= timings.totals["restore/assemble"]


@pytest.mark.parametrize("cpus", [1, 3, 64], indirect=True)
def test_decodes_run_off_the_restoring_thread_on_at_most_one_thread_a_cpu(tmp_path, cpus):
    ran_on = set()

    def decompress(b):
        ran_on.add(threading.current_thread().name)
        return bytes(b)[::-1]

    register_codec(Codec("test-thread-noting", lambda b: bytes(b)[::-1], decompress),
                   replace=True)
    try:
        save_pytree(_state(), ChunkStore(str(tmp_path)), 1, codec="test-thread-noting",
                    chunk_bytes=CHUNK)
        store = ChunkStore(str(tmp_path))
        _, m = RestoreManager(store).restore()
    finally:
        unregister_codec("test-thread-noting")
    assert store.chunks_in_place == store.chunks_read == _n_chunks(m)
    assert ran_on and all(name.startswith("crum-decode") for name in ran_on)
    assert len(ran_on) <= min(cpus, 8)
    assert not _decode_threads()


def _corrupt(root: str, fault: str) -> str:
    """Spoil chunk 32 of ``w``'s 65; returns the ``file@offset`` an error names."""
    rec = load_manifest(root, 1).leaves["w"].shards[0].chunks[32]
    if fault == "garbage":
        frame = np.random.default_rng(5).integers(0, 256, rec.comp_len, np.uint8).tobytes()
    else:  # a whole frame of half the chunk: it decodes short
        frame = get_codec(rec.codec).compress(bytes(rec.raw_len // 2))
        assert len(frame) < rec.comp_len
    with open(os.path.join(root, rec.file), "r+b") as f:
        f.seek(rec.file_offset)
        f.write(frame)
    return f"{rec.file}@{rec.file_offset}"


@pytest.mark.parametrize("fault", ["garbage", "short"])
@pytest.mark.parametrize("cpus", [1, 4], indirect=True)
def test_corrupt_chunk_raises_with_its_file_and_offset(tmp_path, fault, cpus):
    save_pytree(_state(), ChunkStore(str(tmp_path)), 1, codec=ZSTD_OR_GZIP, chunk_bytes=CHUNK)
    where = _corrupt(str(tmp_path), fault)
    store = ChunkStore(str(tmp_path))
    with pytest.raises(IOError, match=where):
        RestoreManager(store).restore()
    assert not _decode_threads()
    # each chunk is counted once its decode is seen to end, in order: those
    # of the leaves restored before ``w`` and ``w``'s first 32
    leaves = load_manifest(str(tmp_path), 1).leaves
    before = list(leaves).index("w")
    assert store.chunks_read == store.chunks_in_place == 65 * before + 32


def _lazy_and_eager(root: str, sharding_for):
    eager, _ = RestoreManager(ChunkStore(root)).restore(sharding_for=sharding_for)
    store = ChunkStore(root)
    lazy, m = RestoreManager(store).restore(lazy=True, sharding_for=sharding_for)
    return eager, lazy, store, m


@pytest.mark.parametrize("cpus", [1, 4], indirect=True)
@pytest.mark.parametrize("sharding_for", [None, _single_device], ids=["host", "device"])
def test_lazy_restore_of_many_chunk_leaves_matches_eager(tmp_path, cpus, sharding_for):
    state = {f"l{i}": _state() for i in range(2)}
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec=ZSTD_OR_GZIP, chunk_bytes=CHUNK)
    eager, lazy, store, m = _lazy_and_eager(str(tmp_path), sharding_for)
    try:
        tree = lazy.as_tree()
    finally:
        lazy.close()
    want = flatten_with_paths(eager)[0]
    for path, leaf in flatten_with_paths(tree)[0].items():
        assert _bits(leaf) == _bits(want[path]), path
    assert store.chunks_in_place == store.chunks_read == _n_chunks(m)
    assert not _decode_threads()


@pytest.mark.parametrize("cpus", [4, 64], indirect=True)
def test_concurrent_lazy_readers_decode_each_chunk_once(tmp_path, cpus):
    """Six readers, four read-ahead threads and their decode pools, more
    threads than cores, with the interpreter switching threads often."""
    state = {f"l{i}": _state() for i in range(3)}
    save_pytree(state, ChunkStore(str(tmp_path)), 1, codec=ZSTD_OR_GZIP, chunk_bytes=CHUNK)
    eager, lazy, store, m = _lazy_and_eager(str(tmp_path), None)
    want = {p: _bits(v) for p, v in flatten_with_paths(eager)[0].items()}
    keys = lazy.keys()
    got, errors = [], []
    barrier = threading.Barrier(6)

    def reader(order):
        barrier.wait()
        try:
            got.append({p: _bits(lazy[p]) for p in order})
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(keys[k:] + keys[:k],))
               for k in range(0, 6)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
        lazy.close()
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(got) == 6 and all(g == want for g in got)
    assert store.chunks_in_place == store.chunks_read == _n_chunks(m)
