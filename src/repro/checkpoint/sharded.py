"""Sharded, topology-independent save/restore of JAX pytrees.

The CRUM principle applied to SPMD: the checkpoint image must contain *no
device state*. Leaves are stored as global logical arrays; every host writes
only the shards it owns (``addressable_shards`` with ``replica_id == 0``),
keyed by their global index ranges. Restore targets **any** mesh: each
target shard is assembled from whichever stored shards overlap its index
domain — the elastic-restart analogue of "checkpoint on one CUDA/GPU
version, restart on another" (§3.1 of the paper).
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Callable

import jax
import numpy as np

import ml_dtypes  # noqa: F401  (registers bfloat16 & friends with numpy)

from repro.checkpoint.codecs import DEFAULT_CODEC
from repro.checkpoint.chunking import (
    DEFAULT_CHUNK_BYTES,
    chunk_digest_np,
    iter_chunks,
)
from repro.checkpoint.manifest import (
    LeafRecord,
    Manifest,
    ShardRecord,
    build_skeleton,
    commit_manifest,
    load_manifest,
    skeleton_fill,
)
from repro.checkpoint.store import ChunkStore
from repro.utils.tree import flatten_with_paths


def _np_dtype(name: str) -> np.dtype:
    return np.dtype(name)  # ml_dtypes registers bfloat16 etc.


def host_slice_plan(
    path: str, shape: tuple[int, ...], host: int, n_hosts: int
) -> tuple[list[int], list[int]] | None:
    """The global [start, stop) window ``host`` of ``n_hosts`` owns.

    THE ownership rule of the simulated cluster, defined once so persist
    (``coord.worker.shard_tree_for_host``) and elastic restore
    (``RestoreManager.restore_elastic``) can never drift apart:

      - a leaf whose leading dimension is >= n_hosts splits contiguously
        along dim 0, ``(host * n0) // n_hosts`` style — non-divisible
        splits give some hosts one extra row, never gaps or overlaps;
      - smaller leaves and scalars are whole-owned by a stable hash of
        their path (exactly one host persists each byte);
      - returns None when this host owns nothing of the leaf.
    """
    shape = tuple(int(d) for d in shape)
    if n_hosts <= 0:
        raise ValueError(f"n_hosts must be positive, got {n_hosts}")
    if not 0 <= host < n_hosts:
        raise ValueError(f"host {host} outside [0, {n_hosts})")
    if len(shape) >= 1 and shape[0] >= n_hosts:
        n0 = shape[0]
        lo = (host * n0) // n_hosts
        hi = ((host + 1) * n0) // n_hosts
        return [lo] + [0] * (len(shape) - 1), [hi] + list(shape[1:])
    if zlib.crc32(path.encode()) % n_hosts == host:
        return [0] * len(shape), list(shape)
    return None


def _shard_index_to_ranges(index: tuple, shape: tuple[int, ...]) -> tuple[list, list]:
    start, stop = [], []
    for sl, dim in zip(index, shape):
        start.append(0 if sl.start is None else int(sl.start))
        stop.append(dim if sl.stop is None else int(sl.stop))
    return start, stop


def _owned_shards(arr: jax.Array) -> list[tuple[list, list, np.ndarray]]:
    """(start, stop, data) for shards this host is responsible for writing."""
    out = []
    for sh in arr.addressable_shards:
        if sh.replica_id != 0:
            continue  # replicas: exactly one device owns each index domain
        start, stop = _shard_index_to_ranges(sh.index, arr.shape)
        out.append((start, stop, np.asarray(sh.data)))
    return out


def _leaf_shards(leaf: Any) -> tuple[tuple[int, ...], np.dtype, list]:
    if isinstance(leaf, jax.Array):
        return tuple(leaf.shape), np.dtype(leaf.dtype), _owned_shards(leaf)
    arr = np.asarray(leaf)
    start = [0] * arr.ndim
    stop = list(arr.shape)
    return tuple(arr.shape), arr.dtype, [(start, stop, arr)]


def _prev_digest_map(prev: Manifest | None) -> dict[tuple, "object"]:
    """(path, start, stop, chunk_idx) -> ChunkRecord from a prior manifest."""
    if prev is None:
        return {}
    out = {}
    for path, lv in prev.leaves.items():
        for s in lv.shards:
            for c in s.chunks:
                out[(path, tuple(s.start), tuple(s.stop), c.index)] = c
    return out


def save_pytree(
    state: Any,
    store: ChunkStore,
    step: int,
    *,
    codec: str = DEFAULT_CODEC,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    host: int = 0,
    prev_manifest: Manifest | None = None,
    meta: dict | None = None,
    commit: bool = True,
    fsync: bool = False,
) -> Manifest:
    """Write this host's shards of ``state``; commit the manifest.

    ``prev_manifest`` enables incremental checkpoints: chunks whose digest
    matches the previous image are *referenced*, not rewritten.
    """
    flat, _ = flatten_with_paths(state)
    skeleton = build_skeleton(state)
    prev = _prev_digest_map(prev_manifest)

    manifest = Manifest(step=step, skeleton=skeleton, meta=meta or {})
    writer = store.writer(step, host)
    reused = written = 0
    try:
        for path, leaf in flat.items():
            shape, dtype, shards = _leaf_shards(leaf)
            lrec = LeafRecord(path=path, shape=list(shape), dtype=dtype.name)
            for start, stop, data in shards:
                srec = ShardRecord(start=start, stop=stop)
                for key, raw in iter_chunks(path, data, chunk_bytes):
                    digest = chunk_digest_np(raw)
                    old = prev.get((path, tuple(start), tuple(stop), key.index))
                    if old is not None and old.digest == digest and old.raw_len == len(raw):
                        srec.chunks.append(old)  # delta reference
                        reused += 1
                    else:
                        srec.chunks.append(
                            writer.append(raw, codec, index=key.index, digest=digest)
                        )
                        written += 1
                lrec.shards.append(srec)
            manifest.leaves[path] = lrec
    finally:
        writer.close(fsync=fsync)
    manifest.meta.setdefault("chunks_written", written)
    manifest.meta.setdefault("chunks_reused", reused)
    if commit:
        # directory durability tracks the payload fsync knob (see manifest
        # .fsync_dir): dir fsyncs without payload fsyncs buy nothing
        commit_manifest(store.root, manifest, durable=fsync)
    return manifest


# --------------------------------------------------------------------------
# Restore
# --------------------------------------------------------------------------

class _LeafAssembler:
    """Assembles arbitrary index-windows of one stored leaf.

    Each stored shard is decoded once per assembler, chunk by chunk
    straight into one array (the decodes on threads of the store's, while
    this thread reads). A window that is exactly a stored shard gets
    that array itself the first time it is asked for (the single-device
    restore, host leaves, a same-topology mesh restore); every other window
    (elastic, over several shards or part of one, a replica asked for
    again) is copied out of the shards it overlaps.
    """

    def __init__(self, store: ChunkStore, lrec: LeafRecord):
        self.store = store
        self.lrec = lrec
        self.shape = tuple(lrec.shape)
        self.dtype = _np_dtype(lrec.dtype)
        self._shard_cache: dict[int, np.ndarray] = {}

    def _shard_array(self, i: int) -> np.ndarray:
        """Stored shard ``i``, each chunk decoded into its bytes of one array."""
        if i in self._shard_cache:
            return self._shard_cache[i]
        s = self.lrec.shards[i]
        out = np.empty(tuple(b - a for a, b in zip(s.start, s.stop)), dtype=self.dtype)
        buf = memoryview(out.reshape(-1).view(np.uint8))
        held = sum(c.raw_len for c in s.chunks)
        if held != len(buf):
            raise IOError(
                f"chunks of {self.lrec.path} shard {s.start}:{s.stop} hold "
                f"{held} bytes, not {len(buf)}"
            )
        self.store.read_chunks_into(s.chunks, buf)
        self._shard_cache[i] = out
        return out

    def window(self, start: list[int], stop: list[int]) -> np.ndarray:
        """Assemble the [start, stop) window from overlapping stored shards."""
        for i, s in enumerate(self.lrec.shards):
            if (list(s.start), list(s.stop)) == (list(start), list(stop)):
                if i not in self._shard_cache:
                    return self._shard_array(i)
                break
        out_shape = tuple(b - a for a, b in zip(start, stop))
        out = np.empty(out_shape, dtype=self.dtype)
        filled = 0
        for i, s in enumerate(self.lrec.shards):
            lo = [max(a, sa) for a, sa in zip(start, s.start)]
            hi = [min(b, sb) for b, sb in zip(stop, s.stop)]
            if any(l >= h for l, h in zip(lo, hi)):
                continue
            src = self._shard_array(i)[
                tuple(slice(l - sa, h - sa) for l, h, sa in zip(lo, hi, s.start))
            ]
            out[tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, start))] = src
            filled += src.size
        if filled < int(np.prod(out_shape, dtype=np.int64)):
            raise ValueError(
                f"stored shards do not cover window {start}:{stop} of "
                f"{self.lrec.path} (covered {filled})"
            )
        return out

    def full(self) -> np.ndarray:
        return self.window([0] * len(self.shape), list(self.shape))


def _normalize_index(index: tuple, shape: tuple[int, ...]) -> tuple[list, list]:
    start, stop = [], []
    for sl, dim in zip(index, shape):
        start.append(0 if sl.start is None else int(sl.start))
        stop.append(dim if sl.stop is None else int(sl.stop))
    return start, stop


def restore_leaf(
    store: ChunkStore,
    lrec: LeafRecord,
    sharding: jax.sharding.Sharding | None,
) -> Any:
    """Restore one leaf, optionally placing it with the given sharding."""
    asm = _LeafAssembler(store, lrec)
    shape = asm.shape

    def cb(index: tuple) -> np.ndarray:
        start, stop = _normalize_index(index, shape)
        n = int(np.prod([b - a for a, b in zip(start, stop)], dtype=np.int64))
        with store.timings.measure("restore/assemble", bytes=n * asm.dtype.itemsize):
            return asm.window(start, stop)

    if sharding is None:
        return cb((slice(None),) * len(shape))

    return jax.make_array_from_callback(shape, sharding, cb)


def restore_pytree(
    store: ChunkStore,
    step: int,
    shardings: Any = None,
    *,
    verify_digests: bool = False,
) -> tuple[Any, Manifest]:
    """Restore the full pytree saved at ``step``.

    ``shardings`` is either None (host numpy arrays), a single Sharding
    applied to all leaves, or a pytree matching the saved structure whose
    leaves are Shardings/None.
    """
    manifest = load_manifest(store.root, step)
    if verify_digests:
        verify_manifest(store, manifest)

    flat_sh: dict[str, Any] = {}
    if shardings is not None and not isinstance(shardings, jax.sharding.Sharding):
        flat_sh, _ = flatten_with_paths(shardings)

    def sh_for(path: str):
        if shardings is None:
            return None
        if isinstance(shardings, jax.sharding.Sharding):
            return shardings
        return flat_sh.get(path)

    leaves = {
        path: restore_leaf(store, lrec, sh_for(path))
        for path, lrec in manifest.leaves.items()
    }
    return skeleton_fill(manifest.skeleton, leaves), manifest


def restore_pytree_elastic(
    store: ChunkStore,
    step: int,
    make_sharding: Callable[[str, tuple[int, ...]], jax.sharding.Sharding | None],
) -> tuple[Any, Manifest]:
    """Elastic restore: target shardings chosen per-(path, shape) callback."""
    manifest = load_manifest(store.root, step)
    leaves = {
        path: restore_leaf(store, lrec, make_sharding(path, tuple(lrec.shape)))
        for path, lrec in manifest.leaves.items()
    }
    return skeleton_fill(manifest.skeleton, leaves), manifest


def verify_manifest(store: ChunkStore, manifest: Manifest) -> None:
    """Integrity pass: re-digest every chunk on disk (paper's 'verified mode')."""
    for lv in manifest.leaves.values():
        for s in lv.shards:
            for c in s.chunks:
                raw = store.read_chunk(c)
                d = chunk_digest_np(raw)
                if d != c.digest:
                    raise IOError(
                        f"digest mismatch for {lv.path} shard {s.start}:{s.stop} "
                        f"chunk {c.index}: {d:#x} != {c.digest:#x}"
                    )
