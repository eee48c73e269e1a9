"""ShadowStateManager — Algorithm 1 adapted to TPU/JAX.

CRUM's shadow UVM pages keep an application-side copy of device memory in
sync lazily, driven by page faults. On TPU there are no page faults to hook,
but the structure of the algorithm survives intact once "page" becomes
"chunk" and "fault" becomes "digest mismatch at a sync point":

    paper (Algorithm 1)                 here
    -----------------------------       ------------------------------------
    CUDA kernel launch marks pages      train step marks all chunks
    writable-by-device                  DEVICE_DIRTY (conservative)
    read fault on a shadow page ->      sync(): device-side digest compare;
    ReadDataFromRealPage()              only mismatching chunks are fetched
    write fault -> MarkPageAsDirty()    host mutation marks HOST_DIRTY
    CUDA call -> SendDataToRealPages()  upload(): HOST_DIRTY chunks pushed
                                        back to device (restore path)

The digest compare runs *on device* (Pallas ``chunk_digest`` kernel on TPU,
jnp fallback elsewhere): only the (n_chunks, 2)-u32 digest tensor crosses
the wire before any data does, so clean chunks cost nothing to skip — the
same economy CRUM gets from not faulting untouched pages.
"""
from __future__ import annotations

import enum
import mmap
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint.chunking import (
    DEFAULT_CHUNK_BYTES,
    chunk_digest_np,
    num_chunks,
)
from repro.obs import trace as obs_trace
from repro.utils.timing import Timings
from repro.utils.tree import flatten_with_paths, unflatten_from_paths


class ChunkState(enum.Enum):
    CLEAN = "clean"              # shadow == device
    DEVICE_DIRTY = "device_dirty"  # device may have advanced; shadow stale
    HOST_DIRTY = "host_dirty"    # shadow mutated on host; device stale


@dataclass
class _ShardStream:
    """One owned shard of one leaf, viewed as a byte stream of chunks."""

    path: str
    shard_ordinal: int
    start: list[int]
    stop: list[int]
    nbytes: int
    n_chunks: int
    states: list[ChunkState]
    digests: list[int]                    # digest of current *shadow* content
    buffer: np.ndarray | None = None      # host shadow bytes (u8), lazily alloc'd
    # True: the current DEVICE_DIRTY marks are page-granular truth (a
    # ManagedSpace's write_tick history), so sync may fetch exactly those
    # chunks and skip the digest compare entirely. Reset by every sync.
    precise: bool = False


@dataclass
class SyncStats:
    chunks_total: int = 0
    chunks_fetched: int = 0
    bytes_total: int = 0
    bytes_fetched: int = 0
    leaves: int = 0
    # exactly which chunks this sync materialized, keyed (path, ordinal) —
    # the streamed proxy transport forwards precisely these chunk payloads
    # to the application, so wire bytes track what actually changed
    changed: dict[tuple[str, int], list[int]] = field(default_factory=dict)
    # which sync epoch produced this image (-1: unepoched / legacy barrier)
    epoch: int = -1
    # phase breakdown: time spent hashing device chunks vs moving bytes —
    # fused digesting (digests computed inside the step) drives digest_us
    # toward zero, which is what the pipeline benchmarks assert
    digest_us: float = 0.0
    fetch_us: float = 0.0
    # chunks whose digest the step already supplied (no boundary scan)
    chunks_prehashed: int = 0

    def merge(self, other: "SyncStats") -> None:
        self.chunks_total += other.chunks_total
        self.chunks_fetched += other.chunks_fetched
        self.bytes_total += other.bytes_total
        self.bytes_fetched += other.bytes_fetched
        self.leaves += other.leaves
        self.changed.update(other.changed)
        self.digest_us += other.digest_us
        self.fetch_us += other.fetch_us
        self.chunks_prehashed += other.chunks_prehashed


@dataclass
class UploadStats:
    """What ``upload()`` pushed host->device (paper: SendDataToRealPages)."""

    chunks_uploaded: int = 0
    bytes_uploaded: int = 0
    leaves_touched: int = 0
    # per-stream bytes pushed, keyed (path, shard_ordinal) — the proxy
    # replay path reports these so recovery cost is attributable per leaf
    per_stream: dict[tuple[str, int], int] = field(default_factory=dict)


class HostShardView:
    """A host-owned slice of a globally-sharded leaf (simulated multi-host).

    In the cluster protocol every worker process holds the full replicated
    state but *persists* only its assigned global index range — the same
    ownership split ``addressable_shards``/``replica_id`` gives a real
    multi-host jax.Array. ``shape``/``dtype`` describe the **global** leaf
    (what the merged manifest records); ``data`` is this host's slice, or
    None when the host owns nothing of the leaf (the owner's hostmeta
    supplies it at merge time).
    """

    __slots__ = ("data", "start", "stop", "_shape", "_dtype")

    def __init__(self, data, *, start=None, stop=None,
                 global_shape=None, dtype=None):
        self.data = None if data is None else np.ascontiguousarray(data)
        self.start = list(start) if start is not None else None
        self.stop = list(stop) if stop is not None else None
        if global_shape is None:
            if self.data is None:
                raise ValueError("unowned HostShardView needs global_shape")
            global_shape = self.data.shape
        self._shape = tuple(int(d) for d in global_shape)
        self._dtype = np.dtype(dtype if dtype is not None else self.data.dtype)

    @property
    def shape(self) -> tuple[int, ...]:
        return self._shape

    @property
    def dtype(self) -> np.dtype:
        return self._dtype


def _owned_host_shards(leaf: Any):
    """(ordinal, start, stop, np_data) for shards this host owns."""
    if isinstance(leaf, HostShardView):
        if leaf.data is None:
            return []
        start = leaf.start if leaf.start is not None else [0] * leaf.data.ndim
        stop = leaf.stop if leaf.stop is not None else list(leaf.data.shape)
        return [(0, list(start), list(stop), leaf.data)]
    if isinstance(leaf, jax.Array):
        out = []
        ordinal = 0
        for sh in leaf.addressable_shards:
            if sh.replica_id != 0:
                continue
            start, stop = [], []
            for sl, dim in zip(sh.index, leaf.shape):
                start.append(0 if sl.start is None else int(sl.start))
                stop.append(dim if sl.stop is None else int(sl.stop))
            out.append((ordinal, start, stop, sh.data))
            ordinal += 1
        return out
    arr = np.asarray(leaf)
    return [(0, [0] * arr.ndim, list(arr.shape), arr)]


class ShadowStateManager:
    """Maintains a host shadow of an on-device state pytree.

    One manager owns one shadow buffer set. The forked checkpointer holds
    two managers (double buffering) so persisting snapshot A never blocks
    filling snapshot B.
    """

    def __init__(
        self,
        *,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
        digest_on_device: bool = True,
        defer_first_digests: bool = False,
        shared_buffers: bool = False,
        segment_factory: Callable[[tuple[str, int], int], np.ndarray] | None = None,
        timings: Timings | None = None,
    ):
        self.chunk_bytes = int(chunk_bytes)
        self.digest_on_device = digest_on_device
        # True: first sync skips the digest pass (a persist phase will
        # backfill via set_digests) — used by ForkedCheckpointer
        self.defer_first_digests = defer_first_digests
        # True: shadow buffers live in anonymous MAP_SHARED mmap segments.
        # Across an os.fork() the pages are *shared*, not COW-duplicated, so
        # a persist child reads the snapshot at zero copy cost and the
        # parent's later writes to *other* buffers never trigger page
        # copies — the paper's fork-and-persist economics. The caller must
        # not mutate a buffer while a child is persisting it (the forked
        # checkpointer's busy-buffer discipline guarantees this).
        self.shared_buffers = shared_buffers
        # Pluggable buffer allocation: (key, nbytes) -> u8 array. The device
        # proxy passes a factory that maps file-backed MAP_SHARED segments,
        # making the shadow buffers themselves the cross-process data plane
        # (step inputs/outputs never pickle through the control pipe).
        self.segment_factory = segment_factory
        self.timings = timings or Timings()
        self._streams: dict[tuple[str, int], _ShardStream] = {}
        self._mmaps: list[mmap.mmap] = []
        self._registered = False
        # pin/retire: a persisting fork child may still be reading the
        # MAP_SHARED pages of a buffer generation that register() replaces;
        # retired generations are released only once the pin count drops to 0
        self._pin_lock = threading.Lock()
        self._pins = 0
        self._retired: list[tuple[dict, list]] = []
        # buffer generation: bumped by register() so a digest backfill from
        # a persist of the *previous* generation can be recognized and
        # dropped instead of installing stale digests into fresh streams
        self.generation = 0
        # sync epochs: each begin_sync_epoch() names one step-boundary
        # image. The epoch is carried through SyncStats (and, in the proxy,
        # through SYNCED frames) so a caller that pipelines SYNC behind the
        # next STEP can match images to boundaries asynchronously instead
        # of treating every sync as a barrier.
        self.sync_epoch = 0

    def _alloc_buffer(self, nbytes: int, key: tuple[str, int] | None = None) -> np.ndarray:
        if self.segment_factory is not None and key is not None:
            return self.segment_factory(key, nbytes)
        if self.shared_buffers and nbytes > 0:
            mm = mmap.mmap(-1, nbytes)  # anonymous + MAP_SHARED on POSIX
            self._mmaps.append(mm)
            return np.frombuffer(mm, dtype=np.uint8, count=nbytes)
        return np.empty(nbytes, np.uint8)

    # -- buffer generation pinning ------------------------------------------------
    def pin(self) -> None:
        """A consumer (e.g. a forked persist child's parent-side job) still
        reads the current buffer generation: re-registration must not release
        it. Balanced by :meth:`unpin`."""
        with self._pin_lock:
            self._pins += 1

    def unpin(self) -> None:
        with self._pin_lock:
            self._pins = max(0, self._pins - 1)
            if self._pins == 0 and self._retired:
                retired, self._retired = self._retired, []
            else:
                retired = []
        for streams, mmaps in retired:
            self._drop_generation(streams, mmaps)

    @staticmethod
    def _drop_generation(streams: dict, mmaps: list) -> None:
        """Release one buffer generation: sever the stream->buffer views so
        the mmaps can actually close (a view held elsewhere — e.g. a
        persist job's snapshot dict — downgrades close to GC-time)."""
        for s in streams.values():
            s.buffer = None
        for mm in mmaps:
            try:
                mm.close()
            except (BufferError, ValueError):  # a view still alive: GC frees
                pass

    # -- registration ---------------------------------------------------------
    def register(self, state: Any) -> None:
        """Learn the chunk layout of ``state``; all chunks start DEVICE_DIRTY.

        Re-registration retires (rather than releases) the previous buffer
        generation while any consumer holds a pin — a persisting fork child
        may still be reading those MAP_SHARED pages.
        """
        flat, _ = flatten_with_paths(state)
        with self._pin_lock:
            old_streams, old_mmaps = self._streams, self._mmaps
            retire = self._pins > 0 and bool(old_streams or old_mmaps)
            if retire:
                self._retired.append((old_streams, old_mmaps))
            self._streams = {}
            self._mmaps = []
        if not retire:
            self._drop_generation(old_streams, old_mmaps)
        for path, leaf in flat.items():
            for ordinal, start, stop, data in _owned_host_shards(leaf):
                nbytes = int(np.asarray(data).nbytes) if not isinstance(
                    data, jax.Array
                ) else int(np.prod(data.shape, dtype=np.int64)) * data.dtype.itemsize
                nc = num_chunks(nbytes, self.chunk_bytes)
                self._streams[(path, ordinal)] = _ShardStream(
                    path=path,
                    shard_ordinal=ordinal,
                    start=start,
                    stop=stop,
                    nbytes=nbytes,
                    n_chunks=nc,
                    states=[ChunkState.DEVICE_DIRTY] * nc,
                    digests=[-1] * nc,
                )
        self.generation += 1
        self._registered = True

    # -- Algorithm-1 events -----------------------------------------------------
    def mark_device_step(self, marks: dict[str, list[int]] | None = None) -> None:
        """Paper: a CUDA call may mutate real pages -> mark shadows stale.

        Without ``marks`` every CLEAN chunk becomes DEVICE_DIRTY (the
        conservative pre-UVM behaviour: any step may have touched any
        byte). With ``marks`` — ``{path: chunk indices}`` from a managed
        space's page-granular write history — a path present in the dict
        gets *exactly* those chunks marked, flagged ``precise`` so the next
        sync fetches them without a digest scan; paths absent from the dict
        (e.g. host-side leaves outside the managed space) stay
        conservative. Precision only applies to single-stream (whole-leaf,
        ordinal-0) paths; sharded leaves fall back to the digest path,
        whose chunk indexing is per-shard, not per-leaf.
        """
        if marks is not None:
            per_path: dict[str, int] = {}
            for p, _ in self._streams:
                per_path[p] = per_path.get(p, 0) + 1
        for (path, ordinal), s in self._streams.items():
            idx = marks.get(path) if marks is not None else None
            if idx is not None and ordinal == 0 and per_path.get(path) == 1:
                for i in idx:
                    if 0 <= i < s.n_chunks and s.states[i] is ChunkState.CLEAN:
                        s.states[i] = ChunkState.DEVICE_DIRTY
                s.precise = True
            else:
                for i, st in enumerate(s.states):
                    if st is ChunkState.CLEAN:
                        s.states[i] = ChunkState.DEVICE_DIRTY
                s.precise = False

    def mark_host_write(self, path: str) -> None:
        """Paper: write fault on a shadow page -> HOST_DIRTY."""
        for (p, _), s in self._streams.items():
            if p == path:
                s.states = [ChunkState.HOST_DIRTY] * s.n_chunks

    def mark_host_chunks(self, path: str, indices: list[int], *, ordinal: int = 0) -> None:
        """Chunk-granular host-write marks (the proxy's delta-UPLOAD path):
        only the listed chunks will be pushed by the next ``upload()``."""
        s = self._streams.get((path, ordinal))
        if s is None:
            raise KeyError(f"no stream for {(path, ordinal)}")
        for i in indices:
            if 0 <= i < s.n_chunks:
                s.states[i] = ChunkState.HOST_DIRTY

    # -- sync (the read-fault path, batched) ------------------------------------
    def begin_sync_epoch(self) -> int:
        """Open a new sync epoch and return its number.

        An epoch names one step-boundary image: the caller issues
        ``begin_sync_epoch()`` at the boundary, keeps stepping, and runs
        ``sync(state, epoch=...)`` against the boundary state while the
        *next* step mutates the live buffers — the double-buffered overlap
        the proxy's pipelined SYNC{epoch} is built on.
        """
        self.sync_epoch += 1
        return self.sync_epoch

    def sync(
        self,
        state: Any,
        *,
        epoch: int | None = None,
        device_digests: dict[str, list[int]] | None = None,
    ) -> SyncStats:
        """Bring the shadow up to date with the device; returns transfer stats.

        Only chunks whose device digest differs from the shadow digest are
        materialized on host — CRUM's read-fault economy at chunk scale.

        ``device_digests`` ({path: per-chunk u64 digests}) are digests the
        step program already computed as a fused final pass: a listed path
        skips the boundary digest scan entirely and compares the supplied
        digests against the shadow's. They compose with page-granular
        ``precise`` marks (the intersection is fetched) instead of racing
        them. Like precise marks, they apply only to single-stream
        (whole-leaf, ordinal-0) paths; sharded leaves fall back to the
        scan, whose chunk indexing is per-shard.
        """
        tr = obs_trace.get()
        t0 = time.perf_counter() if tr is not None else 0.0
        if not self._registered:
            self.register(state)
        flat, _ = flatten_with_paths(state)
        per_path: dict[str, int] = {}
        if device_digests:
            for p, _o in self._streams:
                per_path[p] = per_path.get(p, 0) + 1
        stats = SyncStats(epoch=epoch if epoch is not None else self.sync_epoch)
        for path, leaf in flat.items():
            for ordinal, start, stop, data in _owned_host_shards(leaf):
                stream = self._streams.get((path, ordinal))
                if stream is None:  # new leaf appeared: register on the fly
                    self.register(state)
                    stream = self._streams[(path, ordinal)]
                known = None
                if (
                    device_digests
                    and ordinal == 0
                    and per_path.get(path) == 1
                ):
                    k = device_digests.get(path)
                    if k is not None and len(k) == stream.n_chunks:
                        known = [int(d) for d in k]
                st = self._sync_stream(stream, data, known=known)
                stats.merge(st)
            stats.leaves += 1
        if tr is not None:
            tr.complete("shadow.sync", t0, epoch=stats.epoch,
                        chunks_fetched=stats.chunks_fetched,
                        bytes_fetched=stats.bytes_fetched,
                        prehashed=stats.chunks_prehashed)
        return stats

    def _sync_stream(
        self, stream: _ShardStream, data: Any, known: list[int] | None = None
    ) -> SyncStats:
        stats = SyncStats(
            chunks_total=stream.n_chunks, bytes_total=stream.nbytes
        )
        if stream.buffer is None:
            # first sync: everything must move regardless — bulk copy; the
            # digest pass is skipped when a persist phase will backfill it
            stream.precise = False
            t0 = time.perf_counter()
            with self.timings.measure("shadow/fetch"):
                stream.buffer = self._alloc_buffer(
                    stream.nbytes, (stream.path, stream.shard_ordinal)
                )
                self._copy_all(data, stream)
                stream.states = [ChunkState.CLEAN] * stream.n_chunks
                stats.chunks_fetched = stream.n_chunks
                stats.bytes_fetched = stream.nbytes
                stats.changed[(stream.path, stream.shard_ordinal)] = list(
                    range(stream.n_chunks)
                )
            stats.fetch_us += (time.perf_counter() - t0) * 1e6
            if known is not None:
                stream.digests = list(known)
                stats.chunks_prehashed += stream.n_chunks
            elif self.defer_first_digests:
                stream.digests = [-2] * stream.n_chunks  # pending backfill
            else:
                t0 = time.perf_counter()
                with self.timings.measure("shadow/digest"):
                    stream.digests = self._device_digests(data, stream)
                stats.digest_us += (time.perf_counter() - t0) * 1e6
            return stats
        dirty = [
            i for i, st in enumerate(stream.states)
            if st is ChunkState.DEVICE_DIRTY
        ]
        precise, stream.precise = stream.precise, False
        if not dirty:
            return stats

        if known is not None:
            # fused digests: the step already hashed the chunks, so the
            # boundary compare is pure bookkeeping (not counted as digest
            # time — no hash runs here) — and it *composes* with
            # page-granular marks: only chunks that are both marked dirty
            # AND hash-changed are fetched (shadow digests still unknown
            # from a deferred first sync count as changed)
            keep = {
                i for i in dirty
                if stream.digests[i] < 0 or known[i] != stream.digests[i]
            }
            changed = sorted(keep)
            for i in dirty:
                if i not in keep:
                    stream.states[i] = ChunkState.CLEAN
            dev_digests = known
            stats.chunks_prehashed += len(dirty)
        elif precise:
            # page-granular marks are authoritative: fetch exactly them, no
            # digest scan over the (mostly clean) rest of the leaf — the
            # whole point of the UVM dirty-bit integration
            dev_digests = None
            changed = dirty
        else:
            t0 = time.perf_counter()
            with self.timings.measure("shadow/digest"):
                dev_digests = self._device_digests(data, stream)
            stats.digest_us += (time.perf_counter() - t0) * 1e6

            changed = [
                i for i in dirty if dev_digests[i] != stream.digests[i]
            ]
            # unchanged-but-marked chunks are clean after the compare
            for i in dirty:
                if i not in changed:
                    stream.states[i] = ChunkState.CLEAN

        if not changed:
            return stats
        stats.changed[(stream.path, stream.shard_ordinal)] = sorted(changed)

        t_fetch = time.perf_counter()
        with self.timings.measure("shadow/fetch"):
            if stream.buffer is None:
                stream.buffer = self._alloc_buffer(
                    stream.nbytes, (stream.path, stream.shard_ordinal)
                )
            cb = self.chunk_bytes
            if len(changed) == stream.n_chunks:
                # everything dirty (first sync / full update): one bulk copy
                self._copy_all(data, stream)
                if dev_digests is not None:
                    stream.digests = list(dev_digests)
                else:
                    stream.digests = [
                        chunk_digest_np(
                            stream.buffer[i * cb : min(stream.nbytes, (i + 1) * cb)]
                        )
                        for i in range(stream.n_chunks)
                    ]
                stream.states = [ChunkState.CLEAN] * stream.n_chunks
                stats.chunks_fetched = stream.n_chunks
                stats.bytes_fetched = stream.nbytes
                stats.fetch_us += (time.perf_counter() - t_fetch) * 1e6
                return stats
            fetch = self._make_chunk_fetcher(data, stream, changed)
            for i in changed:
                lo, hi = i * cb, min(stream.nbytes, (i + 1) * cb)
                piece = fetch(i, lo, hi)
                with self.timings.measure("shadow/copy", bytes=hi - lo):
                    stream.buffer[lo:hi] = piece
                stream.digests[i] = (
                    dev_digests[i] if dev_digests is not None
                    else chunk_digest_np(stream.buffer[lo:hi])
                )
                stream.states[i] = ChunkState.CLEAN
                stats.chunks_fetched += 1
                stats.bytes_fetched += hi - lo
        stats.fetch_us += (time.perf_counter() - t_fetch) * 1e6
        return stats

    def _copy_all(self, data: Any, stream: _ShardStream) -> None:
        """The whole shard into its shadow buffer: the device-to-host
        transfer (``shadow/d2h``), then the host copy (``shadow/copy``)."""
        with self.timings.measure("shadow/d2h", bytes=stream.nbytes):
            host = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        with self.timings.measure("shadow/copy", bytes=stream.nbytes):
            np.copyto(stream.buffer, host)

    def _make_chunk_fetcher(self, data: Any, stream: _ShardStream, changed: list[int]):
        """Per-chunk device->host fetch: only dirty bytes cross the wire.

        When most chunks changed a single bulk fetch is cheaper than many
        small DMAs (the paper's exponential read-ahead argument, degenerated
        to its endpoint); below that threshold, chunks are fetched
        individually via on-device slices.
        """
        if (
            isinstance(data, jax.Array)
            and stream.n_chunks > 1
            and len(changed) <= stream.n_chunks // 2
        ):
            itemsize = np.dtype(data.dtype).itemsize
            flat = data.reshape(-1)

            def fetch(i: int, lo: int, hi: int) -> np.ndarray:
                with self.timings.measure("shadow/d2h", bytes=hi - lo):
                    piece = jax.device_get(flat[lo // itemsize : -(-hi // itemsize)])
                return piece.reshape(-1).view(np.uint8)[: hi - lo]

            return fetch
        with self.timings.measure("shadow/d2h", bytes=stream.nbytes):
            host = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        return lambda i, lo, hi: host[lo:hi]

    def _device_digests(self, data: Any, stream: _ShardStream) -> list[int]:
        if self.digest_on_device and isinstance(data, jax.Array):
            from repro.kernels.ops import chunk_digests

            d = np.asarray(chunk_digests(data, self.chunk_bytes))
            return [int((np.uint64(h) << np.uint64(32)) | np.uint64(l))
                    for h, l in zip(d[:, 0], d[:, 1])]
        host = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        cb = self.chunk_bytes
        return [
            chunk_digest_np(host[i * cb : min(stream.nbytes, (i + 1) * cb)])
            for i in range(stream.n_chunks)
        ]

    # -- upload (the write-back path: SendDataToRealPages) ---------------------
    def upload(self, state: Any) -> tuple[Any, UploadStats]:
        """Push HOST_DIRTY chunks back to the device; returns (state', stats).

        The paper's ``SendDataToRealPages()``: shadow content that the host
        mutated is written back before the device computes again. Only
        HOST_DIRTY chunk byte-ranges move; untouched chunks cost nothing.
        Returns a new state pytree (jax arrays are immutable, so patched
        leaves are rebuilt and re-placed with their original sharding) plus
        per-stream bytes-uploaded stats. This is also the device proxy's
        replay data-push primitive: after a proxy respawn, the last synced
        snapshot lives in the (shared-segment) shadow buffers and is pushed
        into the fresh proxy's device state through this path.
        """
        if not self._registered:
            raise RuntimeError("upload() before register()")
        flat, treedef = flatten_with_paths(state)
        stats = UploadStats()
        new_flat = dict(flat)
        cb = self.chunk_bytes
        for path, leaf in flat.items():
            shards = _owned_host_shards(leaf)
            dirty_streams = []
            for ordinal, start, stop, _data in shards:
                stream = self._streams.get((path, ordinal))
                if stream is None:
                    continue
                dirty = [
                    i for i, st in enumerate(stream.states)
                    if st is ChunkState.HOST_DIRTY
                ]
                if dirty:
                    dirty_streams.append((stream, start, stop, dirty))
            if not dirty_streams:
                continue
            stats.leaves_touched += 1
            with self.timings.measure("shadow/upload"):
                new_flat[path] = self._upload_leaf(
                    path, leaf, dirty_streams, cb, stats
                )
        return unflatten_from_paths(treedef, new_flat), stats

    def _upload_leaf(
        self, path: str, leaf: Any, dirty_streams: list, cb: int, stats: UploadStats
    ) -> Any:
        dtype = np.dtype(
            leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype
        )
        shape = tuple(
            leaf.shape if hasattr(leaf, "shape") else np.asarray(leaf).shape
        )
        is_jax = isinstance(leaf, jax.Array)
        if isinstance(leaf, HostShardView):
            # host-owned slice: patch the bytes in place, no rebuild needed
            for stream, _start, _stop, dirty in dirty_streams:
                buf = self._stream_buffer(stream)
                target = np.ascontiguousarray(leaf.data).reshape(-1).view(np.uint8)
                self._patch_chunks(stream, buf, target, dirty, cb, stats)
                leaf.data[...] = target.view(leaf.data.dtype).reshape(leaf.data.shape)
            return leaf

        full = (
            len(dirty_streams) == 1
            and list(dirty_streams[0][1]) == [0] * len(shape)
            and list(dirty_streams[0][2]) == list(shape)
            and len(dirty_streams[0][3]) == dirty_streams[0][0].n_chunks
        )
        if full:
            # everything dirty over the whole leaf: rebuild straight from
            # the shadow buffer, never fetching the stale device content
            stream, _s, _e, dirty = dirty_streams[0]
            buf = self._stream_buffer(stream)
            arr = buf.view(dtype).reshape(shape).copy()
            self._finish_upload(stream, buf, dirty, cb, stats)
        else:
            arr = np.array(np.asarray(leaf))  # host copy of the global leaf
            for stream, start, stop, dirty in dirty_streams:
                buf = self._stream_buffer(stream)
                idx = tuple(slice(a, b) for a, b in zip(start, stop))
                region = np.ascontiguousarray(arr[idx])
                target = region.reshape(-1).view(np.uint8)
                self._patch_chunks(stream, buf, target, dirty, cb, stats)
                arr[idx] = target.view(dtype).reshape(region.shape)
        if is_jax:
            try:
                return jax.device_put(arr, leaf.sharding)
            except Exception:
                return jax.numpy.asarray(arr)
        return arr

    def _stream_buffer(self, stream: _ShardStream) -> np.ndarray:
        if stream.buffer is None:
            # never synced: only meaningful when a segment factory can
            # attach existing shared content (the proxy replay path)
            if self.segment_factory is None:
                raise RuntimeError(
                    f"stream {(stream.path, stream.shard_ordinal)} has no "
                    "shadow content to upload"
                )
            stream.buffer = self._alloc_buffer(
                stream.nbytes, (stream.path, stream.shard_ordinal)
            )
        return stream.buffer

    def _patch_chunks(
        self,
        stream: _ShardStream,
        buf: np.ndarray,
        target: np.ndarray,
        dirty: list[int],
        cb: int,
        stats: UploadStats,
    ) -> None:
        for i in dirty:
            lo, hi = i * cb, min(stream.nbytes, (i + 1) * cb)
            target[lo:hi] = buf[lo:hi]
        self._finish_upload(stream, buf, dirty, cb, stats)

    def _finish_upload(
        self,
        stream: _ShardStream,
        buf: np.ndarray,
        dirty: list[int],
        cb: int,
        stats: UploadStats,
    ) -> None:
        pushed = 0
        for i in dirty:
            lo, hi = i * cb, min(stream.nbytes, (i + 1) * cb)
            stream.digests[i] = chunk_digest_np(buf[lo:hi])
            stream.states[i] = ChunkState.CLEAN
            pushed += hi - lo
        key = (stream.path, stream.shard_ordinal)
        stats.chunks_uploaded += len(dirty)
        stats.bytes_uploaded += pushed
        stats.per_stream[key] = stats.per_stream.get(key, 0) + pushed

    # -- snapshot access ----------------------------------------------------------
    def snapshot(self) -> dict[tuple[str, int], dict]:
        """The current shadow: {(path, ordinal): {start, stop, bytes}}.

        ``digests`` carries the per-chunk shadow digests where known
        (negative entries are the -1 "never computed" / -2 "backfill
        pending" sentinels): the persist path uses a known digest instead
        of re-hashing the chunk, so a page-delta sync is followed by a
        page-delta digest bill, not a full-state rescan.
        """
        out = {}
        for key, s in self._streams.items():
            if s.buffer is None:
                raise RuntimeError(f"stream {key} never synced")
            out[key] = {
                "start": s.start, "stop": s.stop, "data": s.buffer,
                "digests": list(s.digests),
            }
        return out

    def chunk_states(self) -> dict[tuple[str, int], list[ChunkState]]:
        return {k: list(s.states) for k, s in self._streams.items()}

    def digest_table(self) -> dict[str, list[int]] | None:
        """Full-state per-chunk digest view: {path: [u64 digests]}.

        Only meaningful when every stream is a whole leaf (ordinal 0 —
        the proxy-service registration shape) and every digest is known:
        returns None if any stream is a shard slice or still holds a
        negative sentinel, so callers never ship a partial table. Used
        for divergence provenance — these digests are comparable across
        hosts (same replicated state, same chunking).
        """
        out: dict[str, list[int]] = {}
        for (path, ordinal), s in self._streams.items():
            if ordinal != 0 or any(d < 0 for d in s.digests):
                return None
            out[path] = [int(d) for d in s.digests]
        return out or None

    def set_digests(
        self,
        key: tuple[str, int],
        digests: list[int],
        *,
        generation: int | None = None,
    ) -> None:
        """Backfill digests computed during persist (phase 2).

        ``generation`` (when given) must match the buffer generation the
        persist snapshotted: a backfill racing a re-registration would
        otherwise install the *old* generation's digests into fresh
        streams, and a later delta persist would silently reuse chunks
        against the wrong baseline.
        """
        if generation is not None and generation != self.generation:
            return
        s = self._streams.get(key)
        if s is not None and len(digests) == s.n_chunks:
            s.digests = list(digests)

    def invalidate(self) -> None:
        """Drop all shadow content (e.g., after restoring different weights)."""
        for s in self._streams.values():
            s.states = [ChunkState.DEVICE_DIRTY] * s.n_chunks
            s.digests = [-1] * s.n_chunks
