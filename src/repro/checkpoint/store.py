"""ChunkStore — per-host chunk payload files + read path + GC.

Each host appends its compressed chunks to a single ``data-h<host>.bin``
per checkpoint step (one sequential stream per host: the I/O pattern the
paper's forked child produces). Reads are random-access by (file, offset,
comp_len) from the manifest. ``read_chunk_into`` decodes a chunk straight
into the caller's buffer and keeps a read-only view of it, not a copy, in a
cache bounded by the bytes of the arrays those views keep alive; a hit is
copied out and used only if the copy still digests to the manifest's
digest, since the array's owner may have written to it.
``read_chunks_into`` does the same for a run of chunks, with their decodes
on threads of its own while the calling thread reads on (restore reads
every stored shard so). ``read_chunk`` decodes into bytes of its own,
uncached.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import time
from collections import OrderedDict, deque

import numpy as np

from repro.checkpoint.chunking import chunk_digest_np
from repro.checkpoint.codecs import get_codec
from repro.checkpoint.manifest import ChunkRecord, step_dir
from repro.utils.timing import Timings


def _held_bytes(view: memoryview) -> int:
    """Bytes a view keeps alive: the whole buffer it was cut from."""
    base = view.obj
    while getattr(base, "base", None) is not None:
        base = base.base
    return base.nbytes if isinstance(base, np.ndarray) else memoryview(base).nbytes


def host_data_file(step: int, host: int) -> str:
    """Path of a host's payload file, relative to the checkpoint root."""
    return os.path.join(f"step_{step:08d}", f"data-h{host:04d}.bin")


# a run of chunks decodes on one thread for each CPU this process may run on,
# at most this many, each at most _AHEAD chunks behind the reads
_MAX_DECODERS = 8
_AHEAD = 4


class ChunkStore:
    def __init__(self, root: str, *, cache_bytes: int = 256 << 20,
                 timings: Timings | None = None):
        self.root = root
        self.timings = timings or Timings()
        os.makedirs(root, exist_ok=True)
        # read-only views of in-place decodes, each charged the bytes of the
        # whole array it keeps alive; the charges sum to at most cache_bytes
        self._cache: OrderedDict[tuple, tuple[memoryview, int]] = OrderedDict()
        self._cache_bytes = cache_bytes
        self._cache_held = 0
        self._lock = threading.Lock()
        self.bytes_read = 0
        self.chunks_read = 0  # chunks decoded, in place or not
        self.chunks_in_place = 0  # of those, decoded into the caller's buffer
        self.decode_s = 0.0  # seconds of those in-place decodes, summed over threads

    # -- write path ---------------------------------------------------------
    class Writer:
        """Sequential appender for one host's payload file.

        With ``lazy=True`` construction records only the target path and the
        file descriptor is opened on first ``append``. This is the child-safe
        handoff for the fork persist backend: the parent builds the Writer
        (cheap, no fd) before ``os.fork()`` and only the child ever opens the
        file, so parent and child never share an fd offset.
        """

        def __init__(self, store: "ChunkStore", step: int, host: int,
                     *, lazy: bool = False):
            self.host = int(host)
            self.relpath = host_data_file(step, host)
            self._abspath = os.path.join(store.root, self.relpath)
            self._f = None
            self._off = 0
            if not lazy:
                self._open()

        def _open(self) -> None:
            os.makedirs(os.path.dirname(self._abspath), exist_ok=True)
            self._f = open(self._abspath, "wb")

        def append(self, raw: bytes, codec_name: str, *, index: int,
                   digest: int) -> ChunkRecord:
            if self._f is None:
                self._open()
            comp = get_codec(codec_name).compress(raw)
            if os.environ.get("CRUM_CHAOS_DIR"):
                # chaos shim (soak drills): an armed disk_full fault turns
                # this append into ENOSPC mid-persist. One env lookup on
                # every production run — the import never happens.
                from repro.chaos.faults import check_disk_quota

                check_disk_quota(self.host, len(comp), self._off)
            rec = ChunkRecord(
                index=index, raw_len=len(raw), digest=digest,
                codec=codec_name, file=self.relpath,
                file_offset=self._off, comp_len=len(comp),
            )
            self._f.write(comp)
            self._off += len(comp)
            return rec

        def close(self, *, fsync: bool = True) -> None:
            if self._f is None:  # lazy writer that never wrote
                return
            self._f.flush()
            if fsync:
                os.fsync(self._f.fileno())
            self._f.close()
            self._f = None

    def writer(self, step: int, host: int = 0, *, lazy: bool = False
               ) -> "ChunkStore.Writer":
        return ChunkStore.Writer(self, step, host, lazy=lazy)

    # -- read path ------------------------------------------------------------
    def _read_payload(self, rec: ChunkRecord) -> bytes:
        with self.timings.measure("store/read", bytes=rec.comp_len):
            with open(os.path.join(self.root, rec.file), "rb") as f:
                f.seek(rec.file_offset)
                comp = f.read(rec.comp_len)
        if len(comp) != rec.comp_len:
            raise IOError(
                f"short read for {rec.file}@{rec.file_offset}: "
                f"{len(comp)} < {rec.comp_len}"
            )
        return comp

    def _count(self, rec: ChunkRecord, *, in_place: bool, decode_s: float = 0.0) -> None:
        with self._lock:
            self.bytes_read += rec.raw_len
            self.chunks_read += 1
            self.chunks_in_place += in_place
            self.decode_s += decode_s

    def read_chunk(self, rec: ChunkRecord) -> bytes:
        """Read and decode one chunk into bytes of its own (no cache)."""
        comp = self._read_payload(rec)
        with self.timings.measure("store/decode", bytes=rec.raw_len):
            raw = get_codec(rec.codec).decompress(comp)
        if len(raw) != rec.raw_len:
            raise IOError(f"decompressed length mismatch for {rec.file}")
        self._count(rec, in_place=False)
        return raw

    def read_chunk_into(self, rec: ChunkRecord, out: memoryview) -> None:
        """Decode one chunk into ``out``, a byte view of ``rec.raw_len``
        bytes, with no intermediate buffer (a cache hit is copied there)."""
        if len(out) != rec.raw_len:
            raise ValueError(f"{len(out)}-byte buffer for a {rec.raw_len}-byte chunk")
        if self._copy_cached(rec, out):
            return
        comp = self._read_payload(rec)
        with self.timings.measure("store/decode", bytes=rec.raw_len):
            seconds = self._decode_into(rec, comp, out)
        self._decoded(rec, out, seconds)

    def read_chunks_into(self, recs: list[ChunkRecord], out: memoryview) -> None:
        """Decode ``recs`` into consecutive byte ranges of ``out``, as
        :meth:`read_chunk_into` does each, with the decodes on threads.

        This thread reads the chunks in order (``store/read``) and hands each
        decode to a thread of a pool made for this call, at most ``_AHEAD``
        chunks a thread ahead of the decodes; it then waits for each decode
        in order, and that wait is the chunk's ``store/decode``. So the
        store's timers still divide this thread's own time, and this thread
        counts and keeps each chunk. The pool is shut down before this
        returns or raises: a decode not started is cancelled, one started is
        waited for, so no thread writes into ``out`` afterwards, and none is
        alive at the fork persist backend's next fork.
        """
        views, off = [], 0
        for rec in recs:
            views.append(out[off : off + rec.raw_len])
            off += rec.raw_len
        if len(recs) < 2:
            for rec, view in zip(recs, views):
                self.read_chunk_into(rec, view)
            return
        workers = min(len(os.sched_getaffinity(0)), _MAX_DECODERS)
        pool = cf.ThreadPoolExecutor(workers, thread_name_prefix="crum-decode")
        pending: deque[tuple[ChunkRecord, memoryview, cf.Future]] = deque()

        def wait_oldest() -> None:
            rec, view, fut = pending.popleft()
            with self.timings.measure("store/decode", bytes=rec.raw_len):
                seconds = fut.result()
            self._decoded(rec, view, seconds)

        try:
            for rec, view in zip(recs, views):
                if len(pending) >= _AHEAD * workers:
                    wait_oldest()
                if self._copy_cached(rec, view):
                    continue
                comp = self._read_payload(rec)
                pending.append((rec, view, pool.submit(self._decode_into, rec, comp, view)))
            while pending:
                wait_oldest()
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    def _decode_into(self, rec: ChunkRecord, comp: bytes, out: memoryview) -> float:
        """Decode ``comp``, the payload of ``rec``, into ``out``; returns the
        seconds it took. Records no timer and touches no state of the store:
        a decode thread of :meth:`read_chunks_into` runs it."""
        t0 = time.perf_counter()
        codec = get_codec(rec.codec)
        try:
            n = codec.decompress_into(comp, out)
        except Exception as e:  # any codec's failure: name the chunk
            raise IOError(f"cannot decode {rec.file}@{rec.file_offset}: {e!r}") from e
        if n != rec.raw_len:
            raise IOError(
                f"decompressed length mismatch for {rec.file}@{rec.file_offset}: "
                f"{n} != {rec.raw_len}"
            )
        return time.perf_counter() - t0

    def _decoded(self, rec: ChunkRecord, out: memoryview, decode_s: float) -> None:
        self._count(rec, in_place=True, decode_s=decode_s)
        self._keep(rec, out.toreadonly())

    # -- cache of in-place decodes ----------------------------------------------
    def _keep(self, rec: ChunkRecord, view: memoryview) -> None:
        held = _held_bytes(view)
        if held > self._cache_bytes:
            return
        key = (rec.file, rec.file_offset, rec.comp_len)
        with self._lock:
            old = self._cache.pop(key, None)
            self._cache_held += held - (old[1] if old else 0)
            self._cache[key] = (view, held)
            while self._cache_held > self._cache_bytes:
                self._cache_held -= self._cache.popitem(last=False)[1][1]

    def _copy_cached(self, rec: ChunkRecord, out: memoryview) -> bool:
        """Copy a kept decode of ``rec`` into ``out``; False if there is none,
        or if what was copied no longer digests to the manifest's digest (the
        array it was decoded into has been written to since)."""
        key = (rec.file, rec.file_offset, rec.comp_len)
        with self._lock:
            entry = self._cache.get(key)
            if entry is None:
                return False
            self._cache.move_to_end(key)
        out[:] = entry[0]
        if chunk_digest_np(np.frombuffer(out, np.uint8)) == rec.digest:
            return True
        with self._lock:
            if self._cache.get(key) is entry:
                del self._cache[key]
                self._cache_held -= entry[1]
        return False

    # -- garbage collection ----------------------------------------------------
    def gc(self, keep_steps: list[int], *, pin_referenced: bool = True) -> list[int]:
        """Delete committed step dirs not in ``keep_steps``.

        Never deletes a step that a surviving delta manifest references.
        Policy callers already pass the transitive closure (see
        policy.gc_keep), but the store re-derives it itself
        (``pin_referenced``) as a safety net: a caller with a naive keep
        list — or a manifest committed between the caller's plan and this
        collection — must not strand an incremental chain. Safe against a
        concurrent collector on the same root (two trainers, or trainer +
        cluster coordinator): a step another GC got to first is simply
        skipped.
        """
        from repro.checkpoint.manifest import (
            committed_steps,
            load_manifest_if_committed,
            referenced_steps,
        )
        removed = []
        keep = set(keep_steps)
        committed = committed_steps(self.root)
        if pin_referenced:
            # closure over the manifests that will survive: anything they
            # reference survives too (and transitively its own references)
            frontier = [s for s in committed if s in keep]
            while frontier:
                m = load_manifest_if_committed(self.root, frontier.pop())
                if m is None:
                    continue
                for ref in referenced_steps(m):
                    if ref not in keep:
                        keep.add(ref)
                        frontier.append(ref)
        for s in committed:
            if s in keep:
                continue
            d = step_dir(self.root, s)
            try:
                # remove COMMIT first so a crash mid-GC leaves an uncommitted
                # (hence invisible) directory rather than a corrupt one.
                os.remove(os.path.join(d, "COMMIT"))
            except FileNotFoundError:
                continue  # a racing collector owns this step now
            try:
                for name in os.listdir(d):
                    try:
                        os.remove(os.path.join(d, name))
                    except FileNotFoundError:
                        pass
                os.rmdir(d)
            except (FileNotFoundError, NotADirectoryError):
                pass
            removed.append(s)
        return removed
