"""RestoreManager — restart protocol (paper §3.4) + lazy restore (§4.2).

Eager mode re-creates the full state: read manifest, assemble each leaf's
global array from stored shards (any source topology -> any target
topology), place with the target sharding. This is the paper's "replay the
allocations, transfer the data back through the proxy".

Lazy mode returns a mapping that materializes leaves on first access and
prefetches ahead in manifest order with an exponentially growing window —
the paper's read-fault heuristic: the first fault reads one page, each
subsequent fault on the same region doubles the read-ahead. Serving
restarts benefit: embedding tables materialize on demand rather than
stalling the whole restore.
"""
from __future__ import annotations

import concurrent.futures as cf
import threading
from typing import Any, Callable, Literal

import jax

from repro.checkpoint.manifest import Manifest, latest_committed_step, load_manifest
from repro.checkpoint.sharded import _LeafAssembler, restore_leaf
from repro.checkpoint.store import ChunkStore
from repro.checkpoint.manifest import skeleton_fill, skeleton_paths
from repro.utils.timing import Timings

ShardingFor = Callable[[str, tuple[int, ...]], jax.sharding.Sharding | None]
# what a restore checks against the digests its save recorded:
#   "store"  — re-hash every stored chunk before reading (paper's verified
#              mode; timer ``restore/verify``)
#   "device" — re-digest the restored leaves where they were placed, shard
#              by shard on their devices (the Pallas kernel on TPU), so what
#              the next step reads is what was saved (``restore/verify_device``)
Verify = Literal[False, "store", "device"]


class LazyLeaves:
    """Dict-like view over a manifest; leaves materialize on first read.

    Exponential read-ahead: after ``k`` consecutive accesses that hit the
    prefetch frontier, the window grows as 1, 2, 4, ... up to
    ``max_readahead`` leaves submitted to a background reader.
    """

    def __init__(
        self,
        store: ChunkStore,
        manifest: Manifest,
        sharding_for: ShardingFor | None,
        *,
        max_readahead: int = 32,
        timings: Timings | None = None,
    ):
        self._store = store
        self._manifest = manifest
        self._sharding_for = sharding_for or (lambda p, s: None)
        self._order = list(manifest.leaves.keys())
        self._pos = {p: i for i, p in enumerate(self._order)}
        self._cache: dict[str, Any] = {}
        self._futures: dict[str, cf.Future] = {}
        self._window = 1
        self._max_window = max_readahead
        self._frontier = 0
        self._last_idx = -1
        self._lock = threading.Lock()
        self._pool = cf.ThreadPoolExecutor(max_workers=4, thread_name_prefix="crum-read")
        self.timings = timings or Timings()
        self.loads = 0

    def keys(self) -> list[str]:
        return list(self._order)

    def _materialize(self, path: str) -> Any:
        lrec = self._manifest.leaves[path]
        with self.timings.measure("restore/leaf", path=path):
            leaf = restore_leaf(
                self._store, lrec, self._sharding_for(path, tuple(lrec.shape))
            )
        return leaf

    def __getitem__(self, path: str) -> Any:
        # claim-under-lock: concurrent first accesses to the same leaf must
        # materialize it exactly once. The first claimant registers a future
        # (so peers wait on it) and runs the read itself; peers — and reads
        # already prefetched by the pool — block on fut.result().
        owner = False
        with self._lock:
            if path in self._cache:
                return self._cache[path]
            fut = self._futures.get(path)
            if fut is None:
                fut = cf.Future()
                self._futures[path] = fut
                owner = True
                self.loads += 1
        if owner:
            try:
                fut.set_result(self._materialize(path))
            except BaseException as e:
                fut.set_exception(e)
        try:
            leaf = fut.result()
        except BaseException:
            # a failed read (owner or pool prefetch) must not poison the
            # leaf: drop the future so the next access retries materialize
            with self._lock:
                if self._futures.get(path) is fut:
                    self._futures.pop(path)
            raise
        with self._lock:
            self._cache[path] = leaf
            self._futures.pop(path, None)
        self._read_ahead(path)
        return leaf

    def _read_ahead(self, touched: str) -> None:
        """Grow and schedule the prefetch window past the touched leaf."""
        with self._lock:
            i = self._pos[touched]
            if i >= self._last_idx:
                # forward progress: double the window (paper's heuristic)
                self._window = min(self._window * 2, self._max_window)
            else:  # backward jump: new region, reset the stride
                self._window = 1
                self._frontier = 0
            self._last_idx = i
            lo = max(self._frontier, i + 1)
            hi = min(len(self._order), i + 1 + self._window)
            to_fetch = [
                p
                for p in self._order[lo:hi]
                if p not in self._cache and p not in self._futures
            ]
            for p in to_fetch:
                self._futures[p] = self._pool.submit(self._materialize, p)
                self.loads += 1
            self._frontier = max(self._frontier, hi)

    def as_tree(self) -> Any:
        """Force everything and return the full pytree."""
        leaves = {p: self[p] for p in self._order}
        return skeleton_fill(self._manifest.skeleton, leaves)

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


class RestoreManager:
    def __init__(self, store: ChunkStore, *, timings: Timings | None = None):
        self.store = store
        self.timings = timings or Timings()
        # {"chunks": compared, "unmatched": shards} of the last "device" check
        self.last_check: dict[str, int] | None = None

    def _verify_store(self, manifest: Manifest, verify: Verify) -> None:
        if verify not in (False, "store", "device"):
            raise ValueError(f"verify={verify!r}: expected False, 'store' or 'device'")
        if verify == "store":
            from repro.checkpoint.sharded import verify_manifest

            with self.timings.measure("restore/verify"):
                verify_manifest(self.store, manifest)

    def available_steps(self) -> list[int]:
        from repro.checkpoint.manifest import committed_steps

        return committed_steps(self.store.root)

    def _pick_manifest(self, step: int | None) -> Manifest:
        """Load the requested (or newest committed) manifest.

        The pick/load pair races with GC: the step chosen as newest can be
        collected before its manifest read. Re-scan on miss instead of
        surfacing a spurious FileNotFoundError to the caller.
        """
        if step is not None:
            return load_manifest(self.store.root, step)
        for _ in range(8):
            step = latest_committed_step(self.store.root)
            if step is None:
                raise FileNotFoundError(
                    f"no committed checkpoint under {self.store.root}"
                )
            try:
                return load_manifest(self.store.root, step)
            except (FileNotFoundError, NotADirectoryError):
                continue
        raise FileNotFoundError(
            f"committed checkpoints under {self.store.root} kept "
            "vanishing mid-read (GC racing restore)"
        )

    def restore(
        self,
        *,
        step: int | None = None,
        sharding_for: ShardingFor | None = None,
        lazy: bool = False,
        verify: Verify = False,
    ) -> tuple[Any, Manifest]:
        """Restore the newest (or given) committed checkpoint.

        Returns (state, manifest); in lazy mode state is a LazyLeaves whose
        ``as_tree()`` gives the pytree. ``verify`` (see :data:`Verify`)
        raises at the first chunk that differs from the save; a "device"
        check needs eager mode and leaves its counts in ``last_check``.
        """
        manifest = self._pick_manifest(step)
        self._verify_store(manifest, verify)
        if lazy:
            if verify == "device":
                raise ValueError("verify='device' needs the leaves placed: restore eagerly")
            return (
                LazyLeaves(
                    self.store, manifest, sharding_for, timings=self.timings
                ),
                manifest,
            )
        sharding_for = sharding_for or (lambda p, s: None)
        with self.timings.measure("restore/eager"):
            leaves = {}
            for path, lrec in manifest.leaves.items():
                with self.timings.measure("restore/leaf", path=path):
                    leaves[path] = restore_leaf(
                        self.store, lrec, sharding_for(path, tuple(lrec.shape))
                    )
            state = skeleton_fill(manifest.skeleton, leaves)
        if verify == "device":
            with self.timings.measure("restore/verify_device"):
                self.last_check = verify_restored_digests(state, manifest)
        return state, manifest

    # -- proxy restart (paper §3.4: replay allocations, push data back) ---------
    def restore_into_proxy(
        self,
        runner,
        *,
        step: int | None = None,
        sharding_for: ShardingFor | None = None,
        verify: Verify = False,
    ) -> tuple[Any, Manifest]:
        """Restore a committed image and re-create device state in a proxy.

        The paper's restart protocol for the proxy architecture: read the
        image, then replay the logged allocations into a fresh proxy process
        and transfer the data back through it. ``runner`` is a
        ``repro.proxy.ProxyRunner``; a fresh runner is started with the
        restored device state (program + register + upload replayed from
        scratch), a running one gets the state pushed over its segments.
        Returns (state, manifest) exactly like :meth:`restore`.
        """
        state, manifest = self.restore(
            step=step, sharding_for=sharding_for, verify=verify
        )
        with self.timings.measure("restore/proxy_push"):
            if getattr(runner, "started", False):
                runner.push(state["device"])
            else:
                runner.start(
                    device_state=state["device"], base_step=int(manifest.step)
                )
        return state, manifest

    # -- elastic reshard (restore onto a different host count) ------------------
    def restore_elastic(
        self,
        *,
        n_hosts: int,
        host: int | None = None,
        step: int | None = None,
        verify: Verify = False,
    ) -> tuple[Any, Manifest]:
        """Re-slice a committed image across a different worker count.

        The manifest is topology-independent (leaves are global arrays,
        shards are index ranges), so a checkpoint written by N hosts
        restores onto M: with ``host=None`` the full global state is
        assembled (what each simulated worker holds); with ``host=h`` only
        the windows host ``h`` of ``n_hosts`` *owns* are read — each
        window assembled from whichever stored shards overlap it, wrapped
        in :class:`~repro.core.shadow.HostShardView` exactly as
        ``shard_tree_for_host`` would produce it live. Non-divisible
        splits (4 -> 3, 3 -> 5, N -> 1) need no special casing: ownership
        comes from the same ``host_slice_plan`` rule the writers use.

        Returns (state, manifest); in per-host mode the state's leaves are
        HostShardViews ready to be persisted under the new topology.
        """
        from repro.checkpoint.sharded import host_slice_plan
        from repro.core.shadow import HostShardView

        if verify == "device":
            raise ValueError("an elastic restore places nothing: verify='store' only")
        manifest = self._pick_manifest(step)
        self._verify_store(manifest, verify)
        if host is None:
            leaves = {
                path: restore_leaf(self.store, lrec, None)
                for path, lrec in manifest.leaves.items()
            }
            return skeleton_fill(manifest.skeleton, leaves), manifest
        import numpy as np

        with self.timings.measure("restore/elastic"):
            leaves = {}
            for path, lrec in manifest.leaves.items():
                shape = tuple(lrec.shape)
                dtype = np.dtype(lrec.dtype)
                plan = host_slice_plan(path, shape, host, n_hosts)
                if plan is None:
                    leaves[path] = HostShardView(
                        None, global_shape=shape, dtype=dtype
                    )
                    continue
                start, stop = plan
                data = _LeafAssembler(self.store, lrec).window(start, stop)
                leaves[path] = HostShardView(
                    data, start=start, stop=stop,
                    global_shape=shape, dtype=dtype,
                )
        return skeleton_fill(manifest.skeleton, leaves), manifest


def verify_restored_digests(state: Any, manifest: Manifest) -> dict[str, int]:
    """Re-digest a restored state where it lives and compare it with the
    chunk digests its manifest recorded at save time.

    A jax leaf is digested shard by shard on its device (the
    ``kernels.ops.chunk_digests`` dispatch: the Pallas kernel on TPU), a
    host leaf with the numpy reference. A shard whose index range the
    manifest does not hold (a resume under another layout) cannot be
    compared chunk for chunk and is counted as ``unmatched``. Returns
    ``{"chunks": compared, "unmatched": shards}``; raises ValueError at
    the first chunk whose digest differs.
    """
    import numpy as np

    from repro.core.shadow import _owned_host_shards
    from repro.kernels.ops import chunk_digests, digests_to_u64
    from repro.kernels.ref import chunk_digests_np
    from repro.utils.tree import flatten_with_paths

    flat, _ = flatten_with_paths(state)
    work = []  # (path, start, stop, shard record, data)
    unmatched = 0
    for path, lrec in manifest.leaves.items():
        by_range = {(tuple(s.start), tuple(s.stop)): s for s in lrec.shards}
        for _ordinal, start, stop, data in _owned_host_shards(flat[path]):
            srec = by_range.get((tuple(start), tuple(stop)))
            if srec is None:
                unmatched += 1
            elif srec.chunks:
                work.append((path, start, stop, srec, data))

    def digest(item) -> np.ndarray:
        data, chunks = item[4], item[3].chunks
        # a single-chunk shard digests the same under any chunk size that
        # covers it; round up to whole words for the kernel
        cb = -(-chunks[0].raw_len // 4) * 4
        if isinstance(data, jax.Array):
            return digests_to_u64(chunk_digests(data, cb))
        return digests_to_u64(chunk_digests_np(np.asarray(data), cb))

    # each (shard shape, device) pair compiles its own digest program:
    # a few threads overlap those compiles, bounded so that the digests'
    # temporaries stay small next to the restored state
    compared = 0
    with cf.ThreadPoolExecutor(max_workers=4, thread_name_prefix="crum-verify") as pool:
        for (path, start, stop, srec, _), got in zip(work, pool.map(digest, work)):
            for c in srec.chunks:
                if int(got[c.index]) != c.digest:
                    raise ValueError(
                        f"restored {path} {start}:{stop} chunk {c.index} "
                        f"digest {int(got[c.index]):#x} != saved {c.digest:#x}"
                    )
            compared += len(srec.chunks)
    return {"chunks": compared, "unmatched": unmatched}
