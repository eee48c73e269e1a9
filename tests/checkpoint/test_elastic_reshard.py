"""Elastic reshard restore: a committed N-host image re-sliced onto M.

The manifest is topology-independent; ``RestoreManager.restore_elastic``
re-slices it with the SAME ownership rule the writers use
(``host_slice_plan``), so the acceptance here is exhaustive coverage:
non-divisible splits in both directions, single-host collapse, and a
delta chain surviving GC under the new slicing.
"""
import os

import numpy as np
import pytest

from repro.checkpoint.manifest import commit_manifest, merge_hostmetas
from repro.checkpoint.sharded import host_slice_plan
from repro.checkpoint.store import ChunkStore
from repro.core.forked import ForkedCheckpointer
from repro.core.policy import CheckpointPolicy
from repro.core.restore import RestoreManager
from repro.core.shadow import HostShardView
from repro.coord.worker import shard_tree_for_host, state_digest
from repro.utils.tree import flatten_with_paths


def _state(seed=0, rows=12, cols=16):
    rng = np.random.default_rng(seed)
    return {
        "device": {
            "w": rng.standard_normal((rows, cols)).astype(np.float32),
            "b": rng.standard_normal((cols,)).astype(np.float32),
            "scale": np.float32(1.25),
        },
        "host": {"step": np.int64(7)},
    }


def _commit_over_hosts(root, state, step, n_hosts, *, cks=None,
                       incremental=False):
    """Persist + merge + commit one image across n_hosts (thread backend)."""
    cks = cks if cks is not None else {}
    for h in range(n_hosts):
        ck = cks.get(h)
        if ck is None:
            ck = cks[h] = ForkedCheckpointer(
                ChunkStore(root), chunk_bytes=1 << 7, host=h,
                backend="thread", external_commit=True,
                digest_on_device=False, incremental=incremental,
            )
        ck.save_async(step, shard_tree_for_host(state, h, n_hosts)).wait(60)
    commit_manifest(root, merge_hostmetas(root, step))
    for ck in cks.values():
        ck.commit_confirmed(step)
    return cks


def _reassemble(shard_trees):
    """Combine per-host HostShardView trees back into global arrays."""
    out = {}
    for tree in shard_trees:
        flat, _ = flatten_with_paths(tree)
        for path, view in flat.items():
            assert isinstance(view, HostShardView), path
            if path not in out:
                out[path] = (
                    np.full(view.shape, np.nan, dtype=view.dtype)
                    if view.shape else np.zeros((), view.dtype)
                )
            if view.data is None:
                continue
            if view.shape:
                idx = tuple(slice(a, b) for a, b in zip(view.start, view.stop))
                out[path][idx] = view.data
            else:
                out[path] = np.asarray(view.data, dtype=view.dtype).reshape(())
    return out


# -- the ownership rule itself ---------------------------------------------------

def test_host_slice_plan_partitions_exactly():
    """For ANY (n0, n_hosts): dim-0 windows tile [0, n0) without gaps or
    overlaps, and every small leaf has exactly one owner."""
    for n0 in (1, 5, 12, 13):
        for n in (1, 2, 3, 5, 8):
            if n0 >= n:
                edges = []
                for h in range(n):
                    plan = host_slice_plan("p", (n0, 4), h, n)
                    assert plan is not None
                    edges.append((plan[0][0], plan[1][0]))
                assert edges[0][0] == 0 and edges[-1][1] == n0
                for (a, b), (c, d) in zip(edges, edges[1:]):
                    assert b == c  # contiguous, no gap/overlap
            owners = [
                h for h in range(n)
                if host_slice_plan("tiny", (), h, n) is not None
            ]
            assert len(owners) == 1


def test_host_slice_plan_matches_live_sharding():
    """restore_elastic's plan == what shard_tree_for_host persists."""
    state = _state()
    flat, _ = flatten_with_paths(state)
    for n in (1, 2, 3, 5):
        for h in range(n):
            live, _ = flatten_with_paths(shard_tree_for_host(state, h, n))
            for path, view in live.items():
                plan = host_slice_plan(
                    path, np.asarray(flat[path]).shape, h, n
                )
                if view.data is None:
                    assert plan is None, (path, h, n)
                else:
                    assert plan == (view.start, view.stop), (path, h, n)


# -- reshard restores -------------------------------------------------------------

@pytest.mark.parametrize("n_old,n_new", [
    (4, 3),   # neither divides the other
    (3, 5),   # grow, non-divisible
    (4, 6),   # acceptance: 4-host image onto 6
    (4, 1),   # single-host collapse
    (5, 2),
])
def test_reshard_bit_identical(tmp_path, n_old, n_new):
    root = str(tmp_path / "ck")
    state = _state(rows=13)  # odd rows: every split is uneven somewhere
    cks = _commit_over_hosts(root, state, 5, n_old)
    rm = RestoreManager(ChunkStore(root))

    # full-state restore is host-count independent
    full, m = rm.restore_elastic(n_hosts=n_new)
    assert m.step == 5
    assert state_digest(full) == state_digest(state)

    # per-host slices under the NEW topology cover the image exactly
    trees = []
    for h in range(n_new):
        shard, m = rm.restore_elastic(n_hosts=n_new, host=h)
        trees.append(shard)
    merged = _reassemble(trees)
    flat, _ = flatten_with_paths(state)
    for path, leaf in flat.items():
        np.testing.assert_array_equal(merged[path], np.asarray(leaf),
                                      err_msg=path)

    # and the slices are exactly what n_new live writers would persist —
    # a restarted cluster can immediately checkpoint under the new count
    for h in range(n_new):
        live, _ = flatten_with_paths(shard_tree_for_host(state, h, n_new))
        got, _ = flatten_with_paths(trees[h])
        for path in live:
            if live[path].data is None:
                assert got[path].data is None
            else:
                np.testing.assert_array_equal(got[path].data, live[path].data)
                assert got[path].start == live[path].start
                assert got[path].stop == live[path].stop
    for ck in cks.values():
        ck.close()


def test_reshard_after_gc_of_delta_chain(tmp_path):
    """An incremental (delta) manifest re-slices correctly after GC has
    run: chunk references chase into the base step's files, which the
    reference closure keeps alive."""
    root = str(tmp_path / "ck")
    store = ChunkStore(root)
    state = _state(rows=12)
    cks = _commit_over_hosts(root, state, 1, 2, incremental=True)

    # step 2: mutate one row -> delta manifest referencing step 1 payloads
    state2 = {
        "device": dict(state["device"]), "host": {"step": np.int64(8)},
    }
    w2 = state2["device"]["w"].copy()
    w2[3] += 1.0
    state2["device"]["w"] = w2
    _commit_over_hosts(root, state2, 2, 2, cks=cks, incremental=True)

    # GC keep_last=1: step 2 survives, and because its delta references
    # step 1's payload files, the reference closure pins those too
    CheckpointPolicy(keep_last=1).run_gc(store)
    rm = RestoreManager(store)
    assert rm.available_steps()[-1] == 2

    # elastic restore of the delta image onto 3 hosts, bit-identical
    trees = [
        rm.restore_elastic(n_hosts=3, host=h, step=2)[0] for h in range(3)
    ]
    merged = _reassemble(trees)
    flat, _ = flatten_with_paths(state2)
    for path, leaf in flat.items():
        np.testing.assert_array_equal(merged[path], np.asarray(leaf),
                                      err_msg=path)
    for ck in cks.values():
        ck.close()


def test_restore_elastic_unknown_step_raises(tmp_path):
    root = str(tmp_path / "ck")
    os.makedirs(root)
    with pytest.raises(FileNotFoundError):
        RestoreManager(ChunkStore(root)).restore_elastic(n_hosts=2, host=0)


@pytest.mark.parametrize("n_old,n_new", [(2, 3), (2, 2)])
def test_reshard_decodes_each_chunk_once_in_place(tmp_path, n_old, n_new):
    """Every stored shard is decoded into an array of its own; a window that
    is not one stored shard copies from those, and a shard another host's
    window overlaps too comes from the store's cache of those decodes."""
    root = str(tmp_path / "ck")
    state = _state(rows=12)
    cks = _commit_over_hosts(root, state, 1, n_old)
    rm = RestoreManager(ChunkStore(root))
    trees = [rm.restore_elastic(n_hosts=n_new, host=h)[0] for h in range(n_new)]
    merged = _reassemble(trees)
    flat, _ = flatten_with_paths(state)
    for path, leaf in flat.items():
        assert merged[path].dtype == np.asarray(leaf).dtype, path
        assert merged[path].tobytes() == np.asarray(leaf).tobytes(), path
    manifest = rm._pick_manifest(None)
    n_chunks = sum(len(s.chunks) for lv in manifest.leaves.values() for s in lv.shards)
    assert rm.store.chunks_in_place == rm.store.chunks_read == n_chunks
    for ck in cks.values():
        ck.close()
