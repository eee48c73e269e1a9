"""ForkedCheckpointer: async two-phase save, blocking-time economics,
incremental deltas, pipelining, failure surfacing — over both persist
backends (writer-pool ``thread`` and true-COW ``fork``)."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ChunkStore, latest_committed_step
from repro.checkpoint.codecs import Codec, register_codec, unregister_codec
from repro.core import CheckpointPolicy, ForkedCheckpointer, RestoreManager
from repro.utils.tree import tree_equal

BACKENDS = ["thread"] + (["fork"] if hasattr(os, "fork") else [])


@pytest.fixture(params=BACKENDS)
def backend(request):
    return request.param


def _state(step=1, n=1 << 16):
    return {
        "device": {"w": jnp.arange(n, dtype=jnp.float32) + step},
        "host": {"step": np.int64(step)},
    }


def test_async_save_restores_exactly(tmp_store, backend):
    ck = ForkedCheckpointer(tmp_store, chunk_bytes=4096, backend=backend)
    s = _state(1)
    r = ck.save_async(1, s)
    r.wait()
    assert r.error is None
    restored, m = RestoreManager(tmp_store).restore(verify="store")
    assert tree_equal(jax.tree.map(np.asarray, s), restored)
    ck.close()


def test_blocking_time_less_than_total(tmp_store, backend):
    """The paper's headline: application blocks only for phase 1."""
    ck = ForkedCheckpointer(
        tmp_store, chunk_bytes=1 << 14, codec="gzip", backend=backend
    )
    s = _state(1, n=1 << 20)  # 4 MB
    r = ck.save_async(1, s)
    r.wait()
    assert r.blocking_s < r.blocking_s + r.persist_s
    assert r.persist_s > 0
    ck.close()


def test_incremental_second_save_writes_less(tmp_store, backend):
    ck = ForkedCheckpointer(
        tmp_store, chunk_bytes=4096, incremental=True, backend=backend
    )
    s = _state(1)
    ck.save_async(1, s).wait()
    s2 = {
        "device": {"w": s["device"]["w"].at[0].set(-1.0)},
        "host": {"step": np.int64(2)},
    }
    r2 = ck.save_async(2, s2)
    r2.wait()
    assert r2.chunks_reused > 0
    assert r2.chunks_written <= 3  # 1 dirty chunk + host step leaf
    restored, _ = RestoreManager(tmp_store).restore(verify="store")
    assert tree_equal(jax.tree.map(np.asarray, s2), restored)
    ck.close()


def test_pipeline_bounded_by_max_pending(tmp_store, backend):
    ck = ForkedCheckpointer(
        tmp_store, chunk_bytes=4096, max_pending=1, backend=backend
    )
    for step in range(1, 5):
        ck.save_async(step, _state(step))
    done = ck.wait_all()
    assert all(r.error is None for r in done)
    assert latest_committed_step(tmp_store.root) == 4
    ck.close()


def test_save_sync_includes_persist_in_blocking(tmp_store, backend):
    ck = ForkedCheckpointer(tmp_store, chunk_bytes=4096, backend=backend)
    r = ck.save_sync(1, _state(1))
    assert r.blocking_s >= r.persist_s
    ck.close()


def test_persist_failure_surfaces_at_wait(tmp_store, backend):
    ck = ForkedCheckpointer(tmp_store, chunk_bytes=4096, backend=backend)
    # sabotage the store root after construction
    import shutil

    r = ck.save_async(1, _state(1))
    r.wait()  # first one fine
    shutil.rmtree(tmp_store.root)
    # make root un-creatable by placing a file where the dir should be
    with open(tmp_store.root, "w") as f:
        f.write("not a dir")
    r2 = ck.save_async(2, _state(2))
    with pytest.raises(RuntimeError, match="failed"):
        r2.wait(timeout=60)
    ck.close()  # close() drains without re-raising


@pytest.fixture
def crash_codecs():
    """Sabotage codecs, registered only for the duration of a test so the
    global registry (which test_roundtrip parametrizes over) stays clean."""
    register_codec(Codec(
        "boom-raise",
        lambda b: (_ for _ in ()).throw(RuntimeError("codec exploded")),
        lambda b: b,
    ), replace=True)
    register_codec(Codec("boom-exit", lambda b: os._exit(3), lambda b: b),
                   replace=True)
    yield
    unregister_codec("boom-raise")
    unregister_codec("boom-exit")


def test_failing_codec_surfaces_as_error_not_hang(tmp_store, backend, crash_codecs):
    """A crash inside phase 2 (here: the codec) must surface at wait()."""
    ck = ForkedCheckpointer(tmp_store, codec="boom-raise", backend=backend)
    r = ck.save_async(1, _state(1))
    with pytest.raises(RuntimeError, match="codec exploded"):
        r.wait(timeout=60)
    assert r.error is not None
    ck.close()


@pytest.mark.skipif(not hasattr(os, "fork"), reason="needs os.fork")
def test_fork_child_hard_crash_surfaces_as_error_not_hang(tmp_store, crash_codecs):
    """A child that dies without reporting (os._exit mid-persist) must be
    reaped and converted into CheckpointResult.error, not a hang."""
    ck = ForkedCheckpointer(tmp_store, codec="boom-exit", backend="fork")
    r = ck.save_async(1, _state(1))
    with pytest.raises(RuntimeError, match="exit"):
        r.wait(timeout=60)
    assert "3" in r.error
    ck.close()


@pytest.mark.skipif(not hasattr(os, "fork"), reason="needs os.fork")
def test_fork_backend_limits_live_children(tmp_store):
    """max_pending bounds concurrent forked children (paper: one at a time)."""
    ck = ForkedCheckpointer(
        tmp_store, chunk_bytes=4096, max_pending=1, backend="fork"
    )
    peak = 0
    for step in range(1, 5):
        ck.save_async(step, _state(step))
        peak = max(peak, len(ck.backend._live))
    ck.wait_all()
    assert peak <= 1
    ck.close()


def test_concurrent_buffer_acquisition_no_lost_wakeup(tmp_store, backend):
    """Regression: the old busy-event scan let two waiters spin-race for the
    buffer released by the oldest pending checkpoint. Hammer save_async from
    several threads; every save must complete and commit."""
    ck = ForkedCheckpointer(
        tmp_store, chunk_bytes=4096, max_pending=1, backend=backend
    )
    errs = []

    def saver(base):
        try:
            for i in range(3):
                ck.save_async(base + i, _state(base + i)).wait(timeout=120)
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=saver, args=(100 * t,)) for t in (1, 2, 3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert latest_committed_step(tmp_store.root) is not None
    ck.close()


def test_policy_cadence_and_preempt():
    p = CheckpointPolicy(interval_steps=10)
    assert not p.should_checkpoint(5)
    assert p.should_checkpoint(10)
    p.notify_checkpointed(10)
    assert not p.should_checkpoint(11)
    p.request_preempt_checkpoint()
    assert p.should_checkpoint(11)
