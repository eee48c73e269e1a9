"""The program's save and restore spans in a ``jax.profiler`` trace, at a
small size on the CPU: each span is there, nested where it is opened, and
carries the bytes it moves."""
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import span_reduce, trace_reduce
from bench.generator import start_trace
from repro.checkpoint import ChunkStore
from repro.core import ForkedCheckpointer, RestoreManager
from repro.utils.timing import Timings, span

CHUNK = 4096


def _state(step: int) -> dict:
    return {
        "device": {
            "w": jnp.arange(3 * CHUNK // 4, dtype=jnp.float32) + step,  # 3 chunks
            "e": jnp.full((40, 33), step, jnp.bfloat16),  # a short tail chunk
        },
        "host": {"step": np.int64(step)},
    }


def _traced(trace_dir, fn):
    """``fn()`` inside a ``bench.window`` under the profiler; returns the
    program's spans as {name: [(start, end, thread, args)]} and the reduction."""
    start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load(str(trace_dir))
    found = defaultdict(list)
    for e in trace["traceEvents"]:
        if e.get("ph") == "X" and span_reduce.PROGRAM.match(e["name"]):
            a = float(e["ts"])
            found[e["name"]].append((a, a + float(e["dur"]), (e["pid"], e["tid"]),
                                     e.get("args") or {}))
    return found, span_reduce.reduce(trace)


def _nested(found, inner: str, outer: str) -> bool:
    """Every ``inner`` span lies inside an ``outer`` span of its thread."""
    return bool(found[inner]) and all(
        any(t == u and a <= c and d <= b for a, b, u, _ in found[outer])
        for c, d, t, _ in found[inner])


def _bytes(found, name: str) -> int:
    return sum(int(float(args["bytes"])) for *_, args in found[name])


def _nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("change", ["dense", "one-chunk"])
def test_traced_save_spans(tmp_path, change):
    """Two saves fill both shadow buffers; the traced third finds every
    chunk changed (one bulk transfer per leaf) or one chunk of ``w`` (a
    transfer of that chunk alone)."""
    timings = Timings()
    ck = ForkedCheckpointer(ChunkStore(str(tmp_path / "store")), chunk_bytes=CHUNK,
                            backend="fork", timings=timings)
    try:
        for step in (1, 2):
            ck.save_async(step, _state(1)).wait()
        before = dict(timings.counts)
        if change == "dense":
            state = _state(3)
            moved = _nbytes(state)
        else:
            state = _state(1)
            state["device"]["w"] = state["device"]["w"].at[0].add(1.0)
            moved = CHUNK
        results = []
        found, red = _traced(tmp_path / "trace",
                             lambda: results.append(ck.save_async(3, state)))
        results[0].wait()
        assert results[0].error is None
    finally:
        ck.close()
    assert _nested(found, "shadow/d2h", "shadow/fetch")
    assert _nested(found, "shadow/copy", "shadow/fetch")
    assert _nested(found, "shadow/fetch", "ckpt/snapshot")
    assert _nested(found, "shadow/digest", "ckpt/snapshot")
    assert _nested(found, "ckpt/snapshot", "ckpt/blocking")
    # the fork of the persist child comes after phase 1, once a save
    (sub,) = found["ckpt/submit"]
    assert all(b <= sub[0] for _, b, _, _ in found["ckpt/blocking"])
    assert _bytes(found, "shadow/d2h") == moved
    assert _bytes(found, "shadow/copy") == moved
    assert red["spans"]["shadow/d2h"]["bytes"] == moved
    fetch = red["spans"]["shadow/fetch"]
    parts = red["spans"]["shadow/d2h"]["total_s"] + red["spans"]["shadow/copy"]["total_s"]
    assert parts <= fetch["total_s"] + 1e-9
    assert fetch["self_s"] == pytest.approx(fetch["total_s"] - parts, abs=1e-9)
    # each span is also the timer of that name, which the metrics read
    for name in ("shadow/d2h", "shadow/copy", "ckpt/submit"):
        assert timings.counts[name] - before.get(name, 0) == len(found[name]), name


def test_traced_restore_spans(tmp_path):
    root = str(tmp_path / "store")
    ck = ForkedCheckpointer(ChunkStore(root), chunk_bytes=CHUNK)
    try:
        ck.save_async(1, _state(1)).wait()
    finally:
        ck.close()
    timings = Timings()
    rm = RestoreManager(ChunkStore(root, timings=timings),  # an empty chunk cache
                        timings=timings)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    out = []
    found, red = _traced(tmp_path / "trace", lambda: out.append(rm.restore(
        step=1, verify="device",
        sharding_for=lambda path, shape: dev if path.startswith("device/") else None)))
    state, manifest = out[0]
    for inner, outer in [("store/read", "restore/assemble"),
                         ("store/decode", "restore/assemble"),
                         ("restore/assemble", "restore/leaf"),
                         ("restore/leaf", "restore/eager")]:
        assert _nested(found, inner, outer), (inner, outer)
    assert found["restore/verify_device"]
    assert {a["path"] for *_, a in found["restore/leaf"]} == set(manifest.leaves)
    chunks = [c for lv in manifest.leaves.values() for s in lv.shards for c in s.chunks]
    assert _bytes(found, "store/decode") == sum(c.raw_len for c in chunks) == _nbytes(state)
    assert _bytes(found, "store/read") == sum(c.comp_len for c in chunks)
    assert _bytes(found, "restore/assemble") == _nbytes(state)
    assert red["spans"]["store/read"]["count"] == len(chunks)
    for name in ("store/read", "store/decode", "restore/assemble", "restore/leaf"):
        assert timings.counts[name] == len(found[name]), name


def test_a_chunk_cache_hit_emits_no_store_span(tmp_path):
    root = str(tmp_path / "store")
    ck = ForkedCheckpointer(ChunkStore(root), chunk_bytes=CHUNK)
    try:
        ck.save_async(1, _state(1)).wait()
    finally:
        ck.close()
    rm = RestoreManager(ChunkStore(root))
    rm.restore(step=1)  # fills the cache
    found, _ = _traced(tmp_path / "trace", lambda: rm.restore(step=1))
    assert found["restore/leaf"] and not found["store/read"] and not found["store/decode"]


def test_span_is_free_of_annotations_without_a_profiler():
    assert span("a", bytes=1) is span("b")
