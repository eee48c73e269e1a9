"""Fig. 5 / Table 2 reproduction: checkpoint/restart times vs scale, with a
``backend`` axis (thread writer-pool vs true-COW fork).

Paper: ckpt/restart times for Rodinia + HPGMG/HYPRE at 8-32 ranks; image
size per rank; buffer-cache effects; Table 2's headline is blocking time
under forked checkpointing vs the naive synchronous strategy. Here: one
host scales state size (the per-rank image in the paper shrinks as ranks
grow — we sweep the same per-host image sizes directly) and reports, per
persist backend, async blocking time vs the ``save_sync`` baseline for the
same state, plus restore / verify times.

    PYTHONPATH=src python benchmarks/ckpt_restart.py --backend fork
    PYTHONPATH=src python benchmarks/ckpt_restart.py            # both
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.checkpoint import ChunkStore, DEFAULT_CODEC
from repro.core import ForkedCheckpointer, RestoreManager, list_persist_backends


def _make_state(mb: int):
    n = (mb << 20) // 4
    rng = np.random.default_rng(0)
    state = {
        "device": {"w": jnp.asarray(rng.standard_normal(n), jnp.float32)},
        "host": {"step": np.int64(1)},
    }
    jax.block_until_ready(state["device"]["w"])
    return state


def _checkpointer(d: str, backend: str, codec: str) -> ForkedCheckpointer:
    return ForkedCheckpointer(
        ChunkStore(d), codec=codec, chunk_bytes=8 << 20,
        incremental=False, digest_on_device=False, backend=backend,
    )


def run(backends: tuple[str, ...] = ("thread", "fork"),
        sizes_mb: tuple[int, ...] = (16, 64, 256),
        codec: str = DEFAULT_CODEC) -> None:
    backends = tuple(
        b for b in backends
        if b != "fork" or hasattr(os, "fork")
    )
    for mb in sizes_mb:
        state = _make_state(mb)

        # naive synchronous baseline (same state, same codec): the
        # application blocks for the full compress+write
        with tempfile.TemporaryDirectory() as d:
            ck = _checkpointer(d, "thread", codec)
            sync_s = ck.save_sync(1, state).blocking_s
            ck.close()

        for backend in backends:
            with tempfile.TemporaryDirectory() as d:
                ck = _checkpointer(d, backend, codec)
                t0 = time.perf_counter()
                r = ck.save_async(1, state)
                blocking = time.perf_counter() - t0
                r.wait()
                total = blocking + r.persist_s
                ck.close()

                t1 = time.perf_counter()
                rm = RestoreManager(ChunkStore(d))
                restored, _ = rm.restore()
                restart = time.perf_counter() - t1

                t2 = time.perf_counter()
                rm.restore(verify="store")
                verify = time.perf_counter() - t2

            row(
                f"table2_ckpt_restart_{mb}mb_{backend}",
                total * 1e6,
                backend=backend,
                blocking_ms=round(blocking * 1e3, 1),
                persist_ms=round(r.persist_s * 1e3, 1),
                sync_baseline_ms=round(sync_s * 1e3, 1),
                speedup_vs_naive=round(sync_s / max(blocking, 1e-9), 1),
                blocking_below_sync=bool(blocking < sync_s),
                restart_ms=round(restart * 1e3, 1),
                verify_ms=round(verify * 1e3, 1),
                image_mb=round(r.bytes_written / 2**20, 1),
            )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--backend", choices=list_persist_backends(), default=None,
        help="run a single persist backend (default: thread and fork)",
    )
    ap.add_argument("--codec", default=DEFAULT_CODEC)
    ap.add_argument(
        "--sizes-mb", type=int, nargs="+", default=[16, 64, 256],
        metavar="MB",
    )
    args = ap.parse_args(argv)
    backends = (args.backend,) if args.backend else ("thread", "fork")
    run(backends=backends, sizes_mb=tuple(args.sizes_mb), codec=args.codec)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
