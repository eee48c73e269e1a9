"""A run with its timed path broken underneath reads ``correct`` false.

Each test drives the rest of a run of a cell on the CPU, at a small copy of
its configuration and with the configuration's own limits, with one fault
planted in the program where the window calls it: a step that returns its
state unchanged, a step that leaves half of the batch out and takes the
mean over the rest, and a persist that alters the bytes it writes.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, job
from bench import reference as refmod

SEED = 2**32 + 777


def run_small(cell: str) -> dict:
    cfg, ref = refmod.load_config("qwen2-0.5b")
    return harness.run_cell(cell, SEED, 1.0, False, t_process=time.perf_counter(),
                            cfg=ref.small(cfg), require_tpu=False)


def _plant(monkeypatch, wrap):
    real = job.make_train_step

    def make(*a, **k):
        step, sh, bsh = real(*a, **k)
        return wrap(step), sh, bsh

    monkeypatch.setattr(job, "make_train_step", make)


def unchanged(step):
    # the step donates its input, so it runs on a copy of the state it keeps
    return lambda state, batch: (
        state, step(jax.tree.map(jnp.copy, state), batch)[1])


def half_batch(step):
    return lambda state, batch: step(
        state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))


@pytest.mark.parametrize("cell", ["qwen2-0.5b.save-dense", "qwen2-0.5b.resume",
                                  "qwen2-0.5b.no-ckpt"])
@pytest.mark.parametrize("fault,number", [(unchanged, "change_gap"),
                                          (half_batch, "loss_gap")],
                         ids=["state-unchanged", "half-batch"])
def test_a_broken_step_is_not_correct(cell, fault, number, monkeypatch, no_compile_cache):
    _plant(monkeypatch, fault)
    out = run_small(cell)
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]
    assert out["correct"] is False


@pytest.mark.parametrize("cell,number", [("qwen2-0.5b.save-dense", "image_mismatches"),
                                         ("qwen2-0.5b.resume", "failed")])
def test_altered_image_bytes_are_not_correct(cell, number, monkeypatch, no_compile_cache):
    from repro.checkpoint.store import ChunkStore

    real = ChunkStore.Writer.append

    def append(self, raw, codec_name, *, index, digest):
        if index == 0:  # the first chunk of every leaf, as it is written
            raw = bytes([raw[0] ^ 0x40]) + bytes(raw[1:])
        return real(self, raw, codec_name, index=index, digest=digest)

    monkeypatch.setattr(ChunkStore.Writer, "append", append)
    out = run_small(cell)
    assert out["checks"][number]["value"] > 0, out["checks"]
    assert out["correct"] is False
