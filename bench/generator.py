"""The one traffic generator: it reads a mix's parameters and drives the job.

A mix (``traffic/<name>.json``) names its ``kind`` and parameters:

- ``train``: dense AdamW training in cycles of ``steps_per_cycle`` steps
  that end in a host sync of the last step's loss. With ``save`` true every
  cycle starts with a checkpoint through ``CheckpointedTrainer.checkpoint_now``,
  so its persist runs beside the cycle's steps. Set-up runs the first three
  steps through the same call, then ``warmup_saves`` saves of the state as
  it stands, then one step.
- ``resume``: set-up trains ``steps_before_save`` steps, saves, waits for the
  commit and makes one warm resume. A resume drops the image's pages from
  the page cache, deletes the device state, restores the image with
  ``RestoreManager.restore(verify="device")`` and runs the next step.

The window repeats whole cycles (or resumes) until ``seconds`` have passed;
with ``trace`` it is a short traced window instead under ``jax.profiler``:
one cycle of ``trace_steps`` steps, or ``trace_resumes`` resumes. Every call into the
system is wrapped in a ``bench.*`` ``TraceAnnotation``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import reference as refmod
from bench.job import Job, evict_page_cache, fingerprint

Span = jax.profiler.TraceAnnotation


def start_trace(trace_dir: str) -> None:
    """Device and host activity with the bench's spans; no Python call
    tracing, which would slow the host and swell the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, create_perfetto_trace=True, profiler_options=opts)


def _timings(trainer) -> dict:
    t = trainer.timings
    return {k: (t.totals[k], t.counts[k]) for k in list(t.totals)}


def _delta(after: dict, before: dict) -> dict:
    out = {}
    for k, (tot, n) in after.items():
        t0, n0 = before.get(k, (0.0, 0))
        if n > n0:
            out[k] = (tot - t0, n - n0)
    return out


class Run:
    """What a run of one cell did: the readings the metrics and checks use."""

    def __init__(self, job: Job, traffic: dict):
        self.job, self.traffic = job, traffic
        self.losses: list = []        # device scalars, in step order
        self.grad_norms = None        # program's first clipped gradient, per tensor
        self.change_norms = None      # program's change over the first three steps
        self.step = 0
        self.window: dict = {}
        self.warmup_blocking_s: list[float] = []
        self.saved_fp: dict[int, jax.Array] = {}
        self.window_saves: list = []  # (step, stall_s, CheckpointResult)
        self.restore_fps: list = []
        self.resumed_losses: list = []  # the step after each restore
        self.failed = 0
        self.attempted = 0
        self.error: str | None = None  # the last failed resume's exception

    # -- the calls the window makes ---------------------------------------------
    def steps(self, state, n: int):
        """``n`` train steps through the program's jitted step."""
        for _ in range(n):
            self.step += 1
            with Span("bench.step"):
                state, metrics = self.job.step_fn(state, self.job.batch(self.step))
            self.losses.append(metrics["loss"])
        return state

    def save(self, state):
        """One checkpoint; returns (host seconds it stalled, result)."""
        self.saved_fp[self.step] = fingerprint(state)
        t0 = time.perf_counter()
        with Span("bench.save"):
            r = self.job.trainer.checkpoint_now(self.step, self.job.tree(state, self.step))
        return time.perf_counter() - t0, r

    def first_steps(self, state, n: int):
        """The first ``n`` steps through the window's call, with the
        program's reading of the first gradient (from AdamW's first moment)."""
        state = self.steps(state, 1)
        b1 = self.job.cfg["optimizer"]["b1"]
        self.grad_norms = refmod.leaf_norms(state["opt"]["m"]) / (1 - b1)
        return self.steps(state, n - 1)

    def note_change(self, params) -> None:
        """The program's reading of the change since the initial weights."""
        self.change_norms = _change(params, self.job.init_params())

    def program_readings(self) -> dict:
        return {"losses": [float(x) for x in self.losses[: refmod.CHECK_STEPS]
                           + self.resumed_losses],
                "grad_norms": np.asarray(self.grad_norms).tolist(),
                "change_norms": np.asarray(self.change_norms).tolist()}


@jax.jit
def _change(a, b):
    return refmod.leaf_norms(jax.tree.map(
        lambda x, y: x.astype(np.float32) - y.astype(np.float32), a, b))


# -- train ----------------------------------------------------------------------

def run_train(job: Job, traffic: dict, seconds: float, trace_dir: str | None) -> Run:
    run = Run(job, traffic)
    save = bool(traffic["save"])
    n = int(traffic["trace_steps"] if trace_dir else traffic["steps_per_cycle"])
    state = run.first_steps(job.init_state(), refmod.CHECK_STEPS)
    run.note_change(state["params"])
    if save:
        # fill both shadow buffers and run the digest programs once: saves of
        # the state as it stands, so the incremental persist rewrites nothing
        for _ in range(int(traffic["warmup_saves"])):
            _, r = run.save(state)
            r.wait()
            run.warmup_blocking_s.append(r.blocking_s)
        state = run.steps(state, 1)  # every chunk differs from the last save
    cycles = 1 if trace_dir else None
    jax.block_until_ready(state)
    run.window = {"t_setup_end": time.perf_counter()}
    before = _timings(job.trainer)
    if trace_dir:
        start_trace(trace_dir)
    t0 = time.perf_counter()
    steps0, done = run.step, 0
    with Span("bench.window"):
        while True:
            if save:
                stall, r = run.save(state)
                run.window_saves.append((run.step, stall, r))
            state = run.steps(state, n)
            with Span("bench.sync"):
                jax.block_until_ready(run.losses[-1])
            done += 1
            if (cycles is not None and done >= cycles) or (
                    cycles is None and time.perf_counter() - t0 >= seconds):
                break
    t1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    run.window.update(seconds=t1 - t0, steps=run.step - steps0,
                      timings=_delta(_timings(job.trainer), before))
    run.state = state
    return run


def finish_train(run: Run) -> None:
    """After the window: wait for every persist, count what failed."""
    job = run.job
    job.trainer.finish()
    if run.traffic["save"]:
        run.attempted = len(run.window_saves)
        run.failed = sum(1 for _, _, r in run.window_saves if r.error is not None)
    else:
        run.attempted = run.window["steps"]
        tail = run.losses[-run.attempted:]
        run.failed = sum(1 for x in tail if not np.isfinite(float(x)))


def check_images(run: Run) -> int:
    """Read back every image the policy keeps at the close and compare it
    with the device state at its step. Returns the images that differ or
    are missing."""
    job = run.job
    saved = [s for s, _, r in run.window_saves if r.error is None]
    want = saved[-job.cfg["checkpoint"]["keep_last"]:]
    have = set(job.trainer.restorer.available_steps())
    bad = sum(1 for s in want if s not in have)
    for s in (s for s in want if s in have):
        state, _ = job.trainer.restorer.restore(step=s, sharding_for=job.sharding_for)
        got = np.asarray(fingerprint(state["device"]))
        if (int(np.asarray(state["host"]["step"])) != s
                or not np.array_equal(got, np.asarray(run.saved_fp[s]))):
            bad += 1
        del state
    return bad


# -- resume ---------------------------------------------------------------------

def run_resume(job: Job, traffic: dict, seconds: float, trace_dir: str | None) -> Run:
    if int(traffic["steps_before_save"]) != refmod.CHECK_STEPS - 1:
        raise ValueError("the resumed step is the reference's last checked step")
    run = Run(job, traffic)
    state = run.first_steps(job.init_state(), int(traffic["steps_before_save"]))
    saved_step = run.step
    _, r = run.save(state)
    r.wait()
    del state
    restorer = job.trainer.restorer

    def resume():
        """Drop the state, restore the image, run the next step; returns the
        restored state's fingerprint (device) and the step's loss."""
        evict_page_cache(job.trainer.store.root)
        with Span("bench.restore"):
            restored, _ = restorer.restore(step=saved_step, sharding_for=job.sharding_for,
                                           verify="device")
        dev = restored["device"]
        fp = fingerprint(dev)
        run.step = saved_step
        new = run.steps(dev, 1)
        with Span("bench.sync"):
            jax.block_until_ready(run.losses[-1])
        return fp, run.losses.pop(), new

    warm_failed = 0
    try:  # warm: compiles the verify's digest programs
        fp, loss, new = resume()
        run.resumed_losses.append(loss)
        run.note_change(new["params"])
        del new
        run.restore_fps.append(fp)
        jax.block_until_ready(run.change_norms)
    except Exception as e:  # noqa: BLE001 — a failed resume is counted
        warm_failed, run.error = 1, f"{type(e).__name__}: {e}"
        run.change_norms = [float("nan")] * len(run.grad_norms)
    run.window = {"t_setup_end": time.perf_counter()}
    before = _timings(job.trainer)
    budget = int(traffic["trace_resumes"]) if trace_dir else None
    if trace_dir:
        start_trace(trace_dir)
    t0 = time.perf_counter()
    done = failed = 0
    with Span("bench.window"):
        while True:
            try:
                fp, loss, new = resume()
                run.restore_fps.append(fp)
                run.resumed_losses.append(loss)
                del new
            except Exception as e:  # noqa: BLE001 — a failed resume is counted
                failed += 1
                run.error = f"{type(e).__name__}: {e}"
            done += 1
            if (budget is not None and done >= budget) or (
                    budget is None and time.perf_counter() - t0 >= seconds):
                break
    t1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    run.window.update(seconds=t1 - t0, resumes=done - failed, steps=done - failed,
                      timings=_delta(_timings(job.trainer), before))
    run.attempted, run.failed = done + warm_failed, failed + warm_failed
    run.saved_step = saved_step
    run.state = None
    return run


def check_restores(run: Run) -> int:
    """Every restore of the run against the device state that was saved;
    returns the restores that differ."""
    want = np.asarray(run.saved_fp[run.saved_step])
    return sum(1 for fp in run.restore_fps if not np.array_equal(np.asarray(fp), want))


KINDS = {"train": (run_train, finish_train), "resume": (run_resume, lambda run: run.job.trainer.finish())}
