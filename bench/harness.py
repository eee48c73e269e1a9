"""Runs one cell of BENCHMARK.json and builds its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name that BENCHMARK.json gives:

- ``configs/<config>.json`` (sizes), ``configs/<config>.ref.py`` (plain
  reference), ``configs/<config>.limits.json`` (the numbers compared with
  the reference, each with its limit);
- ``traffic/<mix>.json`` (parameters the one generator reads);
- ``metrics/<metric>.py`` (a reader: ``read(ctx) -> float | None``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path

import jax

from bench import flops, generator, peaks, trace_reduce
from bench import reference as refmod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(doc: dict, cell: str, trace: bool) -> list[dict]:
    group = doc["per_layer"] if trace else doc["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def check_devices(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def enable_compile_cache() -> None:
    """The program's cache directory (inside the checkout unless
    JAX_COMPILATION_CACHE_DIR says otherwise), holding every program."""
    from repro.runtime.env import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, cfg: dict | None = None,
             require_tpu: bool = True) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``cfg`` stands in for the configuration's file (the CPU tests run a
    small copy); ``require_tpu`` is for those tests alone.
    """
    from bench.job import Job

    doc = load_benchmark()
    cell = next(w for w in doc["workloads"] if w["name"] == name)
    if require_tpu:
        check_devices(cell["chips"])
    file_cfg, ref = refmod.load_config(cell["config"])
    cfg = cfg or file_cfg
    limits = refmod.load_limits(cell["config"])
    traffic = load_traffic(cell["traffic"])
    run_fn, finish_fn = generator.KINDS[traffic["kind"]]
    enable_compile_cache()
    work = Path(tempfile.mkdtemp(prefix="crum-bench-"))
    try:
        job = Job(cfg, ref, seed=seed, chips=cell["chips"], store_root=str(work / "store"))
        trace_dir = str(work / "trace") if trace else None
        run = run_fn(job, traffic, seconds, trace_dir)
        finish_fn(run)
        if run.error:
            print(f"bench: {run.failed} failed; last: {run.error}", file=sys.stderr)
        memory_peak = job.device_peak_bytes()
        checks = {}
        if traffic["kind"] == "resume":
            checks["restore_mismatches"] = (generator.check_restores(run), 0)
        elif traffic.get("save"):
            checks["image_mismatches"] = (generator.check_images(run), 0)
        checks["failed"] = (run.failed, 0)
        dev = jax.devices()
        device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
                  "count": len(dev), "memory_peak_bytes": memory_peak}
        ctx = _context(run, cfg, cell, traffic, t_process, device)
        red = None
        if trace:
            red = trace_reduce.reduce(trace_reduce.load(trace_dir))
            ctx["trace"] = red
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        metrics = {}
        for m in metrics_for(doc, name, trace):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        prog = run.program_readings()
        run.state = None
        del run, job
        gc.collect()
        correct, checks = judge(prog, refmod.reference_readings(cfg, ref, seed),
                                limits, checks)
        out = {"correct": bool(correct), "attempted": ctx["attempted"],
               "failed": ctx["failed"], "metrics": metrics, "device": device}
        if red is not None:
            out["breakdown"] = trace_reduce.breakdown(red)
        out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
        return _finite(out)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def judge(prog: dict, ref_readings: dict, limits: dict,
          checks: dict | None = None) -> tuple[bool, dict]:
    """The comparison that decides ``correct``: every number the limits
    file holds, read from ``prog`` against the reference's readings, and
    the run's own counts in ``checks``, each at or under its limit.
    Returns (correct, {name: (value, limit)})."""
    checks = dict(checks or {})
    gaps = refmod.compare(prog, ref_readings)
    for k, lim in limits.items():
        checks[k] = (gaps[k], lim)
    return all(val <= lim for val, lim in checks.values()), checks


def _finite(x):
    """The line as strict JSON: a number that is not finite becomes null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def _context(run, cfg, cell, traffic, t_process, device) -> dict:
    """What the metric readers read."""
    job, w = run.job, run.window
    kind = device["kind"]
    return {
        "kind": traffic["kind"], "save": bool(traffic.get("save")),
        "chips": cell["chips"],
        "setup_s": w["t_setup_end"] - t_process,
        "window_s": w["seconds"], "steps": w["steps"],
        "resumes": w.get("resumes", 0),
        "tokens": w["steps"] * job.tokens_per_step,
        "tokens_per_step": job.tokens_per_step,
        "stalls_s": [s for _, s, _ in run.window_saves],
        "results": [r for _, _, r in run.window_saves],
        "warmup_blocking_s": run.warmup_blocking_s,
        "timings": w["timings"],
        "attempted": run.attempted, "failed": run.failed,
        "flops_per_token": flops.train_flops_per_token(cfg, cfg["seq_len"]),
        "peak": peaks.peak(kind) if device["platform"] == "tpu" else None,
        "state_bytes": flops.tree_bytes(job.state_shapes()),
        "trace": None,
    }
