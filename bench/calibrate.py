#!/usr/bin/env python3
"""Readings that the limits of a configuration are set from.

    python bench/calibrate.py --config qwen2-0.5b --seeds 12 --control-seeds 3

For each seed, in one process: the program's first three steps through the
cells' own call (``generator.Run.first_steps``) against the float32
reference, and, on the first ``--control-seeds`` seeds, the control (the
reference with every product's operands rounded through fp8) and the
planted fault that drops half of every batch, each put in the program's
place and judged by the harness's own comparison (``harness.judge``)
against the configuration's limits file. Prints one JSON line per seed and
a summary: the largest program reading of each number (the lower reading),
the smallest of the control and of the fault (the upper readings), and
each one's verdict. Exits non-zero if the program reads not correct, or
the control or the fault reads correct, on any seed.

Runs on the chip at the configuration's own size; the CPU tests call
``readings`` with the small copy of the configuration (``ref.small``).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def readings(cfg: dict, ref, seeds: list[int], control_seeds: int,
             limits: dict) -> list[dict]:
    from bench import generator
    from bench import reference as refmod
    from bench.harness import judge
    from bench.job import Job

    def judged(got: dict, f32: dict) -> dict:
        correct, _ = judge(got, f32, limits)
        return {**refmod.compare(got, f32), "correct": correct}

    rows = []
    for i, seed in enumerate(seeds):
        with tempfile.TemporaryDirectory(prefix="crum-calibrate-") as work:
            job = Job(cfg, ref, seed=seed, chips=1, store_root=work)
            run = generator.Run(job, {"kind": "train"})
            run.note_change(run.first_steps(job.init_state(), refmod.CHECK_STEPS)["params"])
            prog = run.program_readings()
            job.trainer.finish()
            del run, job
        f32 = refmod.reference_readings(cfg, ref, seed)
        row = {"seed": seed, "program": judged(prog, f32)}
        if i < control_seeds:
            row["control"] = judged(
                refmod.reference_readings(cfg, ref, seed, dot=ref.lowp_dot), f32)
            row["half_batch"] = judged(
                refmod.reference_readings(cfg, ref, seed, half_batch=True), f32)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def summary(rows: list[dict]) -> dict:
    out = {"lower": {k: max(r["program"][k] for r in rows) for k in NUMBERS},
           "program_correct": all(r["program"]["correct"] for r in rows)}
    for kind in ("control", "half_batch"):
        got = [r[kind] for r in rows if kind in r]
        if got:
            out[kind] = {k: min(g[k] for g in got) for k in NUMBERS}
            out[f"{kind}_correct"] = any(g["correct"] for g in got)
    return out


def sound(s: dict) -> bool:
    """The program reads correct on every seed; the control and the fault
    on none."""
    return s["program_correct"] and not s.get("control_correct") and not s.get(
        "half_batch_correct")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench import reference as refmod

    harness.enable_compile_cache()
    cfg, ref = refmod.load_config(args.config)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = readings(cfg, ref, seeds, args.control_seeds, refmod.load_limits(args.config))
    s = summary(rows)
    print(json.dumps({"summary": s, "seconds": time.perf_counter() - T_PROCESS}), flush=True)
    return 0 if sound(s) else 1


if __name__ == "__main__":
    sys.exit(main())
