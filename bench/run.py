#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of BENCHMARK.json.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips: it sets up, warms up, measures for
``--seconds`` (``--trace 1``: a short traced window instead), checks what
the timed path produced against the plain reference, and prints one JSON
line as the last line of standard output. The numbers compared, each with
its limit, are the last lines of standard error and the last key of that
line. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime logs under /tmp unless told otherwise; a run writes
    # only in its checkout and its TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                               t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
