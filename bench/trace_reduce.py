"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The profiler writes ``perfetto_trace.json.gz`` (Chrome trace events) under
``<dir>/plugins/profile/<run>/``. Device processes are named
``/device:TPU:<n>``; their ``XLA Ops`` thread holds one complete event per
operation run on the device, and their ``XLA Modules`` thread one per
program run. The benchmark's own host spans (``bench.*``
``TraceAnnotation``\\ s) are on the same clock.

- busy: the union of a device's operation intervals inside the traced
  window (the ``bench.window`` span), averaged over the devices;
- modules: device seconds and runs per program, by program name;
- gaps: each idle interval of the first device, named by the ``bench.*``
  span that covers most of it on the host.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")


def load(trace_dir: str) -> dict:
    """The newest perfetto trace under ``trace_dir``."""
    found = sorted(Path(trace_dir).rglob("perfetto_trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no perfetto_trace.json.gz under {trace_dir}")
    with gzip.open(found[-1], "rt") as f:
        return json.load(f)


def _names(events: list) -> tuple[dict, dict]:
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    return procs, threads


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(trace: dict) -> dict:
    """Busy and idle time, per-program device time and named idle gaps.
    Times in seconds."""
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    procs, threads = _names(events)
    devices = sorted(p for p, n in procs.items() if n.startswith("/device:"))
    ops, mods, spans = defaultdict(list), defaultdict(list), []
    for e in events:
        if e.get("ph") != "X":
            continue
        iv = (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
        thread = threads.get((e["pid"], e.get("tid")), "")
        if e["pid"] in devices:
            if thread == "XLA Ops":
                ops[e["pid"]].append((iv, e["name"]))
            elif thread == "XLA Modules":
                mods[e["pid"]].append((iv, _SUFFIX.sub("", e["name"])))
        elif e["name"].startswith("bench."):
            spans.append((iv, e["name"]))
    win = [iv for iv, n in spans if n == WINDOW]
    if win:
        lo, hi = win[0]
    else:
        allv = [iv for d in devices for iv, _ in ops[d]] or [(0.0, 0.0)]
        lo, hi = min(a for a, _ in allv), max(b for _, b in allv)
    busy_per, gaps = [], []
    for i, d in enumerate(devices):
        busy = union(_clip([iv for iv, _ in ops[d]], lo, hi))
        busy_per.append(sum(b - a for a, b in busy))
        if i == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    modules: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    op_time: dict[str, float] = defaultdict(float)
    for d in devices:
        for (a, b), name in mods[d]:
            if b > lo and a < hi:
                modules[name][0] += (min(b, hi) - max(a, lo)) / 1e6
                modules[name][1] += 1
        for (a, b), name in ops[d]:
            if b > lo and a < hi:
                op_time[name] += (min(b, hi) - max(a, lo)) / 1e6
    inner = [(iv, n) for iv, n in spans if n != WINDOW]
    named = []
    for a, b in gaps:
        cover = defaultdict(float)
        for (s, e), n in inner:
            cover[n] += max(0.0, min(b, e) - max(a, s))
        name = max(cover, key=cover.get) if cover and max(cover.values()) > 0 else "other"
        named.append((name, (b - a) / 1e6))
    n_dev = max(1, len(devices))
    return {
        "devices": len(devices),
        "window_s": (hi - lo) / 1e6,
        "busy_s": sum(busy_per) / n_dev / 1e6,
        "modules": {k: (v[0] / n_dev, v[1] / n_dev) for k, v in modules.items()},
        "ops": dict(op_time),
        "gaps": sorted(named, key=lambda x: -x[1]),
    }


def module_seconds(red: dict, prefix: str) -> tuple[float, float]:
    """Device seconds and runs (per device) of the programs named ``prefix*``."""
    hits = [v for k, v in red["modules"].items() if k.startswith(prefix)]
    return sum(s for s, _ in hits), sum(n for _, n in hits)


def breakdown(red: dict) -> dict:
    """The ten costliest device operations and the ten longest idle gaps."""
    ops = sorted(red["ops"].items(), key=lambda x: -x[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red["gaps"][:10]]}
