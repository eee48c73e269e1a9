"""The program's own host spans in a ``jax.profiler`` trace, and the
device's idle time under them.

The program's timers (``Timings.measure``) are spans of the same name in
the trace, named ``<layer>/<what>``, some with a ``bytes`` count. This
reads them from the same trace events as ``trace_reduce``, inside the
traced window (the ``bench.window`` span):

- spans: per name, total and self seconds, count and the sum of their
  ``bytes``. Self time is a span's time less the part of it that spans of
  the program nested in it on the same thread cover. The runtime's host
  events (compiler passes, ``PjitFunction(...)``) are not the program's
  and are left out;
- idle_by_span: the first device's idle time, split over the innermost
  host span over each part of it: a program span where one is there,
  else a ``bench.*`` span, else ``other``.

The per-layer metrics read the same timers from the window's ``Timings``;
this reduction is for recorded traces and their tests.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench.trace_reduce import WINDOW, _names, union

PROGRAM = re.compile(r"^[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$")


def reduce(trace: dict) -> dict:
    """{"window_s", "idle_s", "spans", "idle_by_span"}; times in seconds."""
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    procs, threads = _names(events)
    devices = sorted(p for p, n in procs.items() if n.startswith("/device:"))
    ops, bench, prog = [], [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        iv = (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
        if e["pid"] in devices:
            if e["pid"] == devices[0] and threads.get((e["pid"], e.get("tid"))) == "XLA Ops":
                ops.append(iv)
        elif e["name"].startswith("bench."):
            bench.append((iv, e["name"]))
        elif PROGRAM.match(e["name"]):
            nbytes = (e.get("args") or {}).get("bytes")
            prog.append((iv, e["name"], (e["pid"], e.get("tid")),
                         int(float(nbytes)) if nbytes is not None else 0))
    win = [iv for iv, n in bench if n == WINDOW]
    lo, hi = win[0] if win else (min(a for a, _ in ops or [(0.0, 0.0)]),
                                 max(b for _, b in ops or [(0.0, 0.0)]))
    busy = union([(max(a, lo), min(b, hi)) for a, b in ops if b > lo and a < hi])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    prog = [((max(a, lo), min(b, hi)), n, t, nb) for (a, b), n, t, nb in prog
            if b > lo and a < hi]
    inner = [(iv, n) for iv, n in bench if n != WINDOW]
    return {
        "window_s": (hi - lo) / 1e6,
        "idle_s": sum(b - a for a, b in gaps) / 1e6,
        "spans": _span_table(prog),
        "idle_by_span": _split(gaps, _innermost(prog, inner, lo, hi)),
    }


def seconds(red: dict, name: str, key: str = "total_s") -> float | None:
    """Seconds (``total_s`` or ``self_s``) of the program's spans ``name``
    in the window; None where the trace holds none."""
    row = red["spans"].get(name)
    return row[key] if row else None


def _span_table(prog: list) -> dict:
    """{name: {total_s, self_s, count, bytes}} of the (clipped) program spans."""
    table: dict[str, dict] = {}
    by_thread = defaultdict(list)
    for i, (iv, name, thread, nbytes) in enumerate(prog):
        by_thread[thread].append((iv, i))
        row = table.setdefault(name, {"total_s": 0.0, "self_s": 0.0, "count": 0, "bytes": 0})
        row["total_s"] += (iv[1] - iv[0]) / 1e6
        row["count"] += 1
        row["bytes"] += nbytes
    children = defaultdict(list)
    for items in by_thread.values():
        stack: list = []
        for (a, b), i in sorted(items, key=lambda x: (x[0][0], -x[0][1], x[1])):
            while stack and not (stack[-1][0][0] <= a and b <= stack[-1][0][1]):
                stack.pop()
            if stack:
                children[stack[-1][1]].append((a, b))
            stack.append(((a, b), i))
    for i, ((a, b), name, _, _) in enumerate(prog):
        covered = sum(y - x for x, y in union(children[i]))
        table[name]["self_s"] += (b - a - covered) / 1e6
    return table


def _innermost(prog: list, bench: list, lo: float, hi: float) -> list:
    """[(start, end, name)] tiling [lo, hi]: each piece named by the
    innermost program span over it (the one that started last), else the
    innermost ``bench.*`` span, else ``other``."""
    cands = [(a, b, n, 0) for (a, b), n, _, _ in prog]
    cands += [(max(a, lo), min(b, hi), n, 1) for (a, b), n in bench if b > lo and a < hi]
    edges = defaultdict(list)
    for i, (a, b, _, _) in enumerate(cands):
        edges[a].append(i)
        edges[b].append(i)
    edges.setdefault(lo, [])
    edges.setdefault(hi, [])
    times = sorted(edges)
    active: set[int] = set()
    out = []
    for t, t_next in zip(times, times[1:]):
        for i in edges[t]:
            a, b = cands[i][:2]
            if t == a and b > a:
                active.add(i)
            if t == b:
                active.discard(i)
        if active:
            name = cands[min(active, key=lambda i: (cands[i][3], -cands[i][0],
                                                    cands[i][1] - cands[i][0]))][2]
        else:
            name = "other"
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], t_next, name)
        else:
            out.append((t, t_next, name))
    return out


def _split(gaps: list, labels: list) -> dict:
    """Seconds of the idle ``gaps`` under each label, largest first; both
    lists sorted by time."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(labels) and labels[j][1] <= a:
            j += 1
        k = j
        while k < len(labels) and labels[k][0] < b:
            s, e, name = labels[k]
            out[name] += (min(b, e) - max(a, s)) / 1e6
            k += 1
    return dict(sorted(out.items(), key=lambda x: -x[1]))

