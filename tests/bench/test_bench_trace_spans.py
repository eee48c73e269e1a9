"""The reduction of the program's spans: self time, bytes and the split of
the device's idle time, on a small synthetic trace (``trace_spans.json``)
and on a save recorded on a TPU v5e (``trace_save_v5e.json.gz``).

On one thread: a save whose ``ckpt/blocking`` holds ``shadow/fetch``
(``shadow/d2h`` then ``shadow/copy``, which holds a runtime event),
``shadow/digest`` while the device digests, and ``ckpt/submit``; a
``shadow/d2h`` on a second thread; ``ckpt/gc`` across the window's end and
``store/read`` past it. Device busy [0, 100] and [700, 800] of the window
[0, 1000] us.
"""
import gzip
import json
import math
from pathlib import Path

import pytest

from bench import span_reduce, trace_reduce

HERE = Path(__file__).resolve().parent


@pytest.fixture
def red():
    return span_reduce.reduce(json.loads((HERE / "trace_spans.json").read_text()))


@pytest.mark.parametrize("name,total,self_,count,nbytes", [
    ("ckpt/blocking", 760, 80, 1, 0),      # less fetch, digest, submit
    ("shadow/fetch", 500, 20, 1, 0),       # less d2h and copy
    ("shadow/d2h", 350, 350, 2, 5120),     # both threads
    ("shadow/copy", 180, 180, 1, 4096),    # a runtime event is not a child
    ("shadow/digest", 140, 140, 1, 0),
    ("ckpt/submit", 40, 40, 1, 0),
    ("ckpt/gc", 50, 50, 1, 0),             # clipped at the window's end
])
def test_program_spans_total_self_count_bytes(red, name, total, self_, count, nbytes):
    row = red["spans"][name]
    assert row["total_s"] == pytest.approx(total * 1e-6)
    assert row["self_s"] == pytest.approx(self_ * 1e-6)
    assert (row["count"], row["bytes"]) == (count, nbytes)


def test_only_program_spans_inside_the_window_are_kept(red):
    assert "store/read" not in red["spans"]  # after the window
    assert not any(n.startswith("bench.") or "::" in n for n in red["spans"])


def test_idle_time_goes_to_the_innermost_covering_span(red):
    want = {"shadow/d2h": 300, "shadow/copy": 180, "ckpt/blocking": 80,
            "shadow/digest": 40, "bench.save": 40, "ckpt/submit": 40,
            "shadow/fetch": 20, "ckpt/gc": 50, "other": 50}
    got = red["idle_by_span"]
    assert set(got) == set(want)
    for name, us in want.items():
        assert got[name] == pytest.approx(us * 1e-6), name
    assert sum(got.values()) == pytest.approx(red["idle_s"])
    whole = trace_reduce.reduce(json.loads((HERE / "trace_spans.json").read_text()))
    assert red["idle_s"] == pytest.approx(whole["window_s"] - whole["busy_s"])


def test_idle_by_span_is_largest_first(red):
    got = list(red["idle_by_span"].items())
    assert got[0] == ("shadow/d2h", pytest.approx(300e-6))
    assert [v for _, v in got] == sorted((v for _, v in got), reverse=True)


def test_without_program_spans_idle_goes_to_bench_spans_or_other():
    red = span_reduce.reduce(json.loads((HERE / "trace_small.json").read_text()))
    assert red["spans"] == {}
    got = {k: round(v * 1e6) for k, v in red["idle_by_span"].items()}
    assert got == {"bench.save": 350, "other": 300, "bench.step": 10}


def test_recorded_v5e_save_trace():
    """A save and 10 steps of qwen2-0.5b.save-dense traced on a TPU v5e (the
    program runs, the bench and program spans, and the device ops of 0.5 ms
    or more, kept from the run)."""
    with gzip.open(HERE / "trace_save_v5e.json.gz", "rt") as f:
        red = span_reduce.reduce(json.load(f))
    spans = red["spans"]
    d2h, copy, submit, fetch = (span_reduce.seconds(red, n) for n in (
        "shadow/d2h", "shadow/copy", "ckpt/submit", "shadow/fetch"))
    assert all(math.isfinite(x) and x > 0 for x in (d2h, copy, submit, fetch))
    assert d2h + copy + submit >= 0.9 * (fetch + submit)
    # one transfer and one copy per leaf: 43 device leaves and the host step,
    # 494,032,768 parameters in bf16 with two f32 moments, an i32 and an i64
    for name in ("shadow/d2h", "shadow/copy", "shadow/fetch", "shadow/digest"):
        assert spans[name]["count"] == 44, name
    assert spans["shadow/d2h"]["bytes"] == spans["shadow/copy"]["bytes"] == (
        494_032_768 * 10 + 4 + 8)
    assert spans["ckpt/submit"]["count"] == spans["ckpt/blocking"]["count"] == 1
