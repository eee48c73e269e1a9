"""The trace reduction on a small trace in the profiler's Chrome format:
one TPU device with its modules and ops lines, host spans of the bench."""
import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import trace_reduce

HERE = Path(__file__).resolve().parent


@pytest.fixture
def red():
    return trace_reduce.reduce(json.loads((HERE / "trace_small.json").read_text()))


def test_busy_is_the_union_of_device_ops_inside_the_window(red):
    # ops [10,150] and [140,300] overlap; [600,650]; [1200,1250] is outside
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["busy_s"] == pytest.approx(340e-6)


def test_device_time_by_program_name(red):
    assert trace_reduce.module_seconds(red, "jit_step_fn") == pytest.approx((290e-6, 1))
    assert trace_reduce.module_seconds(red, "jit__chunk_digests_jit") == pytest.approx(
        (50e-6, 1))
    assert trace_reduce.module_seconds(red, "jit_nothing") == (0, 0)


def test_idle_gaps_are_named_by_the_covering_bench_span(red):
    names = [(n, round(s * 1e6)) for n, s in red["gaps"]]
    assert names == [("bench.save", 350), ("bench.save", 300), ("bench.step", 10)]


def test_breakdown_lists_the_costliest_ops_and_longest_gaps(red):
    b = trace_reduce.breakdown(red)
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(160e-6)]
    assert len(b["device_ops"]) == 3  # the op outside the window is left out
    assert b["idle_gaps"][0] == ["bench.save", pytest.approx(350e-6)]


def test_load_finds_the_profilers_file(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "perfetto_trace.json.gz", "wt") as f:
        shutil.copyfileobj(open(HERE / "trace_small.json"), f)
    assert trace_reduce.reduce(trace_reduce.load(str(tmp_path)))["busy_s"] == pytest.approx(
        340e-6)


def test_union_merges_touching_and_nested_intervals():
    assert trace_reduce.union([(5, 6), (0, 2), (2, 3), (1, 1.5)]) == [(0, 3), (5, 6)]


def test_recorded_v5e_resume_trace():
    """Two resumes of qwen2-0.5b traced on a TPU v5e (the program runs, the
    bench spans, and the device ops of 0.5 ms or more, kept from the run)."""
    with gzip.open(HERE / "trace_resume_v5e.json.gz", "rt") as f:
        red = trace_reduce.reduce(json.load(f))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    # one digest program run per leaf of the 43-leaf train state, per resume
    assert trace_reduce.module_seconds(red, "jit__chunk_digests_jit")[1] == 86
    assert trace_reduce.module_seconds(red, "jit_step_fn")[1] == 2
    # the device waits out each restore's read: the two longest gaps
    (n1, s1), (n2, s2) = red["gaps"][:2]
    assert n1 == n2 == "bench.restore" and min(s1, s2) > 10
