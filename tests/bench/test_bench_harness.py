"""The harness driven through one short window of each cell on the CPU, at
a small copy of the cell's configuration."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench import reference as refmod

CELLS = ["qwen2-0.5b.save-dense", "qwen2-0.5b.resume", "qwen2-0.5b.no-ckpt"]
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
SEED = 2**33 + 12345  # past 32 bits, as a run's seed may be


def run_small(cell: str, trace: bool, seconds: float = 1.0) -> dict:
    cfg, ref = refmod.load_config("qwen2-0.5b")
    return harness.run_cell(cell, SEED, seconds, trace, t_process=time.perf_counter(),
                            cfg=ref.small(cfg), require_tpu=False)


@pytest.mark.parametrize("trace", [0, 1], ids=["measured", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_runs_a_window_and_prints_the_line(cell, trace, no_compile_cache):
    out = run_small(cell, bool(trace))
    assert list(out)[: 5] == LINE_KEYS[:5] and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    doc = harness.load_benchmark()
    want = {m["name"] for m in harness.metrics_for(doc, cell, bool(trace))}
    assert set(out["metrics"]) <= want
    if not trace:  # the end-to-end metrics read the host clock: all present
        assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0 or m["unit"] == "%"
    assert out["device"]["platform"] == "cpu"
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_no_image_is_left_behind(no_compile_cache, tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", None)
    before = set(os.listdir(harness.ROOT))
    run_small("qwen2-0.5b.save-dense", False)
    assert set(os.listdir(harness.ROOT)) == before
    assert os.listdir(tmp_path) == []


def _cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.no-ckpt",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_platform_without_tpu():
    out = _cli(harness.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_cli_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p)
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
