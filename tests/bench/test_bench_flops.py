"""Operation and byte counts, and the table of peaks, on the CPU."""
import json

import jax.numpy as jnp
import pytest

from bench import flops, peaks
from bench import reference as refmod


def test_qwen2_params_and_flops_per_token():
    cfg, _ = refmod.load_config("qwen2-0.5b")
    assert flops.n_params(cfg) == 494_032_768
    # PaLM: 6 N + 12 L H Q T at T = 1024
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(3.228e9, rel=1e-3)


def test_param_count_matches_the_reference_layout():
    cfg, ref = refmod.load_config("qwen2-0.5b")
    shapes = ref.param_shapes(cfg)
    leaves = [x for x in _leaves(shapes)]
    assert sum(int(jnp.prod(jnp.array(s))) for s, _ in leaves) == flops.n_params(cfg)


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def test_tree_bytes_reads_each_leaf_once():
    leaves = [jnp.zeros((3, 5), jnp.bfloat16), jnp.zeros((7,), jnp.float32)]
    assert flops.tree_bytes(leaves) == 3 * 5 * 2 + 7 * 4


def test_peaks_by_device_kind():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


def test_digest_roofline_from_trace_numbers():
    ctx = {"trace": {"modules": {"jit__chunk_digests_jit": (0.01, 42)}},
           "peak": peaks.peak("TPU v5 lite"), "state_bytes": 819_000_000}
    # one pass over 819 MB at 819 GB/s takes 1 ms; the programs took 10 ms
    assert flops.digest_roofline(ctx, 1) == pytest.approx(10.0)
    assert flops.digest_roofline({**ctx, "trace": None}, 1) is None


def test_benchmark_names_a_file_for_every_metric_traffic_and_config():
    from bench.harness import HERE, ROOT

    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for w in doc["workloads"]:
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        for suffix in (".json", ".ref.py", ".limits.json"):
            assert (HERE / "configs" / f"{w['config']}{suffix}").is_file()
