"""Operation and byte counts, and the table of peaks, on the CPU."""
import dataclasses
import json
import math
from pathlib import Path

import jax
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


def test_qwen2_count_is_pinned_exactly():
    cfg, _ = refmod.load_config("qwen2-0.5b")
    assert flops.n_params(cfg) == 494_032_768
    assert flops.train_flops_per_token(cfg, 1024) == 3_228_437_760.0


# state-spaces/mamba2-130m's config.json keys; the ssm_cfg sizes it leaves to
# mamba_ssm's Mamba2 defaults are stated; vocab_size as the program holds it
MAMBA2_130M = {
    "d_model": 768, "n_layer": 24, "vocab_size": 50280, "tie_embeddings": True,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 128, "d_conv": 4, "expand": 2,
                "headdim": 64, "ngroups": 1, "chunk_size": 256},
}


def _ssd(L, Q, N, G, H, P):
    return 3 * L * (2 * Q * N * G + 2 * H * Q * P + 4 * H * N * P)


def test_mamba2_130m_params_and_flops_per_token():
    assert flops.n_params(MAMBA2_130M) == 128_983_488
    # 6 N + 3 L (2 Q N G + 2 H Q P + 4 H N P), Q = 256, H = 24, P = 64
    assert 6 * 128_983_488 == 773_900_928
    assert _ssd(24, 256, 128, 1, 24, 64) == 117_964_800
    assert flops.train_flops_per_token(MAMBA2_130M, 2048) == 891_865_728.0


@pytest.mark.parametrize("seq_len, chunk", [(128, 128), (256, 256), (4096, 256)])
def test_mamba2_chunk_is_the_sequence_when_shorter(seq_len, chunk):
    ssd = flops.train_flops_per_token(MAMBA2_130M, seq_len) - 6 * 128_983_488
    assert ssd == _ssd(24, chunk, 128, 1, 24, 64)


def test_mamba2_counts_groups_and_an_untied_head():
    cfg = {**MAMBA2_130M, "tie_embeddings": False,
           "ssm_cfg": {**MAMBA2_130M["ssm_cfg"], "ngroups": 2}}
    # in_proj and conv widen by 2 G N - 2 N = 256 columns; the head adds V D
    extra = 50280 * 768 + 24 * (768 * 256 + 5 * 256)
    assert flops.n_params(cfg) == 128_983_488 + extra
    assert flops.train_flops_per_token(cfg, 2048) == (
        6 * (128_983_488 + extra) + _ssd(24, 256, 128, 2, 24, 64))


def _program_mamba2(cfg):
    from repro.configs import get_config

    s = cfg["ssm_cfg"]
    return dataclasses.replace(
        get_config("mamba2-130m"), d_model=cfg["d_model"], num_layers=cfg["n_layer"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_embeddings"],
        ssm_state=s["d_state"], ssm_conv=s["d_conv"], ssm_expand=s["expand"],
        ssm_head_dim=s["headdim"], ssm_chunk=s["chunk_size"])


MAMBA2_SMALL = {
    "d_model": 64, "n_layer": 3, "vocab_size": 300, "tie_embeddings": True,
    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 3, "expand": 2,
                "headdim": 16, "ngroups": 1, "chunk_size": 32},
}


@pytest.mark.parametrize("cfg", [MAMBA2_SMALL, MAMBA2_130M], ids=["small", "published"])
def test_mamba2_params_match_the_program_tree(cfg):
    from repro.models.zoo import build

    # shapes only: no weight is allocated
    shapes = jax.eval_shape(build(_program_mamba2(cfg)).init, jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert len(leaves) == 19
    assert sum(math.prod(x.shape) for x in leaves) == flops.n_params(cfg)


def _without(cfg, key):
    if "." in key:
        outer, inner = key.split(".")
        return {**cfg, outer: {k: v for k, v in cfg[outer].items() if k != inner}}
    return {k: v for k, v in cfg.items() if k != key}


@pytest.mark.parametrize("base, key", [
    ("qwen2", "num_key_value_heads"), ("qwen2", "intermediate_size"),
    ("qwen2", "hidden_size"), ("mamba2", "n_layer"), ("mamba2", "tie_embeddings"),
    ("mamba2", "ssm_cfg.d_state"), ("mamba2", "ssm_cfg.chunk_size"),
])
def test_a_missing_key_raises_a_value_error_that_names_it(base, key):
    cfg = refmod.load_config("qwen2-0.5b")[0] if base == "qwen2" else MAMBA2_130M
    cfg = _without(cfg, key)
    with pytest.raises(ValueError, match=f"'{key}'"):
        flops.train_flops_per_token(cfg, 1024)
    with pytest.raises(ValueError, match=f"'{key}'"):
        flops.n_params(cfg)


ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("entry", json.loads((ROOT / "BENCHMARK.json").read_text())["configs"],
                         ids=lambda e: e["name"])
def test_every_benchmark_configuration_has_a_flop_count(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    per_token = flops.train_flops_per_token(cfg, cfg["seq_len"])
    assert math.isfinite(per_token) and per_token > 0
