"""The mamba2-130m configuration through the harness on the CPU, at a small
copy of it (``ref.small``) and with its own limits file: the cell's window
and line, the planted faults, the float8 control, the reference against
the program, and the state layout and counts of the file at full size."""
import math
import time

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, flops, harness
from bench import reference as refmod
from test_bench_faults import _plant, half_batch, unchanged

CONFIG = "mamba2-130m"
CELL = "mamba2-130m.save-dense"
SEED = 2**33 + 4242  # past 32 bits, as a run's seed may be
CONTROL_SEEDS = [2**31 + 5, 2**32 + 9]


def run_small(trace: bool = False) -> dict:
    cfg, ref = refmod.load_config(CONFIG)
    return harness.run_cell(CELL, SEED, 1.0, trace, t_process=time.perf_counter(),
                            cfg=ref.small(cfg), require_tpu=False)


@pytest.mark.parametrize("trace", [0, 1], ids=["measured", "traced"])
def test_the_cell_runs_a_window_and_prints_the_line(trace, no_compile_cache):
    out = run_small(bool(trace))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["image_mismatches"]["value"] == 0
    want = {m["name"] for m in harness.metrics_for(harness.load_benchmark(), CELL, bool(trace))}
    assert set(out["metrics"]) <= want
    if not trace:
        assert set(out["metrics"]) == want
    else:  # the device readers find no chip in a CPU trace; the host's do
        assert {"ckpt_stall_s", "persist_mb_per_s"} <= set(out["metrics"])
        assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault,number", [(unchanged, "change_gap"),
                                          (half_batch, "loss_gap")],
                         ids=["state-unchanged", "half-batch"])
def test_a_broken_step_is_not_correct(fault, number, monkeypatch, no_compile_cache):
    _plant(monkeypatch, fault)
    out = run_small()
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]
    assert out["correct"] is False


def test_altered_image_bytes_are_not_correct(monkeypatch, no_compile_cache):
    from repro.checkpoint.store import ChunkStore

    real = ChunkStore.Writer.append

    def append(self, raw, codec_name, *, index, digest):
        if index == 0:  # the first chunk of every leaf, as it is written
            raw = bytes([raw[0] ^ 0x40]) + bytes(raw[1:])
        return real(self, raw, codec_name, index=index, digest=digest)

    monkeypatch.setattr(ChunkStore.Writer, "append", append)
    out = run_small()
    assert out["checks"]["image_mismatches"]["value"] > 0, out["checks"]
    assert out["correct"] is False


@pytest.fixture(scope="module")
def rows():
    # judged at 6 layers: the control's gradient error grows with depth (the
    # first layers' SSD scalars take the most), and at 2 layers it stays a
    # fifth of what the limits were set from at 24
    cfg, ref = refmod.load_config(CONFIG)
    deep = ref.small(cfg)
    deep["n_layer"] = deep["program"]["fields"]["num_layers"] = 6
    return calibrate.readings(deep, ref, CONTROL_SEEDS, len(CONTROL_SEEDS),
                              refmod.load_limits(CONFIG))


@pytest.mark.parametrize("kind", ["control", "half_batch"])
def test_control_and_fault_are_not_correct_under_the_limits(rows, kind):
    limits = refmod.load_limits(CONFIG)
    for r in rows:
        assert r[kind]["correct"] is False, r[kind]
        assert any(r[kind][k] > lim for k, lim in limits.items())


def test_program_is_correct_and_the_control_separates(rows):
    s = calibrate.summary(rows)
    assert s["program_correct"] and calibrate.sound(s)
    assert s["control"]["loss_gap"] >= 3 * s["lower"]["loss_gap"]
    assert s["half_batch"]["loss_gap"] >= 10 * s["lower"]["loss_gap"]


def test_reference_matches_the_program_at_small_size():
    cfg, ref = refmod.load_config(CONFIG)
    for r in calibrate.readings(ref.small(cfg), ref, CONTROL_SEEDS, 0,
                                refmod.load_limits(CONFIG)):
        p = r["program"]
        assert p["loss_gap"] < 1e-3 and p["grad_gap"] < 0.02 and p["change_gap"] < 0.05, p


def test_reference_quadratic_ssd_masks_before_the_exponential():
    """A decay span far past 88 inside the sequence: the reference's loss
    and gradients stay finite (its SSD is the masked quadratic form)."""
    cfg, ref = refmod.load_config(CONFIG)
    cfg = ref.small(cfg)
    params = ref.init_params(jax.random.key(3), cfg)
    params["blocks"]["ssm"]["dt_bias"] = jnp.ones_like(params["blocks"]["ssm"]["dt_bias"])
    batch = refmod.batch_at(jax.random.key(4), 1, 2, cfg["seq_len"], cfg["vocab_size"])
    loss, grads = jax.jit(lambda p, b: ref.loss_and_grads(p, b, cfg))(params, batch)
    assert math.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_state_layout_at_full_size():
    """What the save path carries: 19 parameter leaves, their two AdamW
    moments and the step; f32 parameters beside bf16 ones."""
    cfg, ref = refmod.load_config(CONFIG)
    params = jax.eval_shape(lambda k: ref.init_params(k, cfg), jax.random.key(0))
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)  # noqa: E731
    state = jax.tree.leaves({"params": params, "opt": {"m": jax.tree.map(f32, params),
                                                       "v": jax.tree.map(f32, params)},
                             "step": jax.ShapeDtypeStruct((), jnp.int32)})
    sizes = [math.prod(x.shape) * x.dtype.itemsize for x in state]
    mib = 1 << 20
    assert len(state) == 58 and flops.tree_bytes(state) == 1_289_899_780
    assert sum(b < mib for b in sizes) == 38
    assert len({(x.shape, str(x.dtype)) for x in state}) == 24
    assert sum(max(1, -(-b // mib)) for b in sizes) == 1268
    f32_params = sorted(k for k, v in params["blocks"]["ssm"].items() if v.dtype == jnp.float32)
    assert f32_params == ["A_log", "D", "dt_bias"]


def test_params_and_flops_of_the_file_are_pinned():
    cfg, _ = refmod.load_config(CONFIG)
    # the count at vocabulary 50,280, plus the 8 rows of 768 that pad it to 50,288
    assert flops.n_params(cfg) == 128_983_488 + 8 * 768 == 128_989_632
    assert flops.train_flops_per_token(cfg, cfg["seq_len"]) == 6 * 128_989_632 + 117_964_800
