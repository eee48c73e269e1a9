"""The control reads not correct: the reference computed with every
product's operands rounded through fp8, put in the program's place and
judged by the harness's own comparison against the configuration's limits
file, at a small copy of the configuration, on the CPU. (On the chip, at
the cell's own size, the same readings set the limits:
``python bench/calibrate.py``.)"""
import pytest

from bench import calibrate
from bench import reference as refmod

SEEDS = [2**31 + 5, 2**32 + 9]


@pytest.fixture(scope="module")
def rows():
    cfg, ref = refmod.load_config("qwen2-0.5b")
    return calibrate.readings(ref.small(cfg), ref, SEEDS, len(SEEDS),
                              refmod.load_limits("qwen2-0.5b"))


@pytest.mark.parametrize("kind", ["control", "half_batch"])
def test_control_and_fault_are_not_correct_under_the_limits(rows, kind):
    limits = refmod.load_limits("qwen2-0.5b")
    for r in rows:
        assert r[kind]["correct"] is False, r[kind]
        assert any(r[kind][k] > lim for k, lim in limits.items())


def test_program_is_correct_and_the_control_separates(rows):
    s = calibrate.summary(rows)
    assert s["program_correct"] and calibrate.sound(s)
    assert s["control"]["loss_gap"] >= 3 * s["lower"]["loss_gap"]
    assert s["half_batch"]["loss_gap"] >= 10 * s["lower"]["loss_gap"]


def test_reference_matches_the_program_at_small_size():
    cfg, ref = refmod.load_config("qwen2-0.5b")
    rows = calibrate.readings(ref.small(cfg), ref, [17], 0, refmod.load_limits("qwen2-0.5b"))
    p = rows[0]["program"]
    assert p["loss_gap"] < 1e-3 and p["grad_gap"] < 0.02 and p["change_gap"] < 0.05
