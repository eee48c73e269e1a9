"""The train CLI on a 4-device data mesh: save, resume on the same mesh with
the restored shards bit-identical to the save, and the same steps on one
device. `chip_smoke.py --chips 4` runs this path at full width on four
chips; here it runs on four host-platform CPU devices at smoke width."""
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train(ckpt, steps: int, every: int, devices: int) -> str:
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-0.5b",
         "--smoke", "--batch", "4", "--seq", "32", "--steps", str(steps),
         "--ckpt-dir", str(ckpt), "--ckpt-every", str(every), "--log-every", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert f"mesh={{'data': {devices}}}" in out.stdout
    return out.stdout


def _losses(out: str) -> dict[int, float]:
    return {int(s): float(l) for s, l in re.findall(r"^\[train\] step=(\d+) loss=(\S+)", out, re.M)}


def test_four_device_mesh_resumes_bit_identical_and_matches_one_device(tmp_path):
    mesh = _losses(_train(tmp_path / "mesh", 2, 2, 4))
    out = _train(tmp_path / "mesh", 3, 2, 4)
    (check,) = [json.loads(l.split(" ", 2)[2]) for l in out.splitlines()
                if l.startswith("[train] restore_check ")]
    assert check["step"] == 2 and check["chunks"] > 0 and check["unmatched"] == 0
    assert "start_step=2" in out
    mesh.update(_losses(out))
    one = _losses(_train(tmp_path / "one", 3, 100, 1))
    assert sorted(mesh) == sorted(one) == [1, 2, 3]
    for s in one:
        assert abs(mesh[s] - one[s]) <= 1e-2 * max(1.0, abs(one[s])), (mesh, one)
