"""chip_smoke.py refuses to report a result it did not get on a TPU."""
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cwd: str) -> tuple[subprocess.CompletedProcess, float]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    return out, time.monotonic() - t0


@pytest.mark.parametrize("where", ["checkout", "script-alone"])
def test_chip_smoke_fails_fast_without_a_tpu(tmp_path, where):
    if where == "script-alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    else:
        cwd = REPO
    out, wall = _run(cwd)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "FAILED" in out.stderr or "no repro package" in out.stderr
    assert wall < 60
