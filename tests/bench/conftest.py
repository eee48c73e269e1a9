"""The benchmark's tests: its package is importable from the checkout root."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs here leave JAX's persistent compilation cache as they find it."""
    from bench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
