"""Observability tests mutate process-global state (the module tracer,
the metrics registry, CRUM_OBS_* env) — restore all of it per test. The
registry starts empty too: a test of another directory run earlier in the
same worker process (a proxy restart drill) leaves its counters in it."""
import pytest

from repro.obs import trace
from repro.obs.metrics import REGISTRY


@pytest.fixture(autouse=True)
def _obs_hygiene():
    REGISTRY.reset()
    yield
    trace.disable()  # closes the shard fd and pops CRUM_OBS_DIR/_RUN
    REGISTRY.reset()
