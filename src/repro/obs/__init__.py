"""Unified observability fabric — tracing, metrics, journal, leak audit.

One subsystem spanning every process in the CRUM stack:

* :mod:`repro.obs.trace` — per-process Chrome ``trace_event`` shards
  with correlation IDs (run, step, epoch, incarnation); disabled by
  default with a zero-allocation no-op path.
* :mod:`repro.obs.metrics` — one registry absorbing the scattered layer
  stats (PagingStats, transport wire counters, checkpoint phases,
  restart budgets) under one snake_case naming scheme.
* :mod:`repro.obs.journal` — the versioned, typed CLUSTER_LOG.jsonl
  schema.
* :mod:`repro.obs.leakcheck` — fd + /dev/shm growth audit for soak runs,
  plus the light periodic :func:`~repro.obs.leakcheck.sample` /
  :class:`~repro.obs.leakcheck.PeriodicAudit` the live watchdog uses.
* :mod:`repro.obs.report` — ``python -m repro.obs.report <run_dir>``
  merges everything into one Perfetto-loadable trace + summary table.

The *live* half (streaming, while the run runs):

* :mod:`repro.obs.live` — worker registry deltas piggybacked on
  HEARTBEAT frames; the coordinator aggregates them into a bounded
  in-memory time-series store served over its TCP listener and
  snapshotted to ``live_metrics.json``.
* :mod:`repro.obs.watch` — the SLO watchdog: rules per heartbeat/round
  (stall ratio, skew, abort rate, stragglers, leak trends, digest
  divergence) emitting versioned ``alert`` journal records.
* :mod:`repro.obs.top` — ``python -m repro.obs.top`` terminal dashboard
  over a live coordinator endpoint or a finished run dir.
* :mod:`repro.obs.baseline` — diff fresh bench rows against the
  committed ``BENCH_results.json`` (``benchmarks.run --compare``);
  ``BENCH_history.jsonl`` keeps the trajectory in-repo.

Enable with ``--obs-dir`` on ``launch/train`` / ``launch/cluster`` (or
``CRUM_OBS_DIR`` in the environment, which is how child processes
inherit it).

The division of spans: the shards here hold the causal spans that cross
processes (a round's phase 1 in the app, its persist in the fork child,
the coordinator's commit), on the wall clock. Spans inside one process
(``Timings.measure`` and ``repro.utils.timing.span``) go to the
``jax.profiler`` trace, on the device trace's clock, whenever a profiler
session runs.
"""
from repro.obs import trace
from repro.obs.metrics import REGISTRY

__all__ = ["trace", "REGISTRY"]
