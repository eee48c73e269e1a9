"""Soak verdict engine: token matching, windows, the hard booleans."""
import json
import os

from repro.obs.journal import JOURNAL_SCHEMA, JournalWriter
from repro.obs.soak import (
    SOAK_SCHEMA,
    evidence_for,
    explain_alerts,
    load_inject_log,
    match_token,
    verdict,
)

T0 = 1000.0


def _write_run(tmp_path, injects, cluster_lines):
    """Lay out a minimal soak run dir from raw journal lines."""
    run_dir = str(tmp_path)
    os.makedirs(os.path.join(run_dir, "ckpt"), exist_ok=True)
    with open(os.path.join(run_dir, "INJECT_LOG.jsonl"), "w") as f:
        for doc in injects:
            f.write(json.dumps(
                {"schema": "crum-inject/1", "event": "inject", **doc}
            ) + "\n")
    with open(os.path.join(run_dir, "ckpt", "CLUSTER_LOG.jsonl"),
              "w") as f:
        for doc in cluster_lines:
            f.write(json.dumps(
                {"schema": JOURNAL_SCHEMA, **doc}) + "\n")
    return run_dir


def _inject(kind="kill_worker", t=T0, seq=1, host=0, any_=None, all_=None,
            explains=("worker_death", "round_abort"), window=30.0):
    return {"kind": kind, "target": f"host:{host}", "t": t, "seq": seq,
            "params": {"host": host},
            "expect": {"window_s": window, "host": host,
                       "any": list(any_ or []), "all": list(all_ or []),
                       "explains": list(explains)}}


def test_token_matching_and_windows(tmp_path):
    run_dir = _write_run(
        tmp_path,
        [_inject(any_=["alert:worker_death", "journal:death"])],
        [
            {"event": "death", "t": T0 + 1.0, "host": 0, "reason": "x"},
            # outside the 30s window: must not count
            {"event": "death", "t": T0 + 99.0, "host": 0, "reason": "x"},
            # wrong host for a host-pinned spec: must not count
            {"event": "alert", "t": T0 + 2.0, "kind": "worker_death",
             "severity": "warning", "host": 1, "message": ""},
        ],
    )
    [inj] = load_inject_log(run_dir)
    from repro.obs.journal import read_journal

    records = read_journal(
        os.path.join(run_dir, "ckpt", "CLUSTER_LOG.jsonl"))
    assert match_token("journal:death", inj, records) == \
        [f"death:host0@{T0 + 1.0:.3f}"]
    assert match_token("alert:worker_death", inj, records) == []
    assert evidence_for(inj, records)["evidenced"]  # "any" satisfied


def test_all_semantics_demand_every_token(tmp_path):
    run_dir = _write_run(
        tmp_path,
        [_inject(kind="disk_full",
                 all_=["journal:round_aborted_persist",
                       "journal:round_committed"],
                 explains=["round_abort"])],
        [{"event": "round", "t": T0 + 1.0, "step": 2, "status": "aborted",
          "reason": "host 0 persist failed: ENOSPC"}],
    )
    [inj] = load_inject_log(run_dir)
    from repro.obs.journal import read_journal

    records = read_journal(
        os.path.join(run_dir, "ckpt", "CLUSTER_LOG.jsonl"))
    assert not evidence_for(inj, records)["evidenced"]  # commit missing
    doc = verdict(run_dir)
    assert not doc["checks"]["all_injections_evidenced"]
    assert not doc["pass"]


def test_unexplained_alert_fails_the_run(tmp_path):
    run_dir = _write_run(
        tmp_path,
        [_inject(any_=["journal:death"])],
        [
            {"event": "death", "t": T0 + 1.0, "host": 0, "reason": "x"},
            {"event": "round", "t": T0 + 2.0, "step": 2,
             "status": "committed"},
            # an alert no injection claims
            {"event": "alert", "t": T0 + 3.0, "kind": "digest_divergence",
             "severity": "critical", "host": 1, "message": "forked"},
        ],
    )
    doc = verdict(run_dir)
    assert doc["checks"]["all_injections_evidenced"]
    assert not doc["checks"]["no_unexplained_alerts"]
    [a] = [x for x in doc["alerts"] if x["explained_by"] is None]
    assert a["kind"] == "digest_divergence"
    assert not doc["pass"]


def test_clean_run_passes(tmp_path):
    run_dir = _write_run(
        tmp_path,
        [_inject(any_=["journal:death"])],
        [
            {"event": "death", "t": T0 + 1.0, "host": 0, "reason": "x"},
            {"event": "alert", "t": T0 + 1.1, "kind": "worker_death",
             "severity": "warning", "host": 0, "message": "x"},
            {"event": "round", "t": T0 + 2.0, "step": 2,
             "status": "committed", "round_s": 1.0},
        ],
    )
    doc = verdict(run_dir)
    assert doc["schema"] == SOAK_SCHEMA
    assert doc["checks"] == {
        "all_injections_evidenced": True,
        "no_unexplained_alerts": True,
        "converged": True,
        "leaks_flat": True,
        "critpath_ok": True,
        "envelope_ok": True,
        "injected": True,
    }
    assert doc["pass"]


def test_explain_is_time_boxed():
    from repro.obs.journal import AlertLine, InjectLine

    inj = InjectLine(event="inject", t=T0, kind="kill_worker", seq=1,
                     expect={"window_s": 10.0,
                             "explains": ["worker_death"]})
    inside = AlertLine(event="alert", t=T0 + 5.0, kind="worker_death")
    outside = AlertLine(event="alert", t=T0 + 50.0, kind="worker_death")
    rows = explain_alerts([inj], [inside, outside])
    assert rows[0]["explained_by"] == 1
    assert rows[1]["explained_by"] is None


def test_envelope_and_leak_checks(tmp_path):
    run_dir = _write_run(
        tmp_path,
        [],
        [{"event": "round", "t": T0, "step": 2, "status": "committed",
          "round_s": 99.0}],
    )
    # a growing coord_fd rollup series (host -1) must trip leaks_flat
    obs_dir = os.path.join(run_dir, "obs")
    os.makedirs(obs_dir)
    with open(os.path.join(obs_dir, "live_metrics.json"), "w") as f:
        json.dump({
            "schema": "crum-live-metrics/1",
            "series": {},
            "rollups": {"10": {"-1": {
                "coord_fd": [[T0, 10, 10, 10, 3], [T0 + 10, 40, 10, 40, 3]],
            }}},
        }, f)
    doc = verdict(run_dir, round_envelope_s=30.0, fd_allowance=8)
    assert not doc["checks"]["envelope_ok"]
    assert doc["slow_rounds"] == [{"step": 2, "round_s": 99.0}]
    assert not doc["checks"]["leaks_flat"]
    assert doc["leak_growth"]["coord_fd"] == 30.0


def test_gate_soak_clean(tmp_path):
    """A soak that injected nothing tested nothing: it fails the verdict
    even when every other check holds."""
    run_dir = _write_run(
        tmp_path,
        [],
        [{"event": "round", "t": T0, "step": 2, "status": "committed",
          "round_s": 1.0}],
    )
    doc = verdict(run_dir)
    assert doc["n_injections"] == 0
    assert doc["checks"]["injected"] is False
    assert all(ok for name, ok in doc["checks"].items()
               if name != "injected"), doc["checks"]
    assert doc["pass"] is False
