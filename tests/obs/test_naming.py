"""Cross-layer naming regression pins.

The key sets frozen in ``repro.obs.metrics`` are consumed across layer
boundaries — RoundRecord's shape IS the journal ``round`` line, SYNCED
info dicts cross the proxy control plane. A producer renaming a key
without updating the pin (and every consumer) is a cross-layer break;
these tests make it loud.
"""
import dataclasses

from repro.obs import metrics as m


def test_paging_stat_keys_pin():
    from repro.uvm.pager import PagingStats

    assert set(PagingStats().as_dict()) == set(m.PAGING_STAT_KEYS)


def test_paging_canonical_is_registry_form():
    from repro.uvm.pager import PagingStats

    canon = PagingStats().canonical()
    assert set(canon) == {f"uvm_{k}" for k in m.PAGING_STAT_KEYS}
    # canonical() and absorb_paging agree on the naming scheme
    r = m.Registry()
    m.absorb_paging(PagingStats().as_dict(), r)
    assert set(r.snapshot()["gauges"]) == set(canon)


def test_transport_stat_keys_pin(tmp_path):
    import numpy as np

    from repro.remote.transport import make_transport

    t = make_transport(
        "stream", {"w": np.zeros(64, np.uint8)}, 64,
        workdir=str(tmp_path),
    )
    try:
        stats = t.stats()
        assert set(stats) == set(m.TRANSPORT_STAT_KEYS)
        canon = t.canonical_stats()
        # numeric keys only, transport_-prefixed; the 'transport' kind
        # label is a string and stays out of the registry form
        assert set(canon) == {
            f"transport_{k}" for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        assert "transport_transport" not in canon
    finally:
        t.close()


def test_round_record_keys_pin():
    from repro.coord.coordinator import RoundRecord

    assert {f.name for f in dataclasses.fields(RoundRecord)} == set(
        m.ROUND_RECORD_KEYS
    )


def test_round_journal_line_matches_pin():
    from repro.obs.journal import RoundLine

    line_fields = {
        f.name for f in dataclasses.fields(RoundLine)
    } - {"event", "t", "schema", "extra"}
    assert line_fields == set(m.ROUND_RECORD_KEYS)


def test_sync_info_keys_pin():
    """The SYNCED info vocabulary: produced by the proxy service, finished
    app-side by supervisor._finish_sync (which adds ``stall_us``). Every
    pinned name must still appear in the producing pair."""
    import inspect

    from repro.proxy import service, supervisor

    src = inspect.getsource(service) + inspect.getsource(supervisor)
    for key in m.SYNC_INFO_KEYS:
        assert f'"{key}"' in src, f"SYNCED info key {key!r} gone"

