"""Bytes of the window's snapshots over their persist seconds in the fork
child (CheckpointResult.bytes_snapshot / persist_s), in MB/s."""


def read(ctx):
    done = [r for r in ctx["results"] if r.error is None and r.persist_s > 0]
    if not done:
        return None
    return sum(r.bytes_snapshot for r in done) / sum(r.persist_s for r in done) / 1e6
