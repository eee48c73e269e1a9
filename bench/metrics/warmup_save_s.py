"""Blocking seconds of the warm-up saves together (CheckpointResult
.blocking_s): the first fill of both shadow buffers and the first digest
pass, which compiles the digest programs."""


def read(ctx):
    w = ctx["warmup_blocking_s"]
    return sum(w) if w else None
