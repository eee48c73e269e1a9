"""Digest passes of the traced saves: every device leaf read once per save,
over the digest programs' device time, as a share of 819 GB/s."""
from bench.flops import digest_roofline


def read(ctx):
    return digest_roofline(ctx, len(ctx["stalls_s"])) if ctx["save"] else None
