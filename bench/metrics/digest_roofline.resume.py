"""Digest passes of the traced resumes' restore check: every device leaf
read once per resume, over the digest programs' device time, as a share of
819 GB/s."""
from bench.flops import digest_roofline


def read(ctx):
    return digest_roofline(ctx, ctx["resumes"]) if ctx["kind"] == "resume" else None
