"""The program's ``restore/verify_device`` timer (re-digest of the placed
state against the saved digests) per resume of the window."""


def read(ctx):
    total, n = ctx["timings"].get("restore/verify_device", (0.0, 0))
    return total / n if n and ctx["kind"] == "resume" else None
