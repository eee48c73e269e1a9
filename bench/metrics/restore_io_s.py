"""The program's ``store/read`` timer (open, seek and read of each chunk
missing from the store's cache) per resume of the window."""


def read(ctx):
    n = ctx["resumes"]
    total, _ = ctx["timings"].get("store/read", (None, 0))
    return total / n if total is not None and n and ctx["kind"] == "resume" else None
