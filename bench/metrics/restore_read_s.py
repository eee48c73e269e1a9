"""The program's ``restore/eager`` timer (read, decompress, place) per
resume of the window."""


def read(ctx):
    total, n = ctx["timings"].get("restore/eager", (0.0, 0))
    return total / n if n and ctx["kind"] == "resume" else None
