"""The program's ``ckpt/submit`` timer (the hand-off to the persist
backend: the fork of the persist child) per save of the window."""


def read(ctx):
    saves = len(ctx["stalls_s"])
    total, _ = ctx["timings"].get("ckpt/submit", (None, 0))
    return total / saves if total is not None and saves else None
