"""The program's ``shadow/copy`` timer (the host copy into the shadow
buffer inside ``shadow/fetch``) per save of the window."""


def read(ctx):
    saves = len(ctx["stalls_s"])
    total, _ = ctx["timings"].get("shadow/copy", (None, 0))
    return total / saves if total is not None and saves else None
