"""The program's ``shadow/d2h`` timer (the device-to-host transfer inside
``shadow/fetch``) per save of the window."""


def read(ctx):
    saves = len(ctx["stalls_s"])
    total, _ = ctx["timings"].get("shadow/d2h", (None, 0))
    return total / saves if total is not None and saves else None
