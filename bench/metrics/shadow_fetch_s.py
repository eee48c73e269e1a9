"""The program's ``shadow/fetch`` timer (device to host copy into the
shadow buffer) per save of the window."""


def read(ctx):
    saves = len(ctx["stalls_s"])
    total = ctx["timings"].get("shadow/fetch", (0.0, 0))[0]
    return total / saves if saves else None
