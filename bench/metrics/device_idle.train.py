"""Share of the traced training window in which no operation ran on the
device, in percent."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train":
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
