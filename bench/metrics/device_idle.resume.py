"""Share of the traced resumes' window in which no operation ran on the
device, in percent."""


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "resume":
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
