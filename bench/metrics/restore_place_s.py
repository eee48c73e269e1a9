"""Self time of the program's ``restore/leaf`` timer (the eager restore of
one leaf less its ``restore/assemble``: the host time of placing it on the
device) per resume of the window. A transfer still running when the
placement call returns is waited for in ``restore/verify_device``."""


def read(ctx):
    t, n = ctx["timings"], ctx["resumes"]
    if not {"restore/leaf", "restore/assemble"} <= set(t) or not n or ctx["kind"] != "resume":
        return None
    return (t["restore/leaf"][0] - t["restore/assemble"][0]) / n
