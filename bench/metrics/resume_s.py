"""The window's wall time (host clock) over the resumes completed in it: each
from dropping the device state to the first step after the restore done."""


def read(ctx):
    if ctx["kind"] != "resume" or not ctx["resumes"]:
        return None
    return ctx["window_s"] / ctx["resumes"]
