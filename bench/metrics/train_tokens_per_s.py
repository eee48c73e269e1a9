"""Tokens of every step completed in the window over the window's wall time
(host clock), saves included."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return ctx["tokens"] / ctx["window_s"]
