"""Self time of the program's ``restore/assemble`` timer (joining a leaf's
chunks and copying them into the window the device gets), less the
``store/read`` and ``store/decode`` nested in it, per resume of the window."""


def read(ctx):
    t, n = ctx["timings"], ctx["resumes"]
    if "restore/assemble" not in t or not n or ctx["kind"] != "resume":
        return None
    nested = sum(t.get(k, (0.0, 0))[0] for k in ("store/read", "store/decode"))
    return (t["restore/assemble"][0] - nested) / n
