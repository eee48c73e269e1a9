"""Process start to the first timed operation (host clock): loading and
compiling the programs, the weights, the first steps and the warm-up saves
or resume."""


def read(ctx):
    return ctx["setup_s"]
