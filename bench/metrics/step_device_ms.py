"""Device time of the train step program per step, from the trace."""
from bench.trace_reduce import module_seconds


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "train":
        return None
    seconds, runs = module_seconds(red, "jit_step_fn")
    return 1e3 * seconds / runs if runs else None
