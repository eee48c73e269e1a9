"""Model FLOPs of the first step after a restore over the whole resume's
time at the chips' bf16 peak, in percent."""


def read(ctx):
    if ctx["kind"] != "resume" or ctx["peak"] is None or not ctx["resumes"]:
        return None
    resume_s = ctx["window_s"] / ctx["resumes"]
    step_flops = ctx["flops_per_token"] * ctx["tokens_per_step"]
    return 100.0 * step_flops / (resume_s * ctx["chips"] * ctx["peak"]["bf16_flops"])
