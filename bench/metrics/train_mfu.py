"""Model FLOPs of the window's tokens (PaLM's count, bench/flops.py) per
second, over the chips' bf16 peak, in percent."""


def read(ctx):
    if ctx["kind"] != "train" or ctx["peak"] is None:
        return None
    rate = ctx["tokens"] / ctx["window_s"] * ctx["flops_per_token"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
