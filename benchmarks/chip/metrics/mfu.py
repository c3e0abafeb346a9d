"""Model FLOPs of the window's client training over the chips' bf16 peak:
FLOPs per token (``flops.train_flops_per_token``) x tokens/s / (chips x
peak)."""


def read(ctx):
    if not ctx["tokens_per_s"] or ctx["peak"] is None:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"]
    return ctx["flops_per_token"] * ctx["tokens_per_s"] / (ctx["chips"]
                                                          * peak)
