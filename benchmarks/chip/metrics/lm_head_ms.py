"""Device time of the model's head and cross-entropy (``lm_head``,
forward and backward) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("lm_head")
