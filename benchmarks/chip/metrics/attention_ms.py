"""Device time of the model's attention blocks (``attention``: norm,
attention and residual, forward and backward) per epoch, on the slowest
chip."""


def read(ctx):
    return ctx["scope_ms"].get("attention")
