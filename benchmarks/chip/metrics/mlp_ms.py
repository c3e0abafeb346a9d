"""Device time of the model's MLP blocks (``mlp``, forward and
backward) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("mlp")
