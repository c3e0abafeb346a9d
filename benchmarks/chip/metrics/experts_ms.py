"""Device time of the held experts' grouped matmuls (``experts``, forward
and backward) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("experts")
