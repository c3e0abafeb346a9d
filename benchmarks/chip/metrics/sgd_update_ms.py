"""Device time of the local steps' SGD updates (``sgd_update``) per
epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("sgd_update")
