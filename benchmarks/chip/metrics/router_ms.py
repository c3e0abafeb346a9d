"""Device time of the routed layers' router (``router``: logits, top-k,
balance loss, the sort of token-slots by expert and the combine, forward
and backward) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("router")
