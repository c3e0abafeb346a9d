"""Device time of the consensus period (``gossip_period``: the servers'
exchange and mixing) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("gossip_period")
