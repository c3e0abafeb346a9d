"""Device time of latent attention's key and value path (``latent_kv``:
the KV down-projection, its norm, the rope split and the up-projection to
per-head keys and values, forward and backward) per epoch, on the slowest
chip."""


def read(ctx):
    return ctx["scope_ms"].get("latent_kv")
