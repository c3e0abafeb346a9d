"""Device time of the int8 wire's encode (``wire_encode``: the delta's
absmax, the dither and scales) per epoch, on the slowest chip; nothing to
read where the wire is exact."""


def read(ctx):
    return ctx["scope_ms"].get("wire_encode")
