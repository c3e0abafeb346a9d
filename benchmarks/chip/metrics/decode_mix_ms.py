"""Device time of the int8 wire's decode and mix (``wire_decode_mix``)
per epoch, on the slowest chip; nothing to read where the wire is exact."""


def read(ctx):
    return ctx["scope_ms"].get("wire_decode_mix")
