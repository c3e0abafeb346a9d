"""Device time of the epoch program's local period (``local_period``:
the T_C local steps of every client) per epoch, on the slowest chip."""


def read(ctx):
    return ctx["scope_ms"].get("local_period")
