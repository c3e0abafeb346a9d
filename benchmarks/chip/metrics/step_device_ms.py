"""Device time of the epoch program per epoch, on the slowest chip."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    per = [r.step_ns / r.steps for r in red.values() if r.steps]
    return max(per) * 1e-6 if per else None
