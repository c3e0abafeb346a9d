"""Device time of the collective ops (all-gathers and the like) per epoch,
on the slowest chip.  Nothing to read where no collective ran."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    per = [r.collective_ns / r.steps for r in red.values()
           if r.steps and r.collective_ns]
    return max(per) * 1e-6 if per else None
