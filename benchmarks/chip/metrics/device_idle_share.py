"""Share of the traced window in which no op ran on the chip, the largest
over the cell's chips."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    return max(r.idle_share for r in red.values())
