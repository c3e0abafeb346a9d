"""Mean device-idle time between one epoch program's end and the next
one's start, on the chip where it is largest."""


def read(ctx):
    red = ctx["trace"]
    if not red:
        return None
    per = [sum(r.gaps_ns) / len(r.gaps_ns) for r in red.values()
           if r.gaps_ns]
    return max(per) * 1e-6 if per else None
