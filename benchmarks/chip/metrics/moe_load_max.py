"""How unevenly the router loads the held experts: the engine's
``record["moe_load_max"]`` (over the held experts of every routed layer,
the largest count of token-slots routed to one over their mean, at the
last local step, on the worst client), the mean over the run's epochs.
Nothing to read for a model without routed experts."""


def read(ctx):
    v = [rec["moe_load_max"] for rec in ctx["records"]
         if "moe_load_max" in rec]
    return sum(v) / len(v) if v else None
