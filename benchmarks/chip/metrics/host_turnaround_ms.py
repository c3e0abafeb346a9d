"""The host's time from one epoch's ``readback`` end to the next epoch's
``dispatch`` start, the mean over the traced epochs, in ms."""
from benchmarks.chip import phases


def read(ctx):
    ns = phases.host_turnaround_ns(ctx["host"])
    return None if ns is None else ns * 1e-6
