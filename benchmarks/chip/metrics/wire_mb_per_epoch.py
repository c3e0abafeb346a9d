"""Bytes the gossip wire moved per epoch, in MB: the engine's own ledger
(``record["wire_mb"]``), which equals the compiled program's all-gather
operand bytes.  Nothing to read where the wire is exact."""


def read(ctx):
    mb = [rec["wire_mb"] for rec in ctx["records"] if "wire_mb" in rec]
    return sum(mb) / len(mb) if mb else None
