"""Mean ``insert`` span (gate, eviction scoring and the host write of one
write-back batch), ms per batch."""


def read(ctx):
    v = ctx.get("spans", {}).get("insert")
    return sum(v) / len(v) if v else None
