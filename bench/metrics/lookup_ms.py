"""Mean ``lookup`` span (the cache front's Algorithm 1 over one batch,
device search and its sync included), ms per batch."""


def read(ctx):
    v = ctx.get("spans", {}).get("lookup")
    return sum(v) / len(v) if v else None
