"""Mean ``embed`` span (the engine's host feature hashing), ms per step."""


def read(ctx):
    v = ctx.get("spans", {}).get("embed")
    return sum(v) / len(v) if v else None
