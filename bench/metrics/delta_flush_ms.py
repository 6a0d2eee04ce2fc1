"""Mean ``delta_flush`` span (the host's preparation and dispatch of one
flush of the rows written since the last search), ms per flush."""


def read(ctx):
    v = ctx.get("spans", {}).get("delta_flush")
    return sum(v) / len(v) if v else None
