"""Mean ``evict`` span (quota and capacity victim selection for one
write-back batch: a scan of every slot per stored row at quota), ms per
insert batch."""


def read(ctx):
    v = ctx.get("spans", {}).get("evict")
    return sum(v) / len(v) if v else None
