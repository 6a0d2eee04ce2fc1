"""``scatter_update``'s share of its roofline, %: the least time the chip
needs to read and write the delta rows the traced stretch's flushes
carried (the rows themselves, not the aligned groups the kernel moves)
over the kernel's device time there."""

from bench import flops, trace_reduce

NAMES = ("scatter_rows",)


def read(ctx):
    tr = ctx.get("trace")
    work = (ctx.get("work") or {}).get("scatter_update")
    if not tr or not work:
        return None
    t = trace_reduce.kernel_seconds(tr["ops"], NAMES)
    if t <= 0:
        return None
    return 100.0 * sum(flops.min_seconds(w, ctx["peaks"]) for w in work) / t
