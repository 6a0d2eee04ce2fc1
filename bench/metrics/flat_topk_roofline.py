"""``flat_topk``'s share of its roofline, %: the least time the chip needs
for the scans made inside the traced stretch (table, meta row, queries
and 2·B·N·d operations) over the kernel's device time there."""

from bench import flops, trace_reduce

NAMES = ("flat_topk",)


def read(ctx):
    tr, work = ctx.get("trace"), (ctx.get("work") or {}).get("flat_topk")
    if not tr or not work:
        return None
    t = trace_reduce.kernel_seconds(tr["ops"], NAMES)
    if t <= 0:
        return None
    return 100.0 * sum(flops.min_seconds(w, ctx["peaks"]) for w in work) / t
