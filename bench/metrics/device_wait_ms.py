"""Mean ``device_wait`` span (the host blocked in the lookup's one
``device_get``: the delta flush's scatters and the scan still running on
the device), ms per lookup batch."""


def read(ctx):
    v = ctx.get("spans", {}).get("device_wait")
    return sum(v) / len(v) if v else None
