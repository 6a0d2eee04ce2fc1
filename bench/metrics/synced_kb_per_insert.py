"""Bytes the index's delta sync moved in the window (its ``sync_stats``
counter), per insert batch, in KiB."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("insert_calls"):
        return None
    return c["sync_bytes"] / c["insert_calls"] / 1024.0
