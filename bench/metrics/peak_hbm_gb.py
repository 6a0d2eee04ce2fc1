"""Peak device memory in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), GB."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 1e9 if b else None
