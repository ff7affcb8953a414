"""Padded slots scanned per real candidate: nprobe * cap over the probed
lists' member counts (a count; it repeats exactly)."""


def read(ctx):
    c = ctx.layer
    if not c.get("candidates"):
        return None
    return c["slots"] / c["candidates"]
