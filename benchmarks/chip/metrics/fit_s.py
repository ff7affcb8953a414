"""Seconds of all fits completed in the window over their count."""


def read(ctx):
    w = ctx.window
    if w is None or not w.latencies:
        return None
    return sum(w.latencies) / len(w.latencies)
