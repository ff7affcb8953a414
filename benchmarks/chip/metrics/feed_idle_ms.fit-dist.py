"""Device idle time per fit in the gaps whose host event is ``feed`` (a
chunk's transfer to its chip, ``data/source.py: prefetch_to_device``), ms,
mean over the devices.  Only the trace's named gaps count: the longest
ones, each named by the innermost host event covering its start."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1e3 * sum(s for name, s in t.gaps if name == "feed") / t.steps
