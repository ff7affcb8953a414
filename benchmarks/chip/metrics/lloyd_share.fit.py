"""Lloyd-kernel device time over device busy time in the traced window,
percent (the rest: seeding, partition gather, scaling, SSE)."""

# The ``lloyd`` kernel: a ``pallas_call`` (name stack) made in
# ``kernels/lloyd.py`` (source line), in the fold and in the merge alike.
SCOPE = r"pallas_call"
SOURCE = r"kernels/lloyd\.py:"


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    k = t.kernel_s(SCOPE, SOURCE)
    return 100.0 * k / t.busy_s if k > 0 else None
