"""Device time per fit of the merge k-means (seeding, Lloyd steps and the
restarts' pick over the pool), ms: the union of the intervals of the
operations traced under the program's ``merge`` scope, over the traced
fits.  None where the trace has no such scope."""

# the scope as a component of the name stack; under a transform JAX writes
# it wrapped, as ``vmap(merge)``
SCOPE = r"(^|/)(\w+\()*merge\)*(/|$)"


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    s = t.scope_s(SCOPE)
    return 1e3 * s / t.steps if s > 0 else None
