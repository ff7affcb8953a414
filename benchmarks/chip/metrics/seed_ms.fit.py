"""Device time per fit of the centers' seeding, ms: the union of the
intervals of the operations traced under the program's ``kmeans_init``
scope (k-means++ in the fold's partitions and in the merge's restarts
alike), over the traced fits.  None where the trace has no such scope."""

# the scope as a component of the name stack; under a transform JAX writes
# it wrapped, as ``vmap(kmeans_init)``
SCOPE = r"(^|/)(\w+\()*kmeans_init\)*(/|$)"


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    s = t.scope_s(SCOPE)
    return 1e3 * s / t.steps if s > 0 else None
