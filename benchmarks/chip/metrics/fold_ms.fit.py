"""Device time per fit of the jitted fold program (partition + local
k-means of one chunk), ms: the union of the intervals of the operations
traced under its name, whether it runs as its own program or inside a
jitted fit."""
SCOPE = r"jit\(_fold_scaled_chunk\)"


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    s = t.scope_s(SCOPE)
    return 1e3 * s / t.steps if s > 0 else None
