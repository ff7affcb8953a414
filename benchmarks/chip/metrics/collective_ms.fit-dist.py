"""Device time per fit of the mesh's collectives (all-gather, all-reduce,
... operations; the sharded merge's candidate gather and its per-round
psums), ms, mean over the devices."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps or t.collective_s <= 0:
        return None
    return 1e3 * t.collective_s / t.steps
