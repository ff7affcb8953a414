"""Device time per fit of the reduce levels, ms: the union of the
intervals of the operations traced under the ``reduce_level`` scope (the
jitted ``reduce_pool``), mean over the devices."""
import re

import numpy as np

from tracered import union

# the scope as a component of the name stack; under a transform JAX writes
# it wrapped, as ``vmap(reduce_level)``
SCOPE = re.compile(r"(^|/)(\w+\()*reduce_level\)*(/|$)")


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    total = 0.0
    for ev in t.devices.values():
        seen = {}
        keep = np.fromiter(
            (seen[s] if s in seen else seen.setdefault(s, bool(SCOPE.search(s)))
             for s in ev.scope), bool, len(ev.scope))
        if keep.any():
            total += sum(b - a for a, b in union(ev.start[keep], ev.end[keep],
                                                  t.lo, t.hi))
    s = total * 1e-9 / max(len(t.devices), 1)
    return 1e3 * s / t.steps if s > 0 else None
