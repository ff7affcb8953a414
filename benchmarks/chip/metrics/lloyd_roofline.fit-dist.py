"""Share of the Lloyd kernel's roofline in the sharded out-of-core fit:
the least time of one device's Lloyd steps per fit (``work.lloyd_step``,
from shapes: its chunks' folds, its reduce levels and its share of the
merge rounds; the final assignments are not counted) over the mean device
time of the ``lloyd`` Pallas kernel per fit."""
import re

from work import lloyd_step, roofline_share

# The ``lloyd`` kernel: a ``pallas_call`` (name stack) made in
# ``kernels/lloyd.py`` (source line), as ``lloyd_roofline.fit`` reads it.
SCOPE = re.compile(r"pallas_call")
SOURCE = re.compile(r"kernels/lloyd\.py:")


def device_work(spec: dict, n: int, d: int, chips: int) -> tuple:
    """(FLOPs, bytes) of one device's Lloyd steps in one fit."""
    flops = nbytes = 0.0
    rows = -(-n // chips)
    cp = spec["chunk"]["chunk_points"]
    pool = 0
    for s in range(0, rows, cp):
        m = min(cp, rows - s)
        n_sub = min(spec["partition"]["n_sub"], m)
        cap = -(-m // n_sub)
        k_local = max(1, cap // spec["local"]["compression"])
        f, b = lloyd_step(cap, k_local, d)
        flops += n_sub * spec["local"]["iters"] * f
        nbytes += n_sub * spec["local"]["iters"] * b
        pool += n_sub * k_local
    for lv in spec.get("levels", []):
        cap = -(-pool // lv["n_sub"])
        k_local = max(1, cap // lv["compression"])
        f, b = lloyd_step(cap, k_local, d)
        flops += lv["n_sub"] * lv["iters"] * f
        nbytes += lv["n_sub"] * lv["iters"] * b
        pool = lv["n_sub"] * k_local
    f, b = lloyd_step(pool, spec["merge"]["k"], d)
    return (flops + spec["merge"]["iters"] * f,
            nbytes + spec["merge"]["iters"] * b)


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    total, seen = 0.0, {}
    for ev in t.devices.values():
        dt = ev.end.clip(t.lo, t.hi) - ev.start.clip(t.lo, t.hi)
        for sc, src, x in zip(ev.scope, ev.source, dt):
            if x > 0:
                hit = seen.get((sc, src))
                if hit is None:
                    hit = seen[(sc, src)] = bool(SCOPE.search(sc)
                                                 and SOURCE.search(src))
                if hit:
                    total += x
    kernel_s = total * 1e-9 / max(len(t.devices), 1)
    data = ctx.cell.config["data"]
    flops, nbytes = device_work(ctx.cell.config["fit"]["spec"], data["n"],
                                data["dim"], ctx.cell.chips)
    return roofline_share(t.steps * flops, t.steps * nbytes, kernel_s,
                          ctx.device_kind)
