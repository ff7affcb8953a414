"""Work of the kernels, from the cell's shapes and spec alone (never from
tiles, padding or the implementation), so a roofline share reads the same
work whatever computes it.

Lloyd step (``kernels/lloyd.py``): for each (point, center) pair a squared
distance and, for the winner, the weighted accumulation: 4*d + 5 FLOPs a
pair (2d for the cross term, 2d for the one-hot accumulation, 5 for the
norms' sum, the comparison and the weight).  Bytes: the points read once a
step, the centers read once, the sums and counts written once, and the
per-point id and distance written once (4 bytes each).

ADC scan (``kernels/scan.py``): for each candidate ``m`` table lookups
and adds (m FLOPs); bytes: the candidate's ``m`` one-byte codes, the
group's (m, C) f32 table read once, and a 4-byte distance written per
candidate.
"""
from __future__ import annotations


def lloyd_step(m: int, k: int, d: int) -> tuple:
    """(FLOPs, bytes) of one Lloyd step over ``m`` points, ``k`` centers."""
    flops = (4 * d + 5) * m * k
    nbytes = 4 * (m * d + k * d + k * d + k + 2 * m)
    return float(flops), float(nbytes)


def sampled_fit_lloyd(spec: dict, n: int, d: int) -> tuple:
    """(FLOPs, bytes) of the Lloyd steps of one sampled fit: every level's
    partitions x iterations, and the merge's restarts x iterations (the
    final assignments run in another kernel and are not counted)."""
    flops = nbytes = 0.0
    rows = n
    levels = [(spec["partition"]["n_sub"], spec["local"]["compression"],
               spec["local"]["iters"])]
    levels += [(lv["n_sub"], lv["compression"], lv["iters"])
               for lv in spec.get("levels", [])]
    for n_sub, comp, iters in levels:
        cap = -(-rows // n_sub)
        k_local = max(1, cap // comp)
        f, b = lloyd_step(cap, k_local, d)
        flops += n_sub * iters * f
        nbytes += n_sub * iters * b
        rows = n_sub * k_local
    mg = spec["merge"]
    f, b = lloyd_step(rows, mg["k"], d)
    runs = mg.get("restarts", 4) * mg["iters"]
    return flops + runs * f, nbytes + runs * b


def adc_scan(groups: int, candidates: float, m: int, codes: int) -> tuple:
    """(FLOPs, bytes) of scanning ``candidates`` list members in ``groups``
    (query, list) groups with ``m`` subspaces of ``codes`` entries."""
    flops = float(candidates) * m
    nbytes = (float(candidates) * m + 4.0 * groups * m * codes
              + 4.0 * candidates)
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str):
    """Percent of the least time (the larger of FLOPs over peak and bytes
    over bandwidth) in ``seconds`` of kernel time; None without time."""
    from peaks import peak
    if seconds <= 0:
        return None
    p = peak(device_kind)
    least = max(flops / p["flops"], nbytes / p["hbm_bytes_s"])
    return 100.0 * least / seconds
