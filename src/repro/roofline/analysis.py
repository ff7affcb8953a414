"""Roofline accounting from compiled dry-run artifacts.

Three terms (seconds, per training/serving step, per chip):

  compute    = HLO_FLOPs / PEAK_BF16
  memory     = HLO_bytes / HBM_BW
  collective = per-chip link bytes / ICI_BW        (ring cost model)

``cost_analysis()`` is per-device and counts every ``lax.scan`` body ONCE
(verified empirically, jax 0.8.2) — so per-cell totals are assembled by the
A/B *differencing* method: lower the same step with 1 and 2 superblocks;
(B - A) isolates one superblock's exact cost (collectives, remat recompute
and all), A - (B - A) isolates the stem; total = stem + n_super * block
(x n_micro for training) + full-shape optimizer step.  See DESIGN.md §7.

Collective bytes are parsed from the compiled HLO text with a ring model:
  all-gather       shard_bytes x (n-1)
  reduce-scatter   full_bytes x (n-1)/n
  all-reduce       2 x full_bytes x (n-1)/n
  all-to-all       local_bytes x (n-1)/n
  collective-permute  local_bytes
"""
from __future__ import annotations

import dataclasses
import re

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9            # B/s
ICI_BW = 50e9             # B/s per link (brief's 3-term formula uses 1 link)

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*(?P<rtype>[a-z0-9]+)\[(?P<rshape>[\d,]*)\][^=]*?"
    r"\b(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(", re.M)

_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{\{(?P<explicit>[\d,]+)\}|\[(?P<iota>[\d,]+)\]<=)")


def _shape_bytes(dtype: str, shape: str) -> int:
    n = 1
    if shape.strip():
        for s in shape.split(","):
            n *= int(s)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-device link bytes by op kind (ring model)."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        nbytes = _shape_bytes(m.group("rtype"), m.group("rshape"))
        # replica group size from the trailing text of this line
        line_end = hlo_text.find("\n", m.end())
        seg = hlo_text[m.start():line_end if line_end > 0 else None]
        g = _GROUPS_RE.search(seg)
        n = 1
        if g:
            if g.group("explicit") is not None:
                n = len(g.group("explicit").split(","))
            else:
                dims = [int(x) for x in g.group("iota").split(",")]
                n = dims[-1] if len(dims) > 1 else dims[0]
        if n <= 1:
            continue
        if op == "all-gather":          # result = gathered; shard = result/n
            moved = nbytes / n * (n - 1)
        elif op == "reduce-scatter":    # result = shard; full = result*n
            moved = nbytes * (n - 1)
        elif op == "all-reduce":
            moved = 2.0 * nbytes * (n - 1) / n
        elif op == "all-to-all":
            moved = nbytes * (n - 1) / n
        else:                           # collective-permute
            moved = float(nbytes)
        out[op] += moved
        counts[op] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


@dataclasses.dataclass
class PartCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: float = 0.0
    coll_by_op: dict = dataclasses.field(default_factory=dict)

    def __sub__(self, o):
        return PartCost(self.flops - o.flops, self.bytes - o.bytes,
                        self.coll - o.coll,
                        {k: self.coll_by_op.get(k, 0) - o.coll_by_op.get(k, 0)
                         for k in set(self.coll_by_op) | set(o.coll_by_op)
                         if k != "counts"})

    def scaled(self, k: float):
        return PartCost(self.flops * k, self.bytes * k, self.coll * k,
                        {kk: v * k for kk, v in self.coll_by_op.items()
                         if kk != "counts"})

    def __add__(self, o):
        return PartCost(self.flops + o.flops, self.bytes + o.bytes,
                        self.coll + o.coll,
                        {k: self.coll_by_op.get(k, 0) + o.coll_by_op.get(k, 0)
                         for k in set(self.coll_by_op) | set(o.coll_by_op)
                         if k != "counts"})


def cost_of_compiled(compiled) -> PartCost:
    ca = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return PartCost(float(ca.get("flops", 0.0)),
                    float(ca.get("bytes accessed", 0.0)),
                    float(coll["total"]),
                    {k: v for k, v in coll.items()
                     if k not in ("total", "counts")})


def roofline_terms(total: PartCost) -> dict:
    return {
        "compute_s": total.flops / PEAK_BF16,
        "memory_s": total.bytes / HBM_BW,
        "collective_s": total.coll / ICI_BW,
    }


def dominant_term(terms: dict) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k])


def model_flops(cfg, shape, kind: str) -> float:
    """Analytic MODEL_FLOPS: 6*N*D for training, 2*N_active per decoded
    token, 2*N_active*S for prefill (N = active params)."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * shape.seq_len
    if kind == "train":
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


# ---------------------------------------------------------------------------
# Per-kernel analytic roofline models — the predicted side of the autotune
# campaign (benchmarks/bench_kernels.py --sweep reports predicted vs
# measured per accepted tile config).
# ---------------------------------------------------------------------------

# per-chip roofs by device-kind substring: peak dense bf16 FLOP/s and HBM
# bandwidth.  TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 819 GB/s HBM).  A device kind with no row is an error, not a default.
DEVICE_ROOFS = {
    "TPU v5 lite": {"peak_flops": PEAK_BF16, "hbm_bw": HBM_BW},
}


def device_roof(device_kind: str) -> dict:
    """Roof constants for a device kind (substring match); raises
    ``KeyError`` for a device the table does not know."""
    needle = (device_kind or "").lower()
    for pat, roof in DEVICE_ROOFS.items():
        if pat.lower() in needle:
            return roof
    raise KeyError(f"device_roof: no published roof for device kind "
                   f"{device_kind!r}; known: {sorted(DEVICE_ROOFS)}")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // max(b, 1))


def kernel_cost(kernel: str, *, dtype_bytes: int = 4, block_m: int = 256,
                block_l: int = 256, **dims) -> PartCost:
    """Analytic FLOPs/HBM-bytes for one Pallas kernel invocation.

    The byte model is tile-aware where the schedule changes traffic: the
    Lloyd/assign kernels re-stream the (K, d) centers once per M tile
    (``x`` itself is streamed once — revisited blocks are not refetched),
    so a wider ``block_m`` cuts center traffic; the ADC scan re-reads each
    group's LUT once per L tile.  FLOPs are schedule-invariant.
    """
    if kernel in ("lloyd", "assign"):
        m, d, k = dims["m"], dims["d"], dims["k"]
        # distance cross-term matmul + distance assembly (+ the fused
        # one-hot accumulation matmul for lloyd)
        flops = 2.0 * m * k * d + 3.0 * m * k
        if kernel == "lloyd":
            flops += 2.0 * m * k * d + 2.0 * m * k
        n_mtiles = _ceil_div(m, block_m)
        rbytes = dtype_bytes * (m * d + k * d * n_mtiles)
        wbytes = 8.0 * m                       # idx (i32) + dist (f32)
        if kernel == "lloyd":
            rbytes += dtype_bytes * m          # weights
            wbytes += 4.0 * (k * d + k + 1)    # sums + counts + sse
        return PartCost(flops, rbytes + wbytes, 0.0)
    if kernel == "centroid":
        m, d, k = dims["m"], dims["d"], dims["k"]
        flops = 2.0 * m * k * d + 2.0 * m * k  # one-hot matmul + counts
        rbytes = dtype_bytes * (m * d + m) + 4.0 * m
        wbytes = 4.0 * k * (d + 1)
        return PartCost(flops, rbytes + wbytes, 0.0)
    if kernel == "scan":
        b, l, msub, c = dims["b"], dims["l"], dims["msub"], dims["c"]
        flops = 2.0 * b * l * msub * c         # one-hot matvec per subspace
        n_ltiles = _ceil_div(l, block_l)
        rbytes = 4.0 * b * l * msub + dtype_bytes * b * msub * c * n_ltiles
        wbytes = 4.0 * b * l
        return PartCost(flops, rbytes + wbytes, 0.0)
    raise ValueError(f"kernel_cost: unknown kernel {kernel!r}")


def predicted_vs_measured(kernel: str, measured_s: float, *,
                          device_kind: str,
                          dtype_bytes: int = 4, block_m: int = 256,
                          block_l: int = 256, **dims) -> dict:
    """One accepted sweep config -> its roofline report: predicted time
    (max of the compute and memory terms on this device's roofs), the
    dominant term, and measured/predicted efficiency.  Only meaningful
    for a compiled run on a device :func:`device_roof` knows."""
    cost = kernel_cost(kernel, dtype_bytes=dtype_bytes, block_m=block_m,
                       block_l=block_l, **dims)
    roof = device_roof(device_kind)
    compute_s = cost.flops / roof["peak_flops"]
    memory_s = cost.bytes / roof["hbm_bw"]
    predicted_s = max(compute_s, memory_s)
    return {
        "flops": cost.flops,
        "bytes": cost.bytes,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "predicted_s": predicted_s,
        "dominant": "compute" if compute_s >= memory_s else "memory",
        "measured_s": float(measured_s),
        "efficiency": (predicted_s / measured_s
                       if measured_s > 0 else 0.0),
    }


def f32_upconvert_bytes(hlo_text: str, sds_spec_pairs, mesh) -> int:
    """CPU-backend artifact quantifier: the CPU pipeline upconverts bf16
    dot operands (weights, KV caches) to f32 because it lacks bf16 dot
    thunks — a TPU's MXU consumes bf16 natively, so these buffers do not
    exist on the target.  Sums f32 buffers in the HLO whose shapes equal a
    bf16 parameter/cache *shard* shape (each distinct shape counted once —
    the converts are hoisted, one per tensor)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    total = 0
    for sds_tree, spec_tree in sds_spec_pairs:
        leaves = zip(jax.tree.leaves(sds_tree), jax.tree.leaves(spec_tree))
        for leaf, spec in leaves:
            if leaf.dtype != jnp.bfloat16:
                continue
            shape = list(leaf.shape)
            for dim, ax in enumerate(tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                f = 1
                for a in axes:
                    f *= mesh.shape[a]
                if shape[dim] % f == 0:
                    shape[dim] //= f
            # counted PER LEAF: same-shaped tensors (we1/we3, k/v) each get
            # their own hoisted convert
            pat = "f32[" + ",".join(str(s) for s in shape) + "]"
            if pat in hlo_text:
                total += 4 * int(np.prod(shape))
    return total
