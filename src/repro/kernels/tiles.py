"""Shared tile-shape rules for the Pallas kernels — ONE contract for the
kernels, the ``LloydBackend`` padding, and the autotuner.

Every kernel in this package tiles its inputs with the same three rules:

  * the point axis M is walked ``block_m`` rows at a time and must arrive
    padded to a whole number of blocks (``require_block_m`` raises a typed
    :class:`TileError` with the pad recipe instead of a bare assert);
  * the center axis K is tiled ``block_k`` at a time, clamped to the
    8-sublane minimum and to the padded K extent (``clamp_block_k`` — the
    *effective* tile, so a tuner sweeping ``block_k`` candidates can dedupe
    configs that collapse to the same kernel);
  * the candidate axis L of the ADC scan clamps the same way
    (``clamp_block_l``).

:mod:`repro.kernels.autotune` keys its config cache on the clamped values
returned here, which is what makes "the tuner picked 256 but the kernel ran
8" impossible by construction.
"""
from __future__ import annotations

SUBLANE = 8     # f32 sublane minimum: no tile may be thinner than this
LANE = 128      # the last-axis register width every d pads to


def pad_to(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= ``n``."""
    return -(-n // mult) * mult


class TileError(ValueError):
    """A kernel was handed a shape its tile config cannot cover.

    Subclasses ``ValueError`` so existing ``except ValueError`` call sites
    keep working; carries the offending ``(extent, block)`` pair."""

    def __init__(self, message: str, *, extent: int = 0, block: int = 0):
        super().__init__(message)
        self.extent = extent
        self.block = block


def require_block_m(m: int, block_m: int, *, kernel: str = "kernel") -> None:
    """The padding contract: M must be a whole number of ``block_m`` rows,
    and ``block_m`` a multiple of the 128-lane width unless one block
    covers every row — the per-row ids, distances and weights travel as
    lane-dense ``(1, M)`` rows, so their ``(1, block_m)`` blocks must be
    lane-aligned or whole (the TPU's block-shape rule).

    Raises :class:`TileError` (a ``ValueError``) with the pad recipe —
    callers that hit this forgot to route through
    ``LloydBackend.prepare`` / ``repro.kernels.ops.padded_layout``."""
    if block_m < 1:
        raise TileError(
            f"{kernel}: block_m must be >= 1, got {block_m}",
            extent=m, block=block_m)
    if m % block_m:
        raise TileError(
            f"{kernel}: M={m} is not a multiple of block_m={block_m} — pad "
            f"the points to {pad_to(m, block_m)} rows with zero-weight "
            f"padding (repro.kernels.ops.padded_layout / "
            f"LloydBackend.prepare do this once per fit), or pass "
            f"block_m<= {m} that divides M",
            extent=m, block=block_m)
    if block_m != m and block_m % LANE:
        raise TileError(
            f"{kernel}: block_m={block_m} must be a multiple of {LANE} when "
            f"it does not cover all M={m} rows (clamp it with "
            f"repro.kernels.tiles.clamp_block_m)",
            extent=m, block=block_m)


def clamp_block_m(m: int, block_m: int) -> int:
    """Effective M tile: one block of the 8-padded point count when the
    requested tile covers it (a 6-row problem runs one 8-row tile however
    large the requested block is), otherwise the request rounded up to a
    whole number of 128 lanes (see :func:`require_block_m`)."""
    rows = pad_to(max(m, 1), SUBLANE)
    return min(pad_to(max(block_m, 1), LANE), rows)


def clamp_block_k(k: int, block_k: int) -> int:
    """Effective K tile for the assignment/Lloyd kernels.

    The kernel pads K up to a whole number of ``block_k`` columns and masks
    the tail, so a tile wider than the padded K extent just wastes VMEM —
    clamp to ``pad_to(k, 8)``; and nothing may drop below the 8-sublane
    minimum, so ``k < 8`` always runs one 8-wide tile (``block_k=4`` is
    raised to 8, ``block_k=256`` is lowered to 8 — both end up the SAME
    kernel, which is why the autotuner dedupes candidates through this
    function instead of sweeping phantom configs)."""
    return max(SUBLANE, min(block_k, pad_to(max(k, 1), SUBLANE)))


def clamp_block_l(l: int, block_l: int) -> int:
    """Effective candidate-axis tile for the ADC scan kernel — the
    :func:`clamp_block_m` rule on the L axis (the scan writes its
    distances as lane-dense rows too)."""
    return clamp_block_m(l, block_l)


# The scoped VMEM the Lloyd-family kernels ask the compiler for, and the
# budget :func:`require_lloyd_vmem` holds a fit to before anything is
# compiled.  A TPU v5e core has 128 MiB of VMEM; the compiler's default
# scoped limit there is 16 MiB.
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def compiler_params():
    """Mosaic parameters shared by the Lloyd-family kernels (ignored by the
    interpreter)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def lloyd_vmem_bytes(block_m: int, block_k: int, kp: int, d: int,
                     itemsize: int) -> int:
    """VMEM the fused Lloyd kernel needs for one grid step: the
    double-buffered point/center/row blocks, the (kp, d) ``sums`` and
    (kp, 1) ``counts`` accumulators that stay resident for the whole walk
    (double-buffered, counts padded to 128 lanes), and the f32 temporaries
    of one step — three (bk, bm) distance tiles and the (kp, bm) one-hot.
    """
    dp = pad_to(d, LANE)
    blocks = 2 * (block_m * dp + block_k * dp) * itemsize
    rows = 2 * 3 * SUBLANE * block_m * 4          # w in, idx/dist out
    resident = 2 * (kp * dp + kp * LANE) * 4
    temps = (3 * block_k * block_m + kp * block_m + block_m * dp) * 4
    return blocks + rows + resident + temps


def require_lloyd_vmem(block_m: int, block_k: int, kp: int, d: int,
                       itemsize: int, *, kernel: str = "lloyd") -> None:
    """Raise :class:`TileError` when a (K, d) fit cannot keep the fused
    kernel's accumulators in :data:`VMEM_LIMIT_BYTES` — before the
    compiler refuses it, and never by falling back to another path."""
    need = lloyd_vmem_bytes(block_m, block_k, kp, d, itemsize)
    if need > VMEM_LIMIT_BYTES:
        raise TileError(
            f"{kernel}: K={kp} centers x d={d} need ~{need / 2 ** 20:.1f} "
            f"MiB of VMEM at block_m={block_m}, block_k={block_k} — over "
            f"the {VMEM_LIMIT_BYTES / 2 ** 20:.0f} MiB budget "
            f"(repro.kernels.tiles.VMEM_LIMIT_BYTES); the (K, d) "
            f"accumulator stays resident for the whole pass, so lower K or "
            f"d, or use backend='jnp'",
            extent=kp, block=block_m)
