"""Pallas ADC (asymmetric distance computation) scan kernel — the IVF/PQ
query hot loop.

A product-quantized database stores each vector as ``m`` small codes; a
query is compared against candidates through per-subspace **lookup tables**
(LUTs): ``dist(q, x) = sum_j lut[j, code_j(x)]`` where ``lut[j, c] =
||q_j - codebook[j, c]||^2``.  The scan over a candidate list is therefore
a gather-accumulate, not a matmul — the memory-bound sibling of
``kernels/lloyd.py``'s fused distance pass, and the per-query analogue of
the paper's "scan the partition you routed to" step.

TPU adaptation: VMEM has no efficient random gather, but the LUT axis is
tiny (``C = 2^bits`` = 16 or 256), so each per-subspace lookup becomes a
one-hot compare + MXU matvec against that subspace's LUT row — the same
iota-compare one-hot trick the fused Lloyd kernel uses for its centroid
accumulation:

  * grid = (B groups, L tiles): group ``b`` is one (query, probed-cell)
    pair sharing a single (m, C) LUT; its candidate codes stream through
    VMEM ``block_l`` rows at a time;
  * per tile the kernel unrolls the (static, small) subspace axis: each
    subspace contributes ``onehot(code_j) @ lut[j]`` to a running f32
    distance accumulator — codes never round-trip through HBM decoded;
  * LUTs may arrive in bf16; accumulation is always fp32.

``adc_scan`` is the public entry: a ``jnp`` reference backend
(``take_along_axis`` gather) and the Pallas kernel with interpret-mode
parity on CPU (interpreted off a TPU), selected like the
``LloydBackend`` registry (``"auto"`` = Pallas on TPU, jnp elsewhere,
overridable via ``REPRO_SCAN_BACKEND``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ENV_VAR = "REPRO_SCAN_BACKEND"
_SCAN_BACKENDS = ("jnp", "pallas")


def adc_scan_jnp(luts: jax.Array, codes: jax.Array) -> jax.Array:
    """Reference ADC scan: (B, m, C) LUTs + (B, L, m) codes -> (B, L) f32
    distances via one batched gather (``lut[b, j, codes[b, l, j]]`` summed
    over ``j``)."""
    luts = luts.astype(jnp.float32)
    idx = jnp.swapaxes(codes.astype(jnp.int32), 1, 2)     # (B, m, L)
    picked = jnp.take_along_axis(luts, idx, axis=2)       # (B, m, L)
    return jnp.sum(picked, axis=1)                        # (B, L)


def _adc_kernel(codes_ref, lut_ref, out_ref, *, m: int, c: int):
    code = codes_ref[0]                                   # (bl, m) int32
    lut = lut_ref[0].astype(jnp.float32)                  # (m, C)
    bl = code.shape[0]
    acc = jnp.zeros((1, bl), jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bl, c), 1)
    for j in range(m):        # m is static and small: unrolled lookups
        onehot = jnp.where(cols == code[:, j:j + 1], 1.0, 0.0)
        # (1, C) x (bl, C)^T: the gather as an MXU matvec against one LUT
        # row, landing lane-dense like the (1, bl) output block
        acc = acc + jax.lax.dot_general(
            lut[j:j + 1], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out_ref[0] = acc


def adc_scan_pallas(luts: jax.Array, codes: jax.Array, *,
                    block_l: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Pallas ADC scan: (B, m, C) LUTs + (B, L, m) int codes -> (B, L) f32.

    ``L`` is padded to a multiple of ``block_l`` internally (padded rows
    scan code 0 and are sliced off — the caller masks invalid candidate
    slots itself, exactly as with the jnp reference).  ``block_l=None``
    consults the autotune cache for this shape
    (:func:`repro.kernels.autotune.lookup` — a host-side read, safe under
    jit); an explicit value is clamped to the effective tile
    (:func:`repro.kernels.tiles.clamp_block_l`) and pins the schedule.
    Either way the values are identical — tiling changes schedule, never
    math.
    """
    from . import default_interpret
    if interpret is None:
        interpret = default_interpret()
    b, m, c = luts.shape
    l = codes.shape[1]
    if codes.shape[0] != b or codes.shape[2] != m:
        raise ValueError(f"adc_scan: codes {codes.shape} do not match "
                         f"luts {luts.shape}")
    codes = codes.astype(jnp.int32)
    if block_l is None:
        from .autotune import lookup
        block_l = lookup("scan", b=b, l=l, msub=m, c=c,
                         dtype=luts.dtype).block_l
    from .tiles import clamp_block_l
    block_l = clamp_block_l(l, block_l)
    lp = -(-l // block_l) * block_l
    if lp != l:
        codes = jnp.pad(codes, ((0, 0), (0, lp - l), (0, 0)))
    grid = (b, lp // block_l)

    out = pl.pallas_call(
        functools.partial(_adc_kernel, m=m, c=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_l, m), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, m, c), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_l), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, lp), jnp.float32),
        interpret=interpret,
        name="scan",
    )(codes, luts)
    return out[:, 0, :l]


def resolve_scan_backend(name: str | None = None) -> str:
    """Resolve an ADC scan backend name: ``"jnp"``/``"pallas"`` pass
    through; ``None``/``"auto"`` consults ``REPRO_SCAN_BACKEND`` then the
    hardware (Pallas on TPU, jnp elsewhere — the interpreter is
    correctness-, not speed-, oriented)."""
    name = name or "auto"
    if name == "auto":
        name = os.environ.get(ENV_VAR) or "auto"
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if name not in _SCAN_BACKENDS:
        raise ValueError(f"unknown scan backend {name!r}; known: "
                         f"{_SCAN_BACKENDS} + 'auto'")
    return name


def adc_scan(luts: jax.Array, codes: jax.Array, *,
             backend: str | None = None, block_l: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Backend-dispatched ADC scan (see :func:`adc_scan_jnp` /
    :func:`adc_scan_pallas`); both return identical (B, L) f32 distances.
    ``block_l=None`` lets the autotune cache pick the candidate tile."""
    name = resolve_scan_backend(backend)
    if name == "pallas":
        return adc_scan_pallas(luts, codes, block_l=block_l,
                               interpret=interpret)
    return adc_scan_jnp(luts, codes)
