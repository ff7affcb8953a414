"""Pallas TPU kernels for the paper's compute hot spots.

  assign.py    — k-means assignment (tiled distance + running argmin)
  centroid.py  — weighted centroid update (one-hot MXU segment-sum)
  lloyd.py     — FUSED Lloyd step: assignment + weighted accumulation + SSE
                 in one pass over x (see repro.core.backend for selection)
  scan.py      — ADC lookup-table scan for IVF/PQ queries (repro.index)
  cluster_attn.py — decode attention over clustered KV centroids
  ops.py       — jit'd public wrappers (padding, dtype plumbing)
  ref.py       — pure-jnp oracles
  tiles.py     — the shared tile-shape contract (clamps, TileError)
  autotune.py  — shape/device-keyed tile-config search + caches
  tune_table.py — committed per-device-kind tile defaults

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are validated
on CPU with ``interpret=True``; ``default_interpret()`` is true exactly when
the default backend is not a TPU, so on a TPU every kernel is compiled.
"""
from __future__ import annotations


def default_interpret() -> bool:
    """interpret=True everywhere except a TPU backend — no override."""
    import jax
    return jax.default_backend() != "tpu"


from .ops import (assign_argmin, centroid_update, cluster_attn_decode,
                  lloyd_step, pad_to, pallas_assign_fn)  # noqa: E402
from .scan import adc_scan, resolve_scan_backend  # noqa: E402
from .tiles import TileError  # noqa: E402

__all__ = ["default_interpret", "assign_argmin", "centroid_update",
           "cluster_attn_decode", "lloyd_step", "pad_to", "pallas_assign_fn",
           "adc_scan", "resolve_scan_backend", "TileError"]
