"""Committed per-device-kind tile defaults — the autotuner's layer-3
fallback (:func:`repro.kernels.autotune.lookup`).

A Python module rather than a JSON data file so plain ``pip install``
packaging ships it (the build only collects ``.py``), and so CI can import
and validate it (:func:`validate_table` is the ``--check-defaults`` hook in
``benchmarks/bench_kernels.py``).

Matching is by *device-kind substring*: the first pattern (insertion
order) whose lowercase form appears in the lowercase
``jax.devices()[0].device_kind`` wins; ``"*"`` matches everything and
belongs last.  Entries are meant to come from real-device sweep campaigns
(``benchmarks/bench_kernels.py --sweep`` under ``benchmarks/
run_device.sh``); refresh them by re-running the sweep on the device kind
and copying the winners here.
"""
from __future__ import annotations

from typing import Optional

# {kernel: {device-kind substring pattern: TileConfig fields}}
# The "TPU v5 lite" (v5e) rows compile for a described v5e at the paper
# and embedding shapes (tests/test_tpu_compile.py); no sweep has timed
# them.  The "*" rows pin the interpret-mode CI smoke values.
TABLE: dict = {
    "lloyd": {
        "TPU v5 lite": {"block_m": 512, "block_k": 256},
        "*": {"block_m": 256, "block_k": 256},
    },
    "assign": {
        "TPU v5 lite": {"block_m": 512, "block_k": 256},
        "*": {"block_m": 256, "block_k": 256},
    },
    "centroid": {
        "TPU v5 lite": {"block_m": 1024},
        "*": {"block_m": 512},
    },
    "scan": {
        "TPU v5 lite": {"block_l": 512},
        "*": {"block_l": 256},
    },
}


def load_default(kernel: str, device_kind: str) -> "Optional[object]":
    """First matching :class:`~repro.kernels.autotune.TileConfig` for a
    device kind, or ``None`` when the kernel has no table (the caller then
    uses the hardcoded default)."""
    from .autotune import TileConfig
    rows = TABLE.get(kernel)
    if not rows:
        return None
    needle = device_kind.lower()
    for pattern, fields in rows.items():
        if pattern == "*" or pattern.lower() in needle:
            return TileConfig.from_dict(fields)
    return None


def validate_table() -> int:
    """Parse every row through ``TileConfig.from_dict`` and check the
    kernel names; returns the entry count.  Raises ``ValueError`` on any
    malformed row — the CI ``--check-defaults`` contract."""
    from .autotune import KERNELS, TileConfig
    n = 0
    for kernel, rows in TABLE.items():
        if kernel not in KERNELS:
            raise ValueError(f"tune_table: unknown kernel {kernel!r}; "
                             f"known: {KERNELS}")
        if not isinstance(rows, dict) or not rows:
            raise ValueError(f"tune_table[{kernel!r}]: must be a non-empty "
                             f"dict of device-kind patterns")
        for pattern, fields in rows.items():
            cfg = TileConfig.from_dict(fields)
            if not any(cfg):
                raise ValueError(f"tune_table[{kernel!r}][{pattern!r}]: "
                                 f"all-zero config")
            n += 1
    return n
