#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

  python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 ... \
      --control-seeds 101 102 103 [--out FILE]

In one process, for each ``--seeds`` seed: the cell's own set-up and
``--steps`` requests of its timed path, then the same comparison a run
makes.  For each ``--control-seeds`` seed, the control stands in for the
program and goes through the same comparison:

  fit cells     the plain reference itself, its matmuls in three bf16
                passes (the scheme of ``Precision.HIGH``) instead of full
                float32 (``drivers/fit_loop.py``);
  search cells  the program with its 4-bit PQ path (``pq.bits=4``) instead
                of the configured 8 bits, over its first ``--steps``
                requests (``drivers/search_loop.py``).

``--fault NAME`` plants one of ``faults.py``'s faults in the program for
the ``--seeds`` readings: a fault that a limit catches reads above it.

Prints one JSON line per seed and, last, the largest program reading and
the smallest control reading of every number.  Runs on the chip the cell
names; ``--allow-cpu`` lets tests drive it at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import faults
import harness


def driver_module(cell):
    return harness.load_module(harness.BENCH / "drivers" /
                               f"{cell.traffic['driver']}.py")


def program_readings(cell, seed, devices, steps: int) -> dict:
    d = driver_module(cell).Driver(cell, seed, devices)
    d.setup()
    window = harness.Window([], [], [], 0.0, 0)
    for i in range(steps):
        t = time.perf_counter()
        window.units.append(d.step(i))
        window.latencies.append(time.perf_counter() - t)
    d.release()
    return d.check(window)


def control_readings(cell, seed, devices, steps: int = 0) -> dict:
    """The control's readings for ``seed``: the cell's driver says what its
    control is (``drivers/<driver>.py: control_readings``)."""
    return driver_module(cell).control_readings(cell, seed, devices, steps)


def summarize(prog: list, ctrl: list) -> dict:
    names = sorted({k for r in prog + ctrl for k in r})
    out = {}
    for n in names:
        p = [r[n] for r in prog if n in r]
        c = [r[n] for r in ctrl if n in r]
        out[n] = {"lower": max(p) if p else None,
                  "upper": min(c) if c else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--fault", help="plant this fault for --seeds")
    ap.add_argument("--out")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell.find(args.workload)
    harness.prepare_env()
    harness.configure_jax()
    import jax
    devices = (jax.devices()[:cell.chips] if args.allow_cpu
               else harness.require_tpu(cell.chips))
    prog, ctrl = [], []

    def show(side, seed, r, t):
        failed = [c.name for c in harness.checks_from(r, cell.limits)
                  if not c.ok]
        print(json.dumps({"side": side, "seed": seed, "readings": r,
                          "fails": failed, "s": time.perf_counter() - t}),
              flush=True)

    plant = (faults.planted(args.fault) if args.fault
             else contextlib.nullcontext())
    with plant:
        for s in args.seeds:
            t = time.perf_counter()
            prog.append(program_readings(cell, s, devices, args.steps))
            show("program", s, prog[-1], t)
    for s in args.control_seeds:
        t = time.perf_counter()
        ctrl.append(control_readings(cell, s, devices, args.steps))
        show("control", s, ctrl[-1], t)
    summary = {"workload": cell.name, "device": devices[0].device_kind,
               "fault": args.fault, "program": prog, "control": ctrl,
               "readings": summarize(prog, ctrl)}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary["readings"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
