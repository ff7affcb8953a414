#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Set-up (data from the seed, on the device; the cell's shapes warmed; the
persistent compile cache in ``<checkout>/.jax_cache``) is timed as
``setup_s``.  Then one client runs the cell's traffic for ``--seconds``,
back to back or at the traffic's arrival times (``harness.run_window``);
with ``--trace 1`` the window runs under the profiler and the per-layer
metrics are read from the trace.  After the window the timed path's answers
are checked against the plain reference (``reference.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
the compared numbers and their limits under ``checks``, last.  No TPU, too
few chips, or no program sources: exit code 2 and no result line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.Cell.find(args.workload)
        harness.prepare_env()
        jax = harness.configure_jax()
        devices = harness.require_tpu(cell.chips)
    except harness.NoResult as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    counter = harness.CompileCounter()
    driver_mod = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    driver = driver_mod.Driver(cell, args.seed, devices)
    driver.setup()
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if trace_dir:
        import tracered
        tracered.start(trace_dir)
    window = harness.run_window(
        driver.step, args.seconds, counter, annotate=bool(args.trace),
        arrivals=harness.arrival_times(cell.traffic, args.seconds))
    reduction = None
    if trace_dir:
        tracered.stop()
    peak = harness.peak_bytes(devices)
    print(harness.latency_line(window), file=sys.stderr)
    if window.compiles:
        print(f"run: {window.compiles} compile(s) inside the window",
              file=sys.stderr)
    if trace_dir:
        reduction = tracered.reduce_dir(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = harness.Context(cell=cell, setup_s=setup_s, window=window,
                          trace=reduction, layer=driver.layer_counts(window),
                          device_kind=devices[0].device_kind)
    metrics = harness.read_metrics(
        cell.metrics("per_layer" if args.trace else "end_to_end"), ctx)

    driver.release()
    readings = driver.check(window)
    checks = harness.checks_from(readings, cell.limits)
    correct = all(c.ok for c in checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    # a request that raises ends the run with no result, so none failed
    result = {"correct": correct, "attempted": len(window.latencies),
              "failed": 0, "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
