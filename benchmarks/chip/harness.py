"""What every run shares: finding the cell and its files, the environment
(compile cache, sources), the device check, the measured window, the
metric readers and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is found by name;
its configuration, traffic mix and limits are files named after the
configuration, the traffic and the cell:

  configs/<config>.json    sizes, spec, source, what was assumed
  traffic/<traffic>.json   ``driver`` (a file in drivers/) and its parameters
  limits/<cell>.json       each compared number's limit, and its readings
  metrics/<metric>.py      ``read(ctx)`` -> a number, or None where the run
                           has nothing for that metric to read
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
SRC = ROOT / "src"
# one fixed directory inside the checkout: the path is part of what a
# cached program is found by
CACHE_DIR = ROOT / ".jax_cache"


class NoResult(Exception):
    """The run cannot produce a result (no chip, missing sources, ...)."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    bench: dict

    @classmethod
    def find(cls, name: str, bench: Optional[dict] = None) -> "Cell":
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        for w in bench["workloads"]:
            if w["name"] == name:
                break
        else:
            raise NoResult(f"no workload {name!r} in BENCHMARK.json")
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        return cls(name=name, chips=int(w["chips"]),
                   config=load_json(ROOT / cfg["file"]),
                   traffic=load_json(BENCH / "traffic" /
                                     f"{w['traffic']}.json"),
                   limits=load_json(BENCH / "limits" / f"{name}.json"),
                   bench=bench)

    def metrics(self, kind: str) -> list:
        """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]


def prepare_env() -> None:
    """Sources on the path, compile cache in the checkout, committed tiles
    only; refuses the program's backend overrides."""
    if not (SRC / "repro").is_dir():
        raise NoResult(f"no program sources at {SRC}")
    for var in ("REPRO_KMEANS_BACKEND", "REPRO_SCAN_BACKEND"):
        if os.environ.get(var):
            raise NoResult(f"{var} is set; the benchmark runs the default "
                           f"backend resolution")
    os.environ.pop("REPRO_TUNE_CACHE", None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(SRC), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, however quick to compile: the eager merge is
    # many small programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_tpu(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoResult(f"no TPU: JAX found {devices[0].platform} "
                       f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoResult(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Backend compiles (or persistent-cache loads) seen by JAX."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on_duration)


@dataclasses.dataclass
class Check:
    """One compared number and its limit: ``value <= limit`` passes."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks_from(readings: dict, limits: dict) -> list:
    """Checks for the numbers ``limits['compare']`` names."""
    out = []
    for name, lim in limits["compare"].items():
        v = readings.get(name)
        out.append(Check(name, float("nan") if v is None else float(v),
                         float(lim["limit"])))
    return out


@dataclasses.dataclass
class Window:
    """What the measured window recorded: per request its arrival (seconds
    from the window's start), its latency and its units of work, and how
    long after its arrival it was started (its wait in the queue)."""
    starts: list
    latencies: list
    units: list
    seconds: float
    compiles: int
    waits: list = dataclasses.field(default_factory=list)


def arrival_times(traffic: dict, seconds: float):
    """Offsets (s from the window's start) at which requests arrive, from
    the traffic's ``arrivals`` section, or None for a closed loop (no
    section: each request starts when the last one has finished).

    ``{"kind": "poisson", "rate_per_s": r, "seed": s}``: exponential gaps
    of mean ``1 / r``, drawn from ``s``, so every run of a cell offers the
    same schedule whatever its ``--seed``.
    """
    arr = traffic.get("arrivals")
    if arr is None:
        return None
    if arr["kind"] != "poisson":
        raise ValueError(f"arrivals: unknown kind {arr['kind']!r}")
    import numpy as np
    rate = float(arr["rate_per_s"])
    n = int(rate * seconds * 1.5) + 64
    gaps = np.random.default_rng(int(arr["seed"])).exponential(1.0 / rate, n)
    t = np.cumsum(gaps) - gaps[0]
    if t[-1] < seconds:
        raise ValueError("arrivals: schedule shorter than the window")
    return t[t < seconds]


def run_window(step, seconds: float, counter: CompileCounter,
               annotate: bool, arrivals=None) -> Window:
    """One client, ``step(i)`` for request ``i``.  Closed loop
    (``arrivals`` None): back to back until ``seconds`` have passed, the
    request under way then completing and counting.  Open loop: request
    ``i`` is started at ``arrivals[i]``, or as soon as the one before it
    has finished if that is later; its latency runs from its arrival, so
    it counts the wait in the queue."""
    import jax
    starts, lats, units, waits = [], [], [], []
    c0 = counter.count
    t0 = time.perf_counter()
    i = 0
    while True:
        t = time.perf_counter()
        if arrivals is None:
            if t - t0 >= seconds:
                break
            arrived = t
        else:
            if i >= len(arrivals):
                break
            arrived = t0 + float(arrivals[i])
            if arrived > t:
                time.sleep(arrived - t)
        waits.append(max(0.0, time.perf_counter() - arrived))
        if annotate:
            with jax.profiler.TraceAnnotation("bench_step"):
                u = step(i)
        else:
            u = step(i)
        t1 = time.perf_counter()
        starts.append(arrived - t0)
        lats.append(t1 - arrived)
        units.append(u)
        i += 1
    total = time.perf_counter() - t0
    return Window(starts, lats, units, total, counter.count - c0, waits)


def latency_line(window: Window, slowest: int = 5) -> str:
    """The window's request latencies in one line: count, quartiles, the
    largest, the slowest requests with their place in the window, and the
    mean and largest wait from arrival to start."""
    import numpy as np
    lat = np.asarray(window.latencies)
    if not lat.size:
        return "latencies: none"
    q = np.percentile(lat, [0, 25, 50, 75, 95, 100])
    worst = np.argsort(-lat, kind="stable")[:slowest]
    wait = np.asarray(window.waits or [0.0])
    return ("latencies (s): n=%d min=%r q1=%r median=%r q3=%r p95=%r "
            "max=%r slowest=%s wait_mean=%r wait_max=%r" % (
                lat.size, *[float(v) for v in q],
                [(int(j), float(lat[j])) for j in worst],
                float(wait.mean()), float(wait.max())))


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    cell: Cell
    setup_s: float
    window: Optional[Window] = None
    trace: Any = None            # tracered.Reduction (traced runs)
    layer: dict = dataclasses.field(default_factory=dict)  # driver's counts
    device_kind: str = ""


def read_metrics(entries: list, ctx: Context) -> dict:
    out = {}
    for m in entries:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(result: dict, checks: list) -> None:
    """The checks as the last lines on stderr, then the result as the last
    line on stdout, its ``checks`` key last."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)
