"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is the ``<host>.trace.json.gz`` that ``jax.profiler.stop_trace``
writes beside its ``.xplane.pb`` (Chrome trace events; times in
microseconds).  Device processes are named ``/device:TPU:<n>``; on each,
the ``XLA Ops`` thread holds one event per executed operation, with its HLO
text (``long_name``), the JAX name stack it was traced under (``tf_op``,
e.g. ``jit(_fold_scaled_chunk)/vmap()/while/body/closed_call/pallas_call:``)
and the Python line that made it (``source``).  The host process
``/host:CPU`` holds the benchmark's own spans (``bench_step`` around each
request of the window) and JAX's dispatch events.

Reduced, per run (times in seconds, averaged over the devices used):

  window      from the first ``bench_step`` start to the last one's end
  busy        the union of the device's operation intervals in the window
  ops         device time by operation (its HLO text)
  kernel_s    device time of operations whose name stack and source match
  scope_s     the union of the intervals of operations under a name stack
              (an operation inside a loop lies inside the loop's own event,
              so a sum would count it twice)
  collectives device time of all-reduce / all-gather / reduce-scatter /
              collective-permute / all-to-all operations
  gaps        idle intervals of the union, each named by the innermost host
              event that covers its start
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Optional

import numpy as np

STEP = "bench_step"
OP_LINE = "XLA Ops"
DEVICE_PROC = re.compile(r"^/device:TPU:(\d+)$")
HOST_PROC = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter", re.I)
# the longest idle gaps are named by what the host was doing; the rest are
# summed under one name
NAMED_GAPS = 64


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


@dataclasses.dataclass
class Events:
    names: list         # an operation's HLO text, a host event's name
    start: np.ndarray   # ns
    end: np.ndarray     # ns
    scope: list = dataclasses.field(default_factory=list)   # tf_op
    source: list = dataclasses.field(default_factory=list)  # Python line


def _events(evs: list, with_meta: bool) -> Events:
    names, s, e, scope, source = [], [], [], [], []
    for ev in evs:
        a = ev.get("args", {})
        names.append(a.get("long_name", ev["name"]))
        s.append(ev["ts"] * 1e3)
        e.append((ev["ts"] + ev.get("dur", 0.0)) * 1e3)
        if with_meta:
            scope.append(a.get("tf_op", ""))
            source.append(a.get("source", ""))
    return Events(names, np.asarray(s, np.float64), np.asarray(e, np.float64),
                  scope, source)


def load(path: str) -> dict:
    """``{"devices": {id: Events of its XLA Ops}, "host": [Events per host
    thread]}`` from one ``.trace.json.gz``."""
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    procs, threads = {}, {}
    by_line = collections.defaultdict(list)
    for ev in trace["traceEvents"]:
        ph = ev.get("ph")
        if ph == "M" and ev["name"] == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ph == "M" and ev["name"] == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
        elif ph == "X":
            by_line[(ev["pid"], ev.get("tid"))].append(ev)
    out = {"devices": {}, "host": []}
    for (pid, tid), evs in by_line.items():
        proc = procs.get(pid, "")
        m = DEVICE_PROC.match(proc)
        if m and threads.get((pid, tid)) == OP_LINE:
            out["devices"][int(m.group(1))] = _events(evs, with_meta=True)
        elif proc == HOST_PROC:
            out["host"].append(_events(evs, with_meta=False))
    return out


def union(start: np.ndarray, end: np.ndarray, lo: float, hi: float
          ) -> list:
    """Merged intervals of ``[start, end)`` clipped to ``[lo, hi)``."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    out = []
    for a, b in zip(s[order], e[order]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clipped(ev: Events, lo: float, hi: float) -> np.ndarray:
    return np.maximum(0.0, np.minimum(ev.end, hi) - np.maximum(ev.start, lo))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices
    ops: dict                     # operation -> seconds (mean over devices)
    collective_s: float
    gaps: list                    # [(host event name, seconds)], longest 1st
    n_devices: int
    steps: int
    devices: dict                 # id -> Events of the window's operations
    lo: float                     # the window, ns
    hi: float

    def kernel_s(self, scope: str, source: str) -> float:
        """Device time (mean over devices) of operations whose name stack
        matches ``scope`` and whose Python line matches ``source``."""
        rs, rf = re.compile(scope), re.compile(source)
        total = 0.0
        for ev in self.devices.values():
            t = _clipped(ev, self.lo, self.hi)
            total += sum(dt for sc, src, dt in zip(ev.scope, ev.source, t)
                         if dt > 0 and rs.search(sc) and rf.search(src))
        return total * 1e-9 / max(len(self.devices), 1)

    def scope_s(self, scope: str) -> float:
        """Device time (mean over devices) covered by operations whose name
        stack matches ``scope``: the union of their intervals."""
        rs = re.compile(scope)
        total = 0.0
        for ev in self.devices.values():
            keep = np.asarray([bool(rs.search(sc)) for sc in ev.scope], bool)
            if keep.any():
                iv = union(ev.start[keep], ev.end[keep], self.lo, self.hi)
                total += sum(b - a for a, b in iv)
        return total * 1e-9 / max(len(self.devices), 1)

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps_by = {}
        for name, t in self.gaps:
            gaps_by[name] = gaps_by.get(name, 0.0) + t
        gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k[:120], t] for k, t in ops],
                "idle_gaps": [[k[:120], t] for k, t in gaps]}


def _host_name_at(host: list, t: float) -> str:
    """The shortest (innermost) host event covering time ``t``."""
    best, best_len = "host idle", np.inf
    for ev in host:
        if not len(ev.start):
            continue
        cover = (ev.start <= t) & (ev.end > t)
        if cover.any():
            idx = np.flatnonzero(cover)
            lens = ev.end[idx] - ev.start[idx]
            j = idx[np.argmin(lens)]
            if lens.min() < best_len and ev.names[j] != STEP:
                best, best_len = ev.names[j], lens.min()
    return best


def reduce(tr: dict, n_devices: int) -> Reduction:
    steps = [(s, e) for ev in tr["host"]
             for n, s, e in zip(ev.names, ev.start, ev.end) if n == STEP]
    if not steps:
        raise ValueError("trace: no bench_step spans on the host")
    lo = min(s for s, _ in steps)
    hi = max(e for _, e in steps)
    devs = sorted(tr["devices"])[:n_devices]
    if not devs:
        raise ValueError("trace: no TPU device planes")
    busy, ops, coll, gaps = 0.0, {}, 0.0, []
    for d in devs:
        op = tr["devices"][d]
        iv = union(op.start, op.end, lo, hi)
        busy += sum(b - a for a, b in iv) * 1e-9
        t = _clipped(op, lo, hi)
        for lab, dt in zip(op.names, t):
            if dt > 0:
                ops[lab] = ops.get(lab, 0.0) + dt * 1e-9
                if COLLECTIVE.search(lab):
                    coll += dt * 1e-9
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(a, b - a) for a, b in zip(edges[0::2], edges[1::2])
                 if b > a]
    n = len(devs)
    # name the longest gaps by what the host was doing; lump the rest
    gaps.sort(key=lambda g: -g[1])
    named = [(_host_name_at(tr["host"], a), g * 1e-9)
             for a, g in gaps[:NAMED_GAPS]]
    rest = sum(g for _, g in gaps[NAMED_GAPS:]) * 1e-9
    gaps = named + ([("(shorter gaps)", rest)] if rest > 0 else [])
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy / n,
        ops={k: v / n for k, v in ops.items()},
        collective_s=coll / n, gaps=[(k, v / n) for k, v in gaps],
        n_devices=n, steps=len(steps),
        devices={d: tr["devices"][d] for d in devs}, lo=lo, hi=hi)


def find_trace(log_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_dir(log_dir: str, n_devices: int) -> Reduction:
    path = find_trace(log_dir)
    if path is None:
        raise ValueError(f"trace: no .trace.json.gz under {log_dir}")
    return reduce(load(path), n_devices)
