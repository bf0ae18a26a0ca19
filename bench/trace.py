"""The device trace of a ``--trace 1`` run, reduced to what the metric
readers need: device busy time, time per device operation and per XLA
program, the benchmark's own host spans, and the idle gaps between
device operations with what the host was doing in each.

The profiler (``jax.profiler``) writes an ``.xplane.pb``; it is read
with ``jax.profiler.ProfileData``.  Device planes are ``/device:TPU:<i>``;
on each, the ``XLA Modules`` line holds one event per program run (named
``jit_<function>(<fingerprint>)``), the ``XLA Ops`` line one per
operation (named by its HLO text, ``%<op> = ...``; a ``while`` event
encloses the operations of its body), and the ``Async XLA Ops`` line the
asynchronous copies, slices and collectives from start to done.  The
benchmark's host spans (``span``) are ``TraceAnnotation`` events on the
``/host:CPU`` plane.  The Python tracer stays off: it would record every
Python call of the server and slow the host it measures.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import shutil
import tempfile
import time
from typing import Dict, List, Tuple

import jax

# the benchmark's host spans; an idle gap is put down to the shortest of
# them that covers it
HOST_SPANS = ("admission", "decode", "patch", "submit", "step", "wait",
              "window")


def span(name: str):
    """A host span the reduction can read (a profiler TraceMe)."""
    return jax.profiler.TraceAnnotation(name)


class Profile:
    """Traces the devices while open, when ``on``, from ``after_s`` into
    the window (``poll`` starts it) to its end.  The traced part runs
    inside a ``window`` span, which bounds the reduction."""

    def __init__(self, on: bool, devs, after_s: float = 0.0):
        self.on = on
        self.devs = devs
        self.after_s = after_s
        self.dir = None
        self._span = None
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = time.monotonic()
        self.poll()
        return self

    def poll(self) -> bool:
        """Start tracing once ``after_s`` of the window has passed;
        True while tracing."""
        if self.on and self.dir is None and (
                time.monotonic() - self._t0 >= self.after_s):
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the benchmark's own spans
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = span("window")
            self._span.__enter__()
        return self.dir is not None

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.__exit__(*exc)
            jax.profiler.stop_trace()

    def reduction(self):
        if self.dir is None:
            return None
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            return reduce_file(files[0], n_devices=len(self.devs))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


class Reduction:
    """What one trace says, over the window span."""

    def __init__(self, window: Tuple[int, int], devices: Dict[int, dict],
                 host: List[Tuple[str, int, int]]):
        self.window = window
        self.devices = devices  # id -> {"busy", "ops", "modules", "gaps"}
        self.host = host  # (name, start_ns, end_ns) of the bench's spans
        n = max(1, len(devices))
        self.window_s = (window[1] - window[0]) * 1e-9
        self.busy_s = sum(d["busy_ns"] for d in devices.values()) * 1e-9 / n

    def op_seconds(self, match, async_ops: bool = False) -> float:
        """Device seconds of the operations whose name ``match`` accepts
        (on the ``Async XLA Ops`` line, with ``async_ops``), averaged over
        the devices."""
        n = max(1, len(self.devices))
        return sum(ns for d in self.devices.values()
                   for (_, name, a), ns in d["ops"].items()
                   if a == async_ops and match(name)) * 1e-9 / n

    def module_stats(self, match) -> Tuple[float, float]:
        """(runs, device seconds) of the XLA programs ``match`` accepts,
        averaged over the devices."""
        n = max(1, len(self.devices))
        runs = secs = 0
        for d in self.devices.values():
            for name, (count, ns) in d["modules"].items():
                if match(name):
                    runs += count
                    secs += ns
        return runs / n, secs * 1e-9 / n

    def busy_in(self, name: str) -> Tuple[float, float]:
        """(device-busy seconds inside the host spans ``name``, seconds of
        those spans), averaged over the devices."""
        spans = _union(_clip([(a, b) for s, a, b in self.host if s == name],
                             *self.window))
        inside = sum(b - a for a, b in spans)
        n = max(1, len(self.devices))
        both = sum(_overlap(d["busy"], spans) for d in self.devices.values())
        return both * 1e-9 / n, inside * 1e-9

    def host_count(self, name: str) -> int:
        return sum(1 for s, _, _ in self.host if s == name)

    def breakdown(self) -> dict:
        n = max(1, len(self.devices))
        ops = collections.Counter()
        for d in self.devices.values():
            for (prog, name, async_), ns in d["ops"].items():
                if not async_:
                    ops[f"{prog} {name}"] += ns
        gaps = []
        for d in self.devices.values():
            gaps.extend(d["gaps"])
        gaps.sort(key=lambda g: -g[1])
        return {
            "device_ops": [[name, ns * 1e-9 / n]
                           for name, ns in ops.most_common(10)],
            "idle_gaps": [[name, ns * 1e-9] for name, ns in gaps[:10]],
        }


def _overlap(xs, ys) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, b - a)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _innermost(host, t: int) -> str:
    best, width = "none", None
    for name, a, b in host:
        if a <= t < b and (width is None or b - a < width):
            best, width = name, b - a
    return best


# operations that enclose others on the ``XLA Ops`` line
ENCLOSING = ("%while", "%conditional", "%call")


def op_name(event_name: str) -> str:
    """``%fusion.85 = bf16[...] fusion(...)`` -> ``%fusion.85``."""
    return event_name.split(" = ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit_step(1766...)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def reduce_file(path: str, n_devices: int) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    windows = [(a, b) for name, a, b in host if name == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one window span, found {len(windows)}")
    lo, hi = windows[0]
    devices = {}
    for plane in device_planes:
        dev = int(plane.name.rsplit(":", 1)[1].split()[0])
        lines = {line.name: line for line in plane.lines}
        runs = []  # (start, end, program) of every program run
        modules = {}
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines \
                else ():
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            if b <= lo or a >= hi:
                continue
            name = module_name(ev.name)
            runs.append((a, b, name))
            c, ns = modules.get(name, (0, 0))
            modules[name] = (c + 1, ns + min(b, hi) - max(a, lo))
        runs.sort()
        starts = [r[0] for r in runs]

        def program_at(t):
            j = bisect.bisect_right(starts, t) - 1
            return runs[j][2] if j >= 0 and t < runs[j][1] else "?"

        ops, iv = collections.Counter(), []
        for key, async_ in (("XLA Ops", False), ("Async XLA Ops", True)):
            for ev in lines[key].events if key in lines else ():
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= lo or a >= hi:
                    continue
                name = op_name(ev.name)
                if not async_:
                    iv.append((a, b))
                if name.startswith(ENCLOSING):
                    continue
                ops[(program_at(a), name, async_)] += min(b, hi) - max(a, lo)
        busy = _union(_clip(iv, lo, hi))
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        longest = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                          if b > a), reverse=True)[:10]
        gaps = [(_innermost(host, a + ns // 2), ns) for ns, a in longest]
        devices[dev] = {
            "busy": busy,
            "busy_ns": sum(b - a for a, b in busy),
            "ops": dict(ops),
            "modules": modules,
            "gaps": gaps,
        }
    if len(devices) < n_devices:
        raise RuntimeError(
            f"trace has {len(devices)} device planes, expected {n_devices}")
    return Reduction((lo, hi), devices, host)
