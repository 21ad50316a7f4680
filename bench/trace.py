"""Profiler trace of the window, reduced to device busy time.

`Profile` records the window with JAX's profiler (host runtime events and
device operations; the Python tracer off, so the serving loop runs at
its own speed). `events` flattens the written ``.xplane.pb`` into plain
lists, the form kept as a test fixture:

  ``device``   {plane name: [[op name, start_ns, end_ns], ...]} for the
               device planes of the chips the cell uses (their "XLA Ops"
               line);
  ``host``     [[thread, event name, start_ns, end_ns], ...];
  ``markers``  {marker name: [start_ns, ...]}: the benchmark's own
               zero-length annotations (window open, each batch of
               images landed on the host).

`summarize` reduces that to one steady slice of the window: the span
from the window's opening to the last image landing, less ``trim`` of it
at each end. Busy time is the union of the device's operation
intervals inside the slice, averaged over the devices; an idle gap is a
stretch of the slice with no operation, named by the host event that
overlaps it most.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

OPEN = "bench.window_open"
LANDED = "bench.images_landed"
DEVICE_LINES = ("XLA Ops",)


class Profile:
    """Context manager: JAX's profiler on, Python tracer off."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False


def marker(name: str) -> None:
    """A zero-length host annotation at this instant, on the trace's
    clock (a no-op when no trace is running)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        pass


def events(log_dir: str, n_devices: int) -> Dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, List] = {}
    host: List = []
    markers: Dict[str, List[float]] = collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [[e.name, e.start_ns, e.end_ns]
                   for line in plane.lines if line.name in DEVICE_LINES
                   for e in line.events]
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (OPEN, LANDED):
                        markers[e.name].append(e.start_ns)
                    else:
                        host.append([line.name, e.name, e.start_ns,
                                     e.end_ns])
    used = dict(sorted(device.items())[:n_devices])
    return {"device": used, "host": host, "markers": dict(markers)}


_HLO = re.compile(r"^(%[\w.\-]+) = (\w+\[[\d,]*\])\S* (\S+?)\(")


def short_name(op: str) -> str:
    """``%slice.113 = f32[4,334,64,32,2]{...} slice(...)`` ->
    ``%slice.113 f32[4,334,64,32,2] slice``; the instruction name alone
    where the result is a tuple."""
    m = _HLO.match(op)
    if m:
        return " ".join(m.groups())
    return op.split(" = ")[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def summarize(ev: Dict, trim: float = 0.1, top: int = 10) -> Dict:
    """busy_s, window_s (the slice), and the breakdown lists."""
    if not ev["device"]:
        raise ValueError("the trace holds no device operations")
    if OPEN not in ev["markers"] or LANDED not in ev["markers"]:
        raise ValueError("the trace holds no window markers")
    w0 = min(ev["markers"][OPEN])
    w1 = max(ev["markers"][LANDED])
    lo, hi = w0 + trim * (w1 - w0), w1 - trim * (w1 - w0)
    slice_ns = hi - lo
    if slice_ns <= 0:
        raise ValueError("empty trace slice")
    busy_ns, per_op = [], collections.Counter()
    gaps = []
    for ops in ev["device"].values():
        clipped = _clip([(a, b) for _, a, b in ops], lo, hi)
        busy = _union(clipped)
        busy_ns.append(sum(b - a for a, b in busy))
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                per_op[short_name(name)] += (b - a) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_doing(ev["host"], a, b), (b - a) / 1e9]
            for a, b in gaps[:top]]
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": slice_ns / 1e9,
            "device_ops": [[n, s] for n, s in per_op.most_common(top)],
            "idle_gaps": idle}


def _host_doing(host: List, a: float, b: float) -> str:
    """The host event overlapping [a, b] the most (the shorter one on a
    tie: the more specific)."""
    best, key = "no host event", None
    for thread, name, s, e in host:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            k = (ov, -(e - s))
            if key is None or k > key:
                best, key = f"{thread}: {name}", k
    return best
