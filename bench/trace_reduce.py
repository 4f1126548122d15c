"""From a profiler trace to the numbers the per-layer metrics read.

`load(path)` reads the `.xplane.pb` file that `jax.profiler` writes with
nothing but JAX, keeping the device operations of every device plane and the
benchmark's own host spans (`jax.profiler.TraceAnnotation` named
"bench.*"). `reduce(trace)` then works out, over the traced window (from the
first benchmark span's start to the last one's end):

* `window_s`: the window's length;
* `busy_s`: the union of the intervals in which an operation ran on the
  device, averaged over the device planes;
* `op_s`: the device seconds of each operation group (the op's name with
  its numeric suffix removed), summed over devices and divided by them;
  loops and calls, which span their bodies' ops, are left out of it;
* `idle_by_host`: the device's idle seconds, charged to the benchmark span
  the host was in ("none" outside all of them);
* `ops`: the raw (text, seconds) of every device op in the window, for a
  metric that matches kernels by name.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

HOST_PREFIX = "bench."
# control-flow ops that span the ops of their body: busy time, not op time
CONTAINERS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class Trace:
    # per device plane: list of (start_ns, end_ns, text)
    devices: List[List[Tuple[int, int, str]]]
    # benchmark host spans: (start_ns, end_ns, name)
    host: List[Tuple[int, int, str]]


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _text(ev) -> str:
    """The op's name and its string-valued stats, so a kernel can be matched
    by whichever of them carries its name."""
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str) and len(v) < 4096:
            parts.append(v)
    return " ".join(parts)


def _op_lines(plane):
    lines = list(plane.lines)
    ops = [l for l in lines if l.name == "XLA Ops"]
    return ops or [l for l in lines if "Ops" in l.name]


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in _op_lines(plane):
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((s, s + int(ev.duration_ns), _text(ev)))
            if evs:
                devices.append(sorted(evs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
    return Trace(devices=devices, host=sorted(host))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_SUFFIX = re.compile(r"[.\-_]?\d+$")


def group(name: str) -> str:
    return _SUFFIX.sub("", name.split(" ")[0])


def reduce(trace: Trace) -> Dict:
    if not trace.devices or not trace.host:
        return {}
    t0 = min(s for s, _, _ in trace.host)
    t1 = max(e for _, e, _ in trace.host)
    n_dev = len(trace.devices)
    busy_ns = 0
    op_ns = collections.Counter()
    idle = collections.Counter()
    ops = []
    spans = sorted(trace.host)
    starts = [s for s, _, _ in spans]
    for evs in trace.devices:
        clipped = [(max(s, t0), min(e, t1), txt) for s, e, txt in evs
                   if e > t0 and s < t1]
        for s, e, txt in clipped:
            if group(txt) in CONTAINERS:
                continue
            op_ns[group(txt)] += e - s
            ops.append((txt, (e - s) / 1e9 / n_dev))
        busy = _union((s, e) for s, e, _ in clipped)
        busy_ns += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            # the benchmark's spans follow one another: charge each its
            # overlap with the gap, starting from the last to start before it
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            left = ge - gs
            while i < len(spans) and spans[i][0] < ge:
                s, e, name = spans[i]
                cut = min(e, ge) - max(s, gs)
                if cut > 0:
                    idle[name] += cut
                    left -= cut
                i += 1
            if left > 0:
                idle["none"] += left
    window_s = (t1 - t0) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_ns / 1e9 / n_dev,
        "op_s": {k: v / 1e9 / n_dev for k, v in op_ns.items()},
        "idle_by_host": {k: v / 1e9 / n_dev for k, v in idle.items()},
        "ops": ops,
        "devices": n_dev,
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The `breakdown` of a traced result line: the device op groups that
    took most time, and idle time by what the host was doing."""
    if not red:
        return {}
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_by_host"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
