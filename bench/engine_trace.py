"""The program's own marks on a profiler trace: engine phases and the two
LAMP kernel passes.

The engine annotates every phase of a step as `engine.<phase>`
(`repro.obs`), nested: `engine.step` holds schedule, build, the launch span
(`engine.mixed` with `inputs` and `launch`), `sync` (`wait`, `readback`),
account, emit and policy. Both Pallas calls of the paged attention carry
their pass in the op's text (`"lamp_pass":"lookahead"` or `"recompute"`).

* `load_spans(path)`: the `engine.*` host events of a `.xplane.pb`, with
  their args, as (start_ns, end_ns, name, args);
* `idle_by_phase(trace, spans)`: the device's idle seconds over the traced
  window of `bench/trace_reduce.py` (first to last "bench.*" span), each
  idle interval charged to the innermost engine span open over it, and to
  "outside" where none is;
* `pass_seconds(red)`: device seconds of each LAMP pass among the window's
  kernel ops of a `trace_reduce.reduce` result;
* `mixed_sums(spans, t0, t1)`: the args of the `engine.mixed` spans that
  start in the window, summed (kernel grid steps live / walked, query
  tokens / slots).

    python3 bench/engine_trace.py <trace.xplane.pb>

prints all of it as one JSON object; `bench/run.py --trace 1 --dump DIR`
keeps the trace of a run.
"""

from __future__ import annotations

import bisect
import collections
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Tuple

if __name__ == "__main__":
    _HERE = Path(__file__).resolve().parent
    sys.path[:] = [str(_HERE.parent), str(_HERE.parent / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != _HERE]

from bench import kernels, trace_reduce  # noqa: E402

ENGINE_PREFIX = "engine."
OUTSIDE = "outside"
# the pass tag in an op's text; tolerate the escaped quotes of a JSON dump
PASS = re.compile(r'lamp_pass\\?"\s*:\s*\\?"(lookahead|recompute)')

Span = Tuple[int, int, str, dict]


def load_spans(path: str) -> List[Span]:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ENGINE_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns), ev.name,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda x: (x[0], -x[1]))


def _innermost(spans: List[Span], t0: int, t1: int):
    """[t0, t1] cut into (start, end, name) segments, each named after the
    innermost span open over it. Spans come from one host thread, so they
    nest; a child that outlives its parent is cut at the parent's end."""
    segs = []

    def add(a, b, name):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            segs.append((a, b, name))

    stack: List[Tuple[int, str]] = []      # (end, name), innermost last
    cur = t0
    for s, e, name, _ in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            add(cur, end, top)
            cur = max(cur, end)
        add(cur, s, stack[-1][1] if stack else OUTSIDE)
        cur = max(cur, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, top = stack.pop()
        add(cur, end, top)
        cur = max(cur, end)
    add(cur, t1, OUTSIDE)
    return segs


def window(trace: trace_reduce.Trace) -> Tuple[int, int]:
    """The traced window as `trace_reduce.reduce` takes it."""
    return (min(s for s, _, _ in trace.host), max(e for _, e, _ in trace.host))


def idle_by_phase(trace: trace_reduce.Trace,
                  spans: List[Span]) -> Dict[str, float]:
    if not trace.devices or not trace.host:
        return {}
    t0, t1 = window(trace)
    segs = _innermost(spans, t0, t1)
    seg_starts = [a for a, _, _ in segs]
    idle = collections.Counter()
    for evs in trace.devices:
        busy = trace_reduce._union(
            (max(s, t0), min(e, t1)) for s, e, _ in evs if e > t0 and s < t1)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            i = max(bisect.bisect_right(seg_starts, gs) - 1, 0)
            while i < len(segs) and segs[i][0] < ge:
                a, b, name = segs[i]
                cut = min(b, ge) - max(a, gs)
                if cut > 0:
                    idle[name] += cut
                i += 1
    n = len(trace.devices)
    return {k: v / 1e9 / n for k, v in idle.items()}


def engine_idle(phases: Dict[str, float]) -> float:
    """Idle seconds inside `engine.step` and its children."""
    return sum(v for k, v in phases.items() if k.startswith(ENGINE_PREFIX))


def pass_seconds(red: Dict) -> Dict[str, float]:
    """Device seconds of the LAMP kernel ops (`bench/kernels.py`) by their
    pass tag. XLA copies the tag onto ops it derives from a kernel's
    outputs (a reduce of its counts), which are not the pass's time."""
    out = {"lookahead": 0.0, "recompute": 0.0}
    for text, s in red.get("ops", ()):
        m = PASS.search(text)
        if m and kernels.LAMP_KERNEL.match(text.split(" ", 1)[0]):
            out[m.group(1)] += s
    return out


def _bucket_slots(bucket) -> int:
    rows, width = json.loads(bucket) if isinstance(bucket, str) else bucket
    return int(rows) * int(width)


def mixed_sums(spans: List[Span], t0: int, t1: int) -> Dict[str, int]:
    out = collections.Counter()
    for s, _, name, args in spans:
        if name == ENGINE_PREFIX + "mixed" and t0 <= s < t1:
            out["spans"] += 1
            for k in ("kv_live", "kv_walked", "tokens"):
                out[k] += int(args.get(k, 0))
            out["slots"] += _bucket_slots(args["bucket"])
    return dict(out)


def summary(path: str) -> Dict:
    trace = trace_reduce.load(path)
    spans = load_spans(path)
    red = trace_reduce.reduce(trace)
    if not red:
        return {}
    t0, t1 = window(trace)
    phases = idle_by_phase(trace, spans)
    steps = sum(1 for s, _, n, _ in spans
                if n == ENGINE_PREFIX + "step" and t0 <= s < t1)
    passes = pass_seconds(red)
    sums = mixed_sums(spans, t0, t1)
    both = sum(passes.values())
    return {
        "window_s": red["window_s"], "busy_s": red["busy_s"], "steps": steps,
        "idle_by_host": red["idle_by_host"], "idle_by_phase": phases,
        "engine_gap_ms": (engine_idle(phases) / steps * 1e3
                          if steps else None),
        "pass_s": passes,
        "lamp_lookahead_share": 100 * passes["lookahead"] / both
        if both else None,
        "mixed": sums,
        "kv_grid_live_share": (100 * sums["kv_live"] / sums["kv_walked"]
                               if sums.get("kv_walked") else None),
        "query_fill": (100 * sums["tokens"] / sums["slots"]
                       if sums.get("slots") else None),
    }


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
