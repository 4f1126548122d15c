"""One run of one cell: set up, measure for a fixed window, check, report.

Everything a cell is made of is found by name: its entry in BENCHMARK.json
names a configuration (`bench/configs/<config>.json`, with the plain
reference of its family in `bench/references/<family>.py`) and a traffic mix
(`bench/traffic/<traffic>.json`, read by `bench/loadgen.py`); the limits of
its correctness check are in `bench/cells/<cell>.json`; each metric is read
by `bench/metrics/<metric>.py` (a metric split by a suffix such as `.open`
shares the reader of its name without it). A new cell, mix, configuration
or metric is a new file.

The window drives the served entry of the program: `LampEngine.add_request`
and `LampEngine.step`, with the configuration's engine settings (the fused
step over the paged Pallas kernels). Token times are taken at step ends from
the engine's public surface (`scheduler.running`, `Sequence.num_generated`,
`RequestOutput.tokens`).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
DRAIN_S = 60.0          # how long the open loop waits, past the window's
                        # close, for the first token of a request due in it
SAMPLE_TOKENS = 512     # served tokens the correctness sample aims for
SAMPLE_MAX = 16         # requests in the correctness sample, at most


class BenchError(RuntimeError):
    """A run that cannot be measured: it prints no result."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: Optional[dict]
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bj = _json(root / "BENCHMARK.json")
    work = [w for w in bj["workloads"] if w["name"] == name]
    if not work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[0]
    conf = [c for c in bj["configs"] if c["name"] == w["config"]][0]
    cell_file = root / "bench" / "cells" / f"{name}.json"
    limits = _json(cell_file).get("limits") if cell_file.exists() else None

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bj["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bj["per_layer"]
                 if ("workloads" in m and name in m["workloads"])
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return Cell(name=name, config=_json(root / conf["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=limits, chips=int(w["chips"]), end_to_end=e2e,
                per_layer=per_layer)


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: dict):
    return _module(BENCH / "references" / f"{config['family']}.py")


def check_device(cell: Cell, allow_cpu: bool = False):
    """The devices and their peaks; refuses anything not in peaks.json."""
    import jax
    devs = jax.devices()
    peaks = _json(BENCH / "peaks.json")
    kind = devs[0].device_kind
    if not allow_cpu:
        if devs[0].platform == "cpu" or kind not in peaks:
            raise BenchError(f"device {devs[0].platform}/{kind!r} is not in "
                             f"bench/peaks.json; this benchmark runs only on "
                             f"a listed accelerator")
        if len(devs) < cell.chips:
            raise BenchError(f"cell {cell.name} needs {cell.chips} chips, "
                             f"JAX found {len(devs)}")
    return devs, peaks.get(kind)


def program_config(config: dict):
    """The program's ModelConfig, at the sizes of the configuration file."""
    from repro.configs import get_config
    from repro.core.policy import LampPolicy
    m, p = config["model"], config["program"]
    d = int(m["n_embd"])
    lamp = config["lamp"]
    tiers = {"deployment": LampPolicy.deployment,
             "paper": LampPolicy.paper_default}
    return get_config(p["arch"]).replace(
        n_layers=int(m["n_layer"]), d_model=d, n_heads=int(m["n_head"]),
        n_kv_heads=int(m["n_head"]), head_dim=None,
        d_ff=int(m.get("n_inner") or 4 * d), vocab=int(m["vocab_size"]),
        max_seq=int(m["n_positions"]), dtype=p["dtype"],
        lamp=tiers[lamp["tier"]](mu=int(lamp["mu"]), tau=float(lamp["tau"])))


def _bucket(n: int, cap: int) -> int:
    """The engine's bucket: the next power of two, capped (cap 0: none)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap) if cap else b


def warm_shapes(config: dict, mix: dict):
    """(rows, width) buckets of the fused step that this cell's window can
    reach; every one costs set-up (about 3 s to load from the persistent
    cache on a v5e).

    A closed loop keeps `concurrency` rows running, so one row bucket; its
    completions can bring several prompts into one step, and the token
    budget can cut one anywhere, so every power-of-two width up to the
    longest window. An open loop states in its mix the fewest and most rows
    its window reaches (`warm_rows`); its prompts arrive one or two to a
    step and are never cut, so its widths are 1 (decode) and the buckets of
    its prompt lengths."""
    e = config["engine"]
    cap = e["max_prefill_batch"] + e["max_decode_batch"]
    top = min(loadgen.max_prompt(mix), e["max_prefill_tokens"])
    c = loadgen.concurrency(mix)
    if c is not None:
        rows = [_bucket(c, cap)]
        widths = {_bucket(w, 0) for w in range(1, top + 1)}
    else:
        lo, hi = mix["warm_rows"]
        rows = sorted({_bucket(n, cap) for n in range(lo, hi + 1)})
        widths = {1} | {_bucket(w, 0)
                        for w in range(loadgen.min_prompt(mix), top + 1)}
    return [(r, w) for r in rows for w in sorted(widths)]


def warm(engine, shapes, vocab: int) -> None:
    """Compile (or load from the persistent cache) the fused step at each
    (rows, width) shape through the engine's public entry. For each row
    bucket R, R - 1 holder requests with one-token prompts are admitted,
    `max_prefill_batch` a step, and decode while probe requests prefill
    beside them, one a step, with prompts whose bucket is each width W and
    one output token; the holders' lengths make them end with the last
    probe. The tokens are drawn from a fixed seed, so set-up is the same
    work in every run."""
    from repro.serving import SamplingParams
    rng = np.random.default_rng(0)
    per_step = engine.econfig.max_prefill_batch

    def add(n_prompt: int, n_new: int) -> None:
        engine.add_request(rng.integers(0, vocab, size=n_prompt).tolist(),
                           SamplingParams(max_new_tokens=n_new,
                                          temperature=0.0))

    widths_of: Dict[int, List[int]] = {}
    for R, W in shapes:
        widths_of.setdefault(R, []).append(W)
    for R, widths in sorted(widths_of.items()):
        admits = [min(per_step, R - 1 - i) for i in range(0, R - 1, per_step)]
        for j, n in enumerate(admits):
            for _ in range(n):
                add(1, len(admits) - j + len(widths))
            engine.step()
        for W in widths:
            add(W // 2 + 1, 1)      # its bucket is W; W + 1 may not fit
            engine.step()
        while engine.has_unfinished():
            engine.step()


class Recorder:
    """Token times per request, and (traced runs) the rows of every step."""

    def __init__(self, engine, rows: bool):
        self.engine = engine
        self.rows = rows
        self.count: Dict[int, int] = {}
        self.times: Dict[int, List[float]] = {}
        self.served: Dict[int, List[int]] = {}
        self.prompts: Dict[int, List[int]] = {}
        self.steps: List[tuple] = []      # (rows, logits rows) per step
        self.util: List[float] = []
        self.outputs = []
        self._before = None

    def before_step(self):
        if not self.rows:
            return
        sch = self.engine.scheduler
        self._before = [(s, s.cache_len, s.num_generated)
                        for s in list(sch.running) + list(sch.waiting)]

    def after_step(self, done, now: float, in_window: bool):
        sch = self.engine.scheduler
        for s in sch.running:
            self._see(s.req_id, s.generated, now)
        for o in done:
            self.outputs.append(o)
            self._see(o.req_id, o.tokens, now)
        if in_window:
            self.util.append(self.engine.pool.utilization)
            if self.rows and self._before is not None:
                rows, emitted = [], 0
                for s, c0, g0 in self._before:
                    if s.cache_len > c0:
                        rows.append((c0, s.cache_len - c0))
                    emitted += s.num_generated > g0
                self.steps.append((rows, emitted))
        self._before = None

    def _see(self, rid: int, tokens: List[int], now: float):
        n = len(tokens)
        k = self.count.get(rid, 0)
        if n > k:
            self.times.setdefault(rid, []).extend([now] * (n - k))
            self.count[rid] = n
            self.served[rid] = list(tokens)


def _counters(engine) -> dict:
    s = engine.stats()
    return {"generated": engine.generated_tokens,
            "mixed_steps": engine.mixed_steps,
            "steps": engine.total_steps,
            "selected": engine.agg_lamp_selected,
            "valid": engine.agg_lamp_valid,
            "compiles": len(engine.compile_events),
            "phase_us": {k: v["mean_us"] * v["count"]
                         for k, v in s["phase"].items()}}


def _delta(a: dict, b: dict) -> dict:
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            out[k] = {kk: vv - a[k].get(kk, 0) for kk, vv in v.items()}
        else:
            out[k] = v - a[k]
    return out


def _sample(rec: Recorder, seed: int) -> List[int]:
    """Requests whose served tokens the reference checks: the longest, then
    others in an order drawn from the seed, up to SAMPLE_TOKENS tokens or
    SAMPLE_MAX requests."""
    ids = sorted(r for r, t in rec.served.items() if t)
    if not ids:
        return []
    longest = max(ids, key=lambda r: (len(rec.served[r]), -r))
    rest = [r for r in np.random.default_rng(int(seed)).permutation(ids)
            if r != longest]
    pick, total = [longest], len(rec.served[longest])
    for r in rest:
        if total >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        pick.append(int(r))
        total += len(rec.served[r])
    return pick


def check_tokens(config: dict, seed: int, prompts: Dict[int, List[int]],
                 served: Dict[int, List[int]], ids: List[int],
                 control: str = "") -> dict:
    """Run the plain reference over each sampled prompt and its served
    tokens; return the gaps by which the served tokens' logits lie below
    the reference's best (and, with `control` naming a precision, the gaps
    of the tokens the reference computed in it puts first)."""
    import jax.numpy as jnp
    ref = reference(config)
    params = ref.make_params(config["model"], seed)
    T = int(config["engine"]["max_model_len"])
    H = int(config["model"]["n_head"])
    got, ctl = [], []
    for rid in ids:
        p, s = prompts[rid], served[rid]
        full = np.zeros((T,), np.int32)
        full[:len(p) + len(s)] = p + s
        out = ref.gaps(params, jnp.asarray(full), n_head=H, low=control)
        lo, hi = len(p) - 1, len(p) + len(s) - 1
        if control:
            got.append(np.asarray(out[0])[lo:hi])
            ctl.append(np.asarray(out[1])[lo:hi])
        else:
            got.append(np.asarray(out)[lo:hi])
    del params
    res = {"positions": int(sum(map(len, got)))}
    for name, arr in (("served", got), ("control", ctl)):
        if arr:
            a = np.concatenate(arr).astype(np.float64)
            res[name] = {"max_gap": float(a.max()), "mean_gap": float(a.mean()),
                         "flip_share": float((a > 0).mean())}
    return res


def judge(readings: dict, limits: Optional[dict]) -> (bool, dict):
    checks = {}
    ok = bool(limits)
    for name, lim in (limits or {}).items():
        v = readings.get(name)
        checks[name] = {"value": v, "limit": lim}
        ok = ok and v is not None and math.isfinite(v) and v <= lim
    return ok, checks


def metric_path(name: str) -> Path:
    """The reader of metric `name`: bench/metrics/<name>.py or, for a name
    whose last suffix (`.open`, `.closed`) splits one quantity by the
    end-to-end metric it moves, the reader of the name before it."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_metric(name: str):
    return _module(metric_path(name))


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, allow_cpu: bool = False,
        dump: Optional[str] = None, control: str = "",
        details: bool = False) -> dict:
    """One run; returns the result object the command prints."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    devs, peak = check_device(cell, allow_cpu)
    from repro.launch.compile_cache import init_compile_cache
    cache_dir = init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.serving import EngineConfig, LampEngine, SamplingParams

    config, mix = cell.config, cell.traffic
    cfg = program_config(config)
    econf = EngineConfig(**config["engine"])
    log(f"cell {cell.name} seed {seed} on {devs[0].platform} "
        f"{devs[0].device_kind!r} x{len(devs)}, cache {cache_dir}")
    ref = reference(config)
    params = ref.make_params(config["model"], seed)
    engine = LampEngine(cfg, params, econf)
    del params
    shapes = warm_shapes(config, mix)
    warm(engine, shapes, int(config["model"]["vocab_size"]))
    reqs = loadgen.requests(mix, seed, int(config["model"]["vocab_size"]))
    rec = Recorder(engine, rows=trace)
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    nxt = 0
    due_of: Dict[int, float] = {}
    late: List[float] = []

    def submit(r, t_due):
        rid = engine.add_request(
            r.prompt, SamplingParams(max_new_tokens=r.max_new,
                                     temperature=0.0,
                                     seed=len(due_of) % 2 ** 31))
        due_of[rid] = t_due
        rec.prompts[rid] = r.prompt
        return rid

    conc = loadgen.concurrency(mix)
    preroll = float(mix.get("preroll_s", 0.0))
    if conc is not None:
        # a closed loop's first sequences prefill during set-up: the window
        # measures decode at their context, as a running server would
        for r in reqs[:conc]:
            submit(r, clock())
        nxt = conc
        while engine.has_unfinished() and not all(
                rec.served.get(rid) for rid in due_of):
            rec.after_step(engine.step(), clock(), False)
    elif reqs[-1].due < preroll + seconds:
        raise BenchError(f"traffic mix has too few requests for a "
                         f"{preroll}s pre-roll and a {seconds}s window")
    window_ids = set()

    def drive(ts, t_stop, tag, in_window):
        """Offer the mix until t_stop; `ts` is when request 0 was due. Host
        spans are named `tag`.admit/.step/.idle; only "bench.*" ones mark the
        traced window."""
        nonlocal nxt
        walls = []
        while True:
            now = clock()
            if now >= t_stop:
                return walls
            with annotate(f"{tag}.admit"):
                while (conc is None and nxt < len(reqs)
                       and ts + reqs[nxt].due <= now):
                    rid = submit(reqs[nxt], ts + reqs[nxt].due)
                    if in_window:
                        late.append(clock() - due_of[rid])
                        window_ids.add(rid)
                    nxt += 1
            if engine.has_unfinished():
                rec.before_step()
                t1 = clock()
                with annotate(f"{tag}.step"):
                    done = engine.step()
                now = clock()
                walls.append(now - t1)
                rec.after_step(done, now, in_window)
                if conc is not None:
                    with annotate(f"{tag}.admit"):
                        for _ in done:
                            if nxt < len(reqs):
                                rid = submit(reqs[nxt], now)
                                window_ids.add(rid)
                                nxt += 1
            else:
                wake = min(ts + reqs[nxt].due if nxt < len(reqs) else t_stop,
                           t_stop)
                with annotate(f"{tag}.idle"):
                    time.sleep(max(0.0, wake - clock()))

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    ts = clock()
    if preroll:
        # an open loop runs its mix for `preroll_s` before the window opens,
        # so the window sees the queue and batch of a server in steady state
        drive(ts, ts + preroll, "preroll", False)
    c0 = _counters(engine)
    live0 = len(engine.scheduler.running) + len(engine.scheduler.waiting)
    t0 = clock()
    setup_s = t0 - t_start
    step_walls = drive(ts, t0 + seconds, "bench", True)
    t_end = clock()
    if trace:
        jax.profiler.stop_trace()
    c1 = _counters(engine)
    window = {"t0": t0, "t_end": t_end, "seconds": t_end - t0,
              "steps": len(step_walls), "live_at_open": live0,
              "live_at_close": (len(engine.scheduler.running)
                                + len(engine.scheduler.waiting)),
              "tokens": c1["generated"] - c0["generated"]}
    # the open loop's requests due in the window wait for a first token
    if conc is None:
        stop = t_end + DRAIN_S
        while (any(not rec.served.get(r) for r in window_ids)
               and clock() < stop and engine.has_unfinished()):
            rec.after_step(engine.step(), clock(), False)
    window["drain_end"] = clock()
    dev = devs[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    outputs = rec.outputs
    failed = sum(1 for o in outputs if o.error is not None)
    attempted = len(window_ids) + (conc or 0)
    d = _delta(c0, c1)
    log(f"window {window['seconds']:.3f}s, {len(step_walls)} steps, "
        f"{d['generated']} tokens, compiles in window {d['compiles']}, "
        f"setup {setup_s:.3f}s")
    if d["compiles"]:
        log(f"WARNING: {d['compiles']} compiles inside the window, buckets "
            f"warm_shapes did not foresee: "
            f"{list(engine.compile_events)[-d['compiles']:]}")
    red = {}
    if trace:
        from bench import trace_reduce
        xp = trace_reduce.find_xplane(tdir)
        red = trace_reduce.reduce(trace_reduce.load(xp))
        if dump:
            os.makedirs(dump, exist_ok=True)
            shutil.copy(xp, Path(dump) / f"{cell.name}-{seed}.xplane.pb")
        shutil.rmtree(tdir, ignore_errors=True)
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(Path(dump) / f"{cell.name}-{seed}-steps.json", "w") as f:
            json.dump({"step_walls": step_walls, "shapes": shapes,
                       "compile_events": [dict(k=str(c.get("kind")),
                                               shape=str(c.get("shape")),
                                               wall=c.get("wall_s"))
                                          for c in engine.compile_events],
                       "top_ops": sorted(red.get("op_s", {}).items(),
                                         key=lambda kv: -kv[1])[:40],
                       "op_texts": sorted({t[:400] for t, _ in
                                           red.get("ops", [])})[:300],
                       "red": {k: v for k, v in red.items()
                               if k not in ("ops", "op_s")}},
                      f, indent=1)
    ctx = SimpleNamespace(
        cell=cell, config=config, model=config["model"], peak=peak,
        setup_s=setup_s, window=window, delta=d, rec=rec, due=due_of,
        window_ids=window_ids, late=late, red=red)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_metric(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    # the program's state goes before the reference runs
    ids = _sample(rec, seed)
    prompts = {r: rec.prompts[r] for r in ids}
    served = {r: rec.served[r] for r in ids}
    del engine, rec, ctx
    gc.collect()
    t_ref = clock()
    res = check_tokens(config, seed, prompts, served, ids, control=control)
    log(f"reference over {len(ids)} requests, {res['positions']} served "
        f"tokens in {clock() - t_ref:.3f}s")
    readings = res.get("served", {})
    ok, checks = judge(readings, cell.limits)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    if trace and red:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    out = {"correct": bool(ok and failed == 0), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace and red:
        from bench import trace_reduce
        out["breakdown"] = trace_reduce.breakdown(red)
    out["compiles_in_window"] = d["compiles"]
    if control or details:
        out["readings"] = res
        out["window"] = {k: v for k, v in window.items()
                         if k not in ("t0", "t_end", "drain_end")}
    out["checks"] = checks
    return out
