"""The program's marks on a profiler trace (bench/engine_trace.py): idle time
charged to engine phases by hand, the LAMP pass tags, the readers of the
metrics that read them, and a small trace recorded on a TPU v5e
(data/chip-small-phases.xplane.pb.gz)."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import engine_trace, harness, kernels, trace_reduce
from bench.trace_reduce import Trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _hand_trace():
    host = [(0, 10 * MS, "bench.step"), (10 * MS, 12 * MS, "bench.admit"),
            (12 * MS, 20 * MS, "bench.step")]
    dev = [(3 * MS + MS // 2, 5 * MS + MS // 2, "fusion.1"),
           (13 * MS, 18 * MS, "fusion.2")]
    spans = [
        (1 * MS, 9 * MS + MS // 2, "engine.step", {"step": 0}),
        (1 * MS, 2 * MS, "engine.schedule", {}),
        (2 * MS, 4 * MS, "engine.mixed",
         {"rows": 3, "bucket": "[4, 2]", "tokens": 4, "kv_live": 10,
          "kv_walked": 32}),
        (2 * MS, 3 * MS, "engine.inputs", {}),
        (3 * MS, 4 * MS, "engine.launch", {}),
        (5 * MS, 9 * MS, "engine.sync", {}),
        (5 * MS, 8 * MS, "engine.wait", {}),
        (8 * MS, 9 * MS, "engine.readback", {}),
        (12 * MS + MS // 2, 19 * MS + MS // 2, "engine.step", {"step": 1}),
        (13 * MS, 14 * MS, "engine.mixed",
         {"rows": 1, "bucket": [1, 1], "tokens": 1, "kv_live": 3,
          "kv_walked": 8}),
        (18 * MS + MS // 2, 19 * MS + MS // 2, "engine.account", {}),
        # a span before the window opens is not the window's
        (-5 * MS, -4 * MS, "engine.mixed",
         {"rows": 1, "bucket": [1, 1], "tokens": 1, "kv_live": 1,
          "kv_walked": 8}),
    ]
    return Trace(devices=[dev], host=host), spans


def test_idle_by_phase_by_hand():
    trace, spans = _hand_trace()
    phases = engine_trace.idle_by_phase(trace, spans)
    # idle [0, 3.5], [5.5, 13], [18, 20] ms, each piece charged to the
    # innermost span open over it
    want = {"outside": 1 + 3 + 0.5, "engine.schedule": 1,
            "engine.inputs": 1, "engine.launch": 0.5, "engine.wait": 2.5,
            "engine.readback": 1, "engine.step": 0.5 + 0.5 + 0.5,
            "engine.account": 1}
    assert phases == pytest.approx({k: v / 1e3 for k, v in want.items()})
    red = trace_reduce.reduce(trace)
    assert sum(phases.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert engine_trace.engine_idle(phases) == pytest.approx(0.0085)
    # bench.step holds [0, 10] and [12, 20]: 11 ms idle, of which the
    # engine's spans cover all but [0, 1], [9.5, 10], [12, 12.5], [19.5, 20]
    assert red["idle_by_host"]["bench.step"] == pytest.approx(0.011)


def test_idle_by_phase_without_engine_spans():
    trace, _ = _hand_trace()
    phases = engine_trace.idle_by_phase(trace, [])
    assert set(phases) == {"outside"}
    assert engine_trace.engine_idle(phases) == 0
    assert engine_trace.idle_by_phase(Trace(devices=[], host=[]), []) == {}


def test_mixed_sums_in_window():
    trace, spans = _hand_trace()
    t0, t1 = engine_trace.window(trace)
    assert engine_trace.mixed_sums(spans, t0, t1) == {
        "spans": 2, "kv_live": 13, "kv_walked": 40, "tokens": 5,
        "slots": 9}


def _op(name, pass_=None):
    meta = "{}" if pass_ is None else '{\n"lamp_pass":"%s"\n}' % pass_
    return (f"%{name} = (f32[8,1,1]) custom-call(), custom_call_target="
            f"\"tpu_custom_call\", frontend_attributes={{kernel_metadata="
            f"{meta}}}")


def test_pass_seconds_and_reader():
    from bench.metrics import lamp_lookahead_share
    red = {"ops": [(_op("paged_prefill_attention.6", "lookahead"), 0.3),
                   (_op("paged_prefill_attention.7", "recompute"), 0.5),
                   (_op("paged_prefill_attention.6", "lookahead"), 0.1),
                   (_op("fusion.3"), 0.7),
                   # XLA copies the tag onto ops made from a kernel's output
                   (_op("reduce.68", "recompute"), 0.2)]}
    assert engine_trace.pass_seconds(red) == pytest.approx(
        {"lookahead": 0.4, "recompute": 0.5})
    assert kernels.kernel_seconds(red) == pytest.approx(0.9)
    ctx = SimpleNamespace(red=red)
    assert lamp_lookahead_share.read(ctx) == pytest.approx(100 * 0.4 / 0.9)
    # an untagged program (or no trace) reads nothing
    untagged = {"ops": [(_op("paged_prefill_attention.6"), 0.3)]}
    assert lamp_lookahead_share.read(SimpleNamespace(red=untagged)) is None
    assert lamp_lookahead_share.read(SimpleNamespace(red={})) is None


def test_kv_grid_live_share_reader():
    from bench.metrics import kv_grid_live_share
    cell = harness.load_cell("small-long-decode")
    # one decode step of two rows at 100 and 1000 tokens (bucket 2 of the
    # 64-row cap, 64 blocks a row), then a step with nothing advanced
    steps = [([(99, 1), (999, 1)], 2), ([], 0)]
    ctx = SimpleNamespace(config=cell.config, rec=SimpleNamespace(steps=steps))
    live = (99 // 16 + 1) + (999 // 16 + 1)
    assert kv_grid_live_share.read(ctx) == pytest.approx(
        100 * live / (2 * 64))
    empty = SimpleNamespace(config=cell.config, rec=SimpleNamespace(steps=[]))
    assert kv_grid_live_share.read(empty) is None


def test_recorded_chip_trace_phases(tmp_path):
    """A 2 s window of small-long-decode traced on one TPU v5e with the
    engine's `engine.*` spans and the kernels' pass tags (4 steps of the
    (64, 1) bucket). The engine's spans account for the device's idle time
    in the benchmark's step span, `engine.step` itself holds little of it,
    both LAMP passes are found and add up to the kernel time the benchmark
    reads, and every fused launch span carries its grid and token counts.
    The numbers are what the reduction read when it was recorded."""
    path = tmp_path / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "chip-small-phases.xplane.pb.gz").read_bytes()))
    trace = trace_reduce.load(str(path))
    spans = engine_trace.load_spans(str(path))
    red = trace_reduce.reduce(trace)
    phases = engine_trace.idle_by_phase(trace, spans)
    assert sum(phases.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    gap = engine_trace.engine_idle(phases)
    assert gap == pytest.approx(red["idle_by_host"]["bench.step"], rel=0.01)
    assert gap == pytest.approx(0.039897224, abs=1e-9)
    assert phases["engine.step"] < 0.1 * gap
    for phase in ("inputs", "wait", "readback", "account"):
        assert phases[f"engine.{phase}"] > 0.1 * gap
    passes = engine_trace.pass_seconds(red)
    assert passes["lookahead"] + passes["recompute"] == pytest.approx(
        kernels.kernel_seconds(red), abs=1e-9)
    assert passes["lookahead"] == pytest.approx(0.701745233, abs=1e-9)
    mixed = [a for _, _, n, a in spans if n == "engine.mixed"]
    assert mixed and all({"kv_live", "kv_walked", "tokens"} <= set(a)
                         for a in mixed)
    s = engine_trace.summary(str(path))
    assert s["steps"] == 4
    assert s["engine_gap_ms"] == pytest.approx(gap / 4 * 1e3)
    assert s["mixed"] == {"spans": 4, "kv_live": 8958, "kv_walked": 16384,
                          "tokens": 256, "slots": 256}
