"""The reduction from a trace to metrics: by hand on a made-up trace, and on
a small trace recorded on a TPU v5e (data/chip-small.xplane.pb)."""

from pathlib import Path

import pytest

from bench import kernels, trace_reduce
from bench.trace_reduce import Trace

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_by_hand():
    ms = 1_000_000
    host = [(0, 10 * ms, "bench.step"), (10 * ms, 12 * ms, "bench.admit"),
            (12 * ms, 20 * ms, "bench.step")]
    dev = [(0, 20 * ms, "%while.3"), (1 * ms, 4 * ms, "fusion.1"), (3 * ms, 5 * ms, "%paged_prefill_attention.7 custom-call"),
           (13 * ms, 19 * ms, "fusion.2"), (25 * ms, 30 * ms, "late.1")]
    red = trace_reduce.reduce(Trace(devices=[dev], host=host))
    assert red["window_s"] == pytest.approx(0.020)
    # a loop spans the window: all of it busy, but no op time of its own
    assert red["busy_s"] == pytest.approx(0.020)
    assert "%while" not in red["op_s"]
    busy = trace_reduce.reduce(Trace(devices=[dev[1:]], host=host))
    # without it: [1, 5] and [13, 19] ms; the op after the window is cut off
    assert busy["busy_s"] == pytest.approx(0.010)
    red = busy
    assert red["op_s"]["fusion"] == pytest.approx(0.009)
    # idle: [0,1], [5,10] in step, [10,12] admit, [12,13], [19,20] step
    assert red["idle_by_host"]["bench.step"] == pytest.approx(0.008)
    assert red["idle_by_host"]["bench.admit"] == pytest.approx(0.002)
    assert kernels.kernel_seconds(red) == pytest.approx(0.002)
    b = trace_reduce.breakdown(red)
    assert b["device_ops"][0] == ["fusion", pytest.approx(0.009)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_two_devices_average():
    host = [(0, 100, "bench.step")]
    red = trace_reduce.reduce(Trace(devices=[[(0, 50, "a")], [(0, 100, "a")]],
                                    host=host))
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["devices"] == 2


def test_empty_trace_reads_nothing():
    assert trace_reduce.reduce(Trace(devices=[], host=[])) == {}


def test_recorded_chip_trace(tmp_path):
    """A 2 s window of small-long-decode, traced on one TPU v5e (4 steps of
    the (64, 1) bucket). The numbers are what the reduction read from it
    when it was recorded; by hand, the busy and idle time add up to the
    window and the two LAMP kernels are most of the busy time."""
    import gzip
    path = tmp_path / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "chip-small.xplane.pb.gz").read_bytes()))
    red = trace_reduce.reduce(trace_reduce.load(str(path)))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(2.172734377, abs=1e-9)
    assert red["busy_s"] == pytest.approx(2.131322436, abs=1e-9)
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    assert set(red["idle_by_host"]) <= {"bench.step", "bench.admit",
                                        "bench.idle", "none"}
    k = kernels.kernel_seconds(red)
    assert k == pytest.approx(1.582664199, abs=1e-9)
    assert k == pytest.approx(red["op_s"]["%paged_prefill_attention"])
    assert 0.5 < k / red["busy_s"] < 1.0
    b = trace_reduce.breakdown(red)
    assert b["device_ops"][0][0] == "%paged_prefill_attention"
    assert b["device_ops"][1][0] == "%copy"
