"""The reduction from a profiler trace to device metrics, on a hand-built
trace whose answers are counted by hand."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts the benchmark on the path)

from harness import layers, trace  # noqa: E402

MS = 1_000_000


def _trace():
    # one chip, a 100 ms window [0, 100 ms): three programs, four ops
    # (two overlapping), two of them the rank kernel
    ops = [("fusion.1", 10 * MS, 5 * MS, ""),
           ("custom-call.2", 12 * MS, 6 * MS, "long_name=_reid_tiles_kernel"),
           ("reid_topk_segments", 40 * MS, 2 * MS, ""),
           ("copy.3", 95 * MS, 10 * MS, "")]          # runs past the window
    modules = [("jit_admit", 10 * MS, 8 * MS, ""),
               ("jit_rank", 40 * MS, 2 * MS, ""),
               ("jit_copy", 95 * MS, 10 * MS, "")]
    host = [("bench.window", 0, 100 * MS, ""),
            ("bench.tick", 5 * MS, 40 * MS, ""),
            ("bench.ingest", 0, 5 * MS, ""),
            ("PjitFunction(rank)", 20 * MS, 22 * MS, ""),
            ("bench.tick", 50 * MS, 45 * MS, "")]
    return trace.Trace({0: {"ops": ops, "modules": modules}}, host)


def test_union_and_gaps():
    iv = [(10, 5), (12, 6), (40, 2), (95, 10)]
    assert trace.union_ns(iv, 0, 100) == 8 + 2 + 5
    assert trace.gaps(iv, 0, 100) == [(0, 10), (18, 40), (42, 95)]
    assert trace.union_ns([], 0, 100) == 0
    assert trace.gaps([], 0, 100) == [(0, 100)]


def test_read_device_counts_kernel_by_name_or_stats():
    tr = _trace()
    lo, hi = trace.span(tr.host, trace.WINDOW)
    d = trace.read_device(tr.devices[0], lo, hi, layers.is_rank_kernel)
    assert d.busy_ns == 15 * MS
    assert d.launches == 3
    assert d.kernel_calls == 2 and d.kernel_ns == 8 * MS
    assert d.op_ns["copy.3"] == 10 * MS


def test_idle_gaps_go_to_the_innermost_host_span():
    tr = _trace()
    got = dict((n, s) for n, s in trace.attribute_gaps(tr.devices[0],
                                                       tr.host, 0, 100 * MS))
    # gap 0-10 ms (mid 5 ms: the second tick span starts there), gap
    # 18-40 ms (mid 29: inside PjitFunction), gap 42-95 ms (mid 68.5: the
    # second tick)
    assert got == {"bench.tick": (10 + 53) / 1e3,
                   "PjitFunction(rank)": 22 / 1e3}


def test_metric_readers_on_the_hand_trace():
    tr = _trace()
    lo, hi = trace.span(tr.host, trace.WINDOW)
    dev = {0: trace.read_device(tr.devices[0], lo, hi, layers.is_rank_kernel)}
    peak = dict(flops_per_s=1e12, bytes_per_s=1e9)
    ctx = layers.Context(chips=1, ticks=2, window_s=0.1,
                         devices=dev, ingest_s=[0.001, 0.003],
                         rank_calls=[(2_000_000, 1_000_000)], peak=peak)
    read = {}
    for name in ("ingest_ms", "launches_per_tick", "device_busy_ms_per_tick",
                 "rank_kernel_ms_per_tick", "rank_kernel_roofline",
                 "device_idle_share"):
        mod = layers.load_file(os.path.join(layers.BENCH, "metrics",
                                            f"{name}.py"), f"m_{name}")
        read[name] = mod.read(ctx)
    assert abs(read["ingest_ms"] - 2.0) < 1e-12
    assert read["launches_per_tick"] == 1.5
    assert abs(read["device_busy_ms_per_tick"] - 7.5) < 1e-12
    assert abs(read["rank_kernel_ms_per_tick"] - 4.0) < 1e-12
    # least time: max(2e6 / 1e12, 1e6 / 1e9) = 1 ms of 8 ms kernel time
    assert abs(read["rank_kernel_roofline"] - 12.5) < 1e-9
    assert abs(read["device_idle_share"] - 85.0) < 1e-9


def test_readers_return_nothing_without_a_kernel():
    empty = trace.DeviceReading(busy_ns=0, launches=0, kernel_ns=0,
                                kernel_calls=0, op_ns={})
    ctx = layers.Context(chips=1, ticks=1, window_s=1.0,
                         devices={0: empty}, ingest_s=[], rank_calls=[],
                         peak=dict(flops_per_s=1.0, bytes_per_s=1.0))
    for name in ("rank_kernel_ms_per_tick", "rank_kernel_roofline",
                 "ingest_ms"):
        mod = layers.load_file(os.path.join(layers.BENCH, "metrics",
                                            f"{name}.py"), f"m0_{name}")
        assert mod.read(ctx) is None
