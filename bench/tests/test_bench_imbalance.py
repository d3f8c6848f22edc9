"""``chip_busy_imbalance`` on a hand-built two-chip trace: the busiest
chip's busy time over the mean of the cell's chips."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts the benchmark on the path)

from harness import layers, trace  # noqa: E402

MS = 1_000_000


def _read(devices):
    ctx = layers.Context(chips=len(devices), ticks=2, window_s=0.1,
                         devices=devices, ingest_s=[], rank_calls=[],
                         peak=None)
    mod = layers.load_file(os.path.join(layers.BENCH, "metrics",
                                        "chip_busy_imbalance.py"),
                           "m_chip_busy_imbalance")
    return mod.read(ctx)


def test_imbalance_of_a_two_chip_trace():
    # a 100 ms window: chip 0 busy 10 + 20 ms (two ops overlapping by
    # 5 ms, so 25 ms), chip 1 busy 15 ms (one op runs past the window)
    tr = trace.Trace(
        {0: {"ops": [("fusion.1", 10 * MS, 10 * MS, ""),
                     ("reid_topk_tiles", 15 * MS, 20 * MS, "")],
             "modules": [("jit_rank", 10 * MS, 25 * MS, "")]},
         1: {"ops": [("fusion.1", 10 * MS, 5 * MS, ""),
                     ("copy.2", 90 * MS, 30 * MS, "")],
             "modules": [("jit_rank", 10 * MS, 5 * MS, "")]}},
        [("bench.window", 0, 100 * MS, "")])
    lo, hi = trace.span(tr.host, trace.WINDOW)
    dev = {i: trace.read_device(tr.devices[i], lo, hi, layers.is_rank_kernel)
           for i in tr.devices}
    assert [dev[i].busy_ns for i in (0, 1)] == [25 * MS, 15 * MS]
    # 25 over the mean of 25 and 15
    assert abs(_read(dev) - 25 / 20) < 1e-12


def test_imbalance_reads_nothing_without_a_busy_chip():
    idle = trace.DeviceReading(busy_ns=0, launches=0, kernel_ns=0,
                               kernel_calls=0, op_ns={})
    assert _read({}) is None
    assert _read({0: idle, 1: idle}) is None
