"""Per-layer metrics of a traced run.

Each per-layer metric of ``BENCHMARK.json`` has a reader of its own,
``bench/metrics/<name>.py``, with ``read(ctx) -> float | None``: ``ctx``
(below) holds the reduced trace, the benchmark's own spans and the rank
calls' logical shapes.  A reader that finds nothing to read returns None,
and the metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

from harness import roofline, trace as trace_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def is_rank_kernel(name: str, meta: str) -> bool:
    """The ranking kernels (``reid_topk_*``) wherever the profiler names
    them: the op's own name or its descriptive stats."""
    return "reid" in name.lower() or "reid" in meta.lower()


@dataclasses.dataclass
class Context:
    chips: int
    ticks: int                 # ticks served in the traced window
    window_s: float            # traced window, host clock
    devices: dict              # chip id -> trace.DeviceReading
    ingest_s: list             # seconds per ingest call in the window
    rank_calls: list           # (ops, bytes) of every ranking call
    peak: dict | None

    @property
    def busiest(self):
        return max(self.devices.values(), key=lambda d: d.busy_ns)


def rank_calls(cfg: dict, w: dict, rounds: list) -> list:
    """(operations, bytes) of each ranking call of the traced window, from
    the logical shapes of its round: Q queries, and G gallery rows, the
    detections of every (camera, frame) some query admitted that the frame
    store still holds.  A round with no such row makes no ranking call."""
    s = cfg["serve"]
    T = int(s.get("tile_grid", 0))
    gal, t0, R = w["gal"], w["t0"], int(s["retention"])
    C = gal.shape[0]
    ndet = (gal >= 0).sum(-1)                                  # (C, H)
    steps = np.where(ndet > 0, np.arange(ndet.shape[1]), -1)
    steps[:, :t0] = -1
    latest = np.maximum.accumulate(steps, axis=1)
    D = w["feats"].shape[1]
    width = C * T * T if T else C
    out = []
    for tick, recs in rounds:
        keys = set()
        for f, mask in recs:
            for c in np.flatnonzero(mask):
                keys.add((int(c), f))
        G = sum(int(ndet[c, f]) for c, f in keys
                if f >= t0 and f >= latest[c, tick] - R)
        if G:
            out.append(roofline.rank_call(len(recs), G, D, width,
                                          int(s["topk"]), gallery_tags=2))
    return out


def per_layer(cell, cfg, w, window, rec, logdir, peak, out):
    from harness.drive import load_benchmark

    tr = trace_mod.load(logdir)
    lo, hi = trace_mod.span(tr.host, trace_mod.WINDOW)
    used = sorted(tr.devices)[:cell.chips]
    devices = {i: trace_mod.read_device(tr.devices[i], lo, hi,
                                        is_rank_kernel) for i in used}
    ctx = Context(chips=cell.chips, ticks=len(window.lat),
                  window_s=(hi - lo) / 1e9, devices=devices,
                  ingest_s=window.ingest,
                  rank_calls=rank_calls(cfg, w, rec.rounds), peak=peak)
    for i, d in devices.items():
        print(f"trace chip {i}: busy {d.busy_ns / 1e9!r} s of "
              f"{ctx.window_s!r} s, {d.launches} program executions, "
              f"rank kernel {d.kernel_ns / 1e9!r} s in {d.kernel_calls} "
              f"calls", file=out)
    calls = ctx.rank_calls
    if calls and peak:
        t_ops = sum(o for o, _ in calls) / peak["flops_per_s"]
        t_b = sum(b for _, b in calls) / peak["bytes_per_s"]
        print(f"rank calls: {len(calls)} from the rounds' shapes; "
              f"{sum(o for o, _ in calls)} operations, "
              f"{sum(b for _, b in calls)} bytes; least time set by "
              f"{'operations' if t_ops >= t_b else 'bytes'}", file=out)
    bench = load_benchmark()
    layer = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        mod = load_file(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                        f"bench_metric_{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            layer[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if not used:                # no chip in the trace (a CPU rehearsal)
        return layer, dict(busy_s=0.0, window_s=ctx.window_s), None
    busiest = max(used, key=lambda i: devices[i].busy_ns)
    dev_extra = dict(
        busy_s=float(np.mean([d.busy_ns for d in devices.values()]) / 1e9),
        window_s=ctx.window_s)
    top_ops = sorted(devices[busiest].op_ns.items(), key=lambda kv: -kv[1])
    breakdown = dict(
        device_ops=[[n, ns / 1e9] for n, ns in top_ops[:10]],
        idle_gaps=trace_mod.attribute_gaps(tr.devices[busiest], tr.host,
                                           lo, hi))
    return layer, dev_extra, breakdown
