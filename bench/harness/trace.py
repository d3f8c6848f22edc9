"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Planes named ``/device:TPU:<n>`` are the chips.  On each, the ``XLA Ops``
line holds one event per device operation and the ``XLA Modules`` line one
per program execution.  The host plane holds the benchmark's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``) and JAX's
dispatch spans on the same clock, which is what idle gaps are attributed
to.

Everything here works on plain tuples so that the tests can feed it a
hand-built trace: ``Trace(devices={id: {"ops": [...], "modules": [...]}},
host=[...])`` with events ``(name, start_ns, duration_ns, meta)``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    devices: dict            # id -> {"ops": [ev], "modules": [ev]}
    host: list               # [ev] of the thread that ran the loop


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            dev = {}
            for key, lname in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                line = lines.get(lname)
                dev[key] = [] if line is None else [
                    (e.name, int(e.start_ns), int(e.duration_ns),
                     _meta(e)) for e in line.events]
            devices[int(plane.name.rsplit(":", 1)[1])] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns), "")
                       for e in line.events]
                # the thread that ran the benchmark's loop
                if any(n == WINDOW for n, _, _, _ in evs):
                    host = evs
    return Trace(devices, host)


def _meta(e) -> str:
    """The event's descriptive stats as one string (the op's long name,
    its HLO category, its source scope), so a kernel can be found by name
    wherever the profiler put it."""
    parts = []
    for k, v in e.stats:
        if isinstance(v, (str, bytes)):
            parts.append(f"{k}={v if isinstance(v, str) else v.decode()}")
    return " ".join(parts)


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [start, start+dur) clipped to [lo, hi)."""
    total, end = 0, lo
    for s, d in sorted(intervals):
        a, b = max(s, end), min(s + d, hi)
        if b > a:
            total += b - a
        end = max(end, b)
    return total


def gaps(intervals, lo: int, hi: int):
    """The idle stretches of [lo, hi) between the intervals."""
    out, end = [], lo
    for s, d in sorted(intervals):
        if end < s < hi:
            out.append((end, min(s, hi)))
        end = max(end, s + d)
    if end < hi:
        out.append((end, hi))
    return out


def span(host, name: str):
    """(start, end) of the first host event called ``name``."""
    for n, s, d, _ in host:
        if n == name:
            return s, s + d
    raise KeyError(f"no host span {name!r} in the trace")


@dataclasses.dataclass
class DeviceReading:
    busy_ns: int
    launches: int
    kernel_ns: int
    kernel_calls: int
    op_ns: dict              # HLO instruction name -> device ns


def op_name(text: str) -> str:
    """The HLO instruction's name from the profiler's op text
    (``%reid_topk_segments.1 = (...) custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_device(dev: dict, lo: int, hi: int, is_kernel) -> DeviceReading:
    ops = [e for e in dev["ops"] if lo <= e[1] < hi]
    op_ns: dict = {}
    kernel_ns = kernel_calls = 0
    for name, s, d, meta in ops:
        short = op_name(name)
        op_ns[short] = op_ns.get(short, 0) + d
        if is_kernel(name, meta):
            kernel_ns += d
            kernel_calls += 1
    return DeviceReading(
        busy_ns=union_ns([(s, d) for _, s, d, _ in ops], lo, hi),
        launches=sum(1 for e in dev["modules"] if lo <= e[1] < hi),
        kernel_ns=kernel_ns, kernel_calls=kernel_calls, op_ns=op_ns)


def attribute_gaps(dev: dict, host, lo: int, hi: int, top: int = 10):
    """Idle time of one chip over [lo, hi), summed by what the host was
    doing at the middle of each gap: the innermost host span there."""
    import bisect

    busy = [(s, d) for _, s, d, _ in dev["ops"] if lo <= s < hi]
    spans = sorted(((s, s + d, n) for n, s, d, _ in host if d > 0),
                   key=lambda x: (x[0], -x[1]))
    starts = [s for s, _, _ in spans]
    # spans of one thread nest: each span's parent is the innermost span
    # that was open when it started
    parent, stack = [], []
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    by_name: dict = {}
    for a, b in gaps(busy, lo, hi):
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and spans[i][1] <= mid:
            i = parent[i]
        name = spans[i][2] if i >= 0 else "(no host span)"
        by_name[name] = by_name.get(name, 0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[n, ns / 1e9] for n, ns in ranked]
