#!/usr/bin/env python3
"""ReXCam chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload duke8.live --seed 7 --seconds 20 --trace 0

Builds the cell's world from the seed, profiles it and builds the serving
engine through ``repro.api``, warms up until the live query population has
reached its target, serves ticks as fast as the engine takes them for
``--seconds``, then checks what was served against the plain reference and
prints one JSON object as the last line of standard output.  ``--trace 1``
traces the window with the profiler and reports the per-layer metrics
instead of the end-to-end ones.  ``--control 1`` puts the control in the
program's place for the comparison: the compared rounds scored in bfloat16,
whose ``score_gap`` must fail its limit (for setting the limit; the
benchmark's own runs do not pass it).

Before anything is allocated the run pins glibc's mmap threshold
(``pin_allocator``), so that what a tick costs does not depend on whether
set-up compiled its programs or loaded them from the cache.

Without a TPU, or with fewer chips than the cell needs, or on a chip with
no published peaks, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# glibc's M_MMAP_THRESHOLD, and its documented starting value
M_MMAP_THRESHOLD, MMAP_THRESHOLD = -3, 128 * 1024


def pin_allocator() -> None:
    """Fix glibc's mmap threshold before anything is allocated.

    glibc raises the threshold to the size of the largest mapped block
    freed so far.  A process that compiles its programs frees large blocks
    and so serves the engine's per-round buffers from its heap; one that
    loads them from the compilation cache maps and unmaps them every round,
    and on duke8 ticks 2.8 times slower.  Setting the threshold keeps it at
    glibc's starting value and stops that adaptation, so a tick costs the
    same whatever set-up did before it.
    """
    import ctypes

    if ctypes.CDLL("libc.so.6").mallopt(M_MMAP_THRESHOLD,
                                        MMAP_THRESHOLD) != 1:
        raise OSError("mallopt(M_MMAP_THRESHOLD) refused")


BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    try:
        pin_allocator()
    except OSError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import drive
    from harness.peaks import UnknownDevice

    try:
        cell = drive.find_cell(drive.load_benchmark(), args.workload)
        result = drive.run(cell, args.seed, args.seconds, bool(args.trace),
                           control=bool(args.control), t_process=T_PROCESS)
    except (drive.RunError, UnknownDevice, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
