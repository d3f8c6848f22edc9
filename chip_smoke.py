#!/usr/bin/env python
"""Smoke run of the live serving path on a TPU.

    python chip_smoke.py             # one chip: phases A-D
    python chip_smoke.py --chips 4   # the four-chip fleet against one engine

One process drives everything through ``repro.api`` exactly as
``python -m repro.launch.serve`` does (the same world, engine and ingest
helpers).  Phases on one chip:

  A  the compiled ranking kernels at the deployments' widths (Q=256 live
     queries, a 4,096-row round gallery of 64-d embeddings; 8 and 130
     cameras; camera and T=8 tile admission) against the ``kernels/ref.py``
     oracle computed on the host, and the engine's round step, which must
     hold the Mosaic kernel: an interpret-mode fallback fails here.
  B  Duke-8 campus: 64 live queries over 600 ticks, camera admission.
  C  Duke-8 at tile_grid=8 with learned entry-region masks.
  D  city-130 (the soak world, 12 queries): camera admission, then
     tile_grid=8 with every tile admitted, which must count the same.

B-D run under ``RecompileGuard(max_new=1)`` and must reproduce the counts
the same seeded phases give on the CPU with interpret-mode kernels
(``EXPECTED``).  Tick times are host-clock smoke timings over ticks that
compiled nothing, not benchmark numbers: ``tick()`` returns after copying
every round output to the host, so each one includes the device's work.

``--chips 4`` runs only the fleet: ``serve(shards=4)`` over the four chips
on the Duke-8 stream, one worker lost mid-run, against the single engine on
chip 0.  The traces must be identical and every gallery block must sit on
its owner's chip.

Without a TPU the script exits non-zero before any phase; any failed check
exits non-zero too.  The last line of a passing run is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

Q, G, D = 256, 4096, 64          # phase A: live queries, gallery rows, dims
# |kernel - host| bound on a score: a 64-term f32 dot product of unit
# vectors rounds to within 64 * 2**-24 ~ 4e-6 on either side
SCORE_TOL = 1e-5
DUKE_QUERIES, TICKS = 64, 600
LOSE_AT = 300        # fleet phase: tick at which the busiest worker is lost

# What each seeded phase counts on the CPU (JAX_PLATFORMS=cpu,
# interpret-mode kernels).  gallery_rows is the padded round-gallery
# high-water mark, primed up front so the guarded run compiles each step
# once.
EXPECTED = {
    "duke8": dict(admitted_steps=2833, unique_frames=1994, matches=549,
                  rescues=0, replay_misses=0, gallery_rows=64),
    "duke8_tiles8": dict(admitted_steps=2833, unique_frames=1994,
                         matches=549, rescues=0, replay_misses=0,
                         gallery_rows=64, admitted_tiles=59233,
                         unique_tiles=50125),
    "city130": dict(admitted_steps=7771, unique_frames=7422, matches=864,
                    rescues=12, replay_misses=0, gallery_rows=32),
    "city130_tiles8": dict(admitted_steps=7771, unique_frames=7422,
                           matches=864, rescues=12, replay_misses=0,
                           gallery_rows=32, admitted_tiles=497344,
                           unique_tiles=475008),
}


class SmokeError(AssertionError):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def tpu_device() -> dict:
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}); nothing was run")
    d = jax.devices()
    return dict(platform=d[0].platform, kind=d[0].device_kind, count=len(d))


class CompileClock:
    """Sums the backend compile time JAX reports, so a tick that compiled
    can be told apart from a steady one."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.events += 1


# ---------------------------------------------------------------------------
# phase A: kernels at real widths
# ---------------------------------------------------------------------------

def _unit(x):
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def _compare(name, got, want, scores, valid):
    """Scores within SCORE_TOL; indices equal except where the competing
    host scores are within SCORE_TOL of each other; sentinels identical."""
    gv, gi = (np.asarray(a) for a in got)
    wv, wi = (np.asarray(a) for a in want)
    check(np.array_equal(gi == -1, wi == -1),
          f"{name}: (NEG_INF, -1) sentinel slots differ from the oracle")
    real = wi >= 0
    err = float(np.abs(gv[real] - wv[real]).max(initial=0.0))
    check(err <= SCORE_TOL, f"{name}: max |score - oracle| {err:.3g} > "
                            f"{SCORE_TOL:g}")
    rows, cols = np.nonzero(real & (gi != wi))
    for r, c in zip(rows, cols):
        check(bool(valid[r, gi[r, c]]),
              f"{name}: row {r} band {c} ranked masked-out column {gi[r, c]}")
        gap = abs(float(scores[r, gi[r, c]]) - float(scores[r, wi[r, c]]))
        check(gap <= SCORE_TOL,
              f"{name}: row {r} band {c} index {gi[r, c]} vs oracle "
              f"{wi[r, c]}, scores {gap:.3g} apart")
    print(f"  {name}: max score error {err:.3g}, {len(rows)} index swaps "
          f"within tolerance, {int(real.sum())} real bands")


def phase_kernels():
    import jax
    from repro.analysis.registry import entries
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    host = jax.devices("cpu")[0]
    for C, T, k in [(8, 0, 1), (130, 0, 3), (8, 8, 1), (130, 8, 1)]:
        q, g = _unit(rng.normal(size=(Q, D))), _unit(rng.normal(size=(G, D)))
        q_seg = rng.integers(0, 4, Q).astype(np.int32)
        g_seg = rng.integers(0, 4, G).astype(np.int32)
        gal_cam = rng.integers(0, C, G).astype(np.int32)
        if T:
            cells = gal_cam * T * T + rng.integers(0, T * T, G)
            admit = rng.random((Q, C * T * T)) < 0.5
            args = (q, q_seg, admit, g, cells.astype(np.int32), g_seg, k)
            kernel, oracle = ops.reid_topk_tiles, ref.reid_topk_tiles_ref
            name = f"reid_topk_tiles C={C} T={T} k={k}"
            valid = admit[:, cells]
        else:
            admit = rng.random((Q, C)) < 0.5
            args = (q, q_seg, admit, g, gal_cam, g_seg, k)
            kernel, oracle = ops.reid_topk_segments, ref.reid_topk_segments_ref
            name = f"reid_topk_segments C={C} k={k}"
            valid = admit[:, gal_cam]
        valid = valid & (g_seg[None, :] == q_seg[:, None])
        got = kernel(*args, interpret=False)
        with jax.default_device(host):
            want = oracle(*args)
        _compare(name, got, want, q.astype(np.float64) @ g.T.astype(
            np.float64), valid)

    step = next(e for e in entries(include_fleet=False)
                if e.name == "rank_advance_round_seg")
    args, kw = step.example()
    text = step.fn.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in text,
          "the engine's rank_advance_round_seg step compiled without the "
          "Mosaic kernel (interpret-mode fallback)")
    print("  rank_advance_round_seg step: Mosaic kernel compiled in")


# ---------------------------------------------------------------------------
# phases B-D: the serving path
# ---------------------------------------------------------------------------

def _pcts(lat):
    if not lat:
        return "no steady ticks"
    p50, p99 = (float(x) for x in np.percentile(np.asarray(lat) * 1e3,
                                                 [50, 99]))
    return f"p50 {p50!r} ms, p99 {p99!r} ms over {len(lat)} ticks"


def counts_of(eng, matches: int) -> dict:
    c = dict(admitted_steps=int(eng.admitted_steps),
             unique_frames=int(eng.unique_frames), matches=int(matches),
             rescues=sum(q.rescued for q in eng.queries.values()),
             replay_misses=int(eng.replay_misses),
             gallery_rows=int(eng.padded_gallery_rows))
    if eng.tile_grid > 0:
        c.update(admitted_tiles=int(eng.admitted_tiles),
                 unique_tiles=int(eng.unique_tiles))
    return c


def run_phase(name, world, policy, ticks, clock, **serve_kw):
    """Serve ``world`` for ``ticks`` ticks under RecompileGuard(max_new=1)
    and check the counts against EXPECTED[name]."""
    from repro.analysis import RecompileGuard
    from repro.launch.serve import ingest_tick, serve_world

    expect = EXPECTED[name]
    eng = serve_world(world, policy=policy, **serve_kw)
    eng.prime_batch(len(world["q_vids"]))
    eng.prime_gallery(expect["gallery_rows"])
    t0, horizon = eng.t, world["vis"].horizon
    c0, e0 = clock.seconds, clock.events
    steady, warm_wall, matches = [], 0.0, 0
    with RecompileGuard.for_engine(eng, max_new=1, label=name):
        for t in range(t0, min(t0 + ticks, horizon)):
            ingest_tick(eng, world, t)
            ev = clock.events
            w0 = time.perf_counter()
            matches += eng.tick()["matches"]
            dt = time.perf_counter() - w0
            if clock.events == ev:
                steady.append(dt)
            else:
                warm_wall += dt
    got = counts_of(eng, matches)
    C, n_q = world["vis"].n_cams, len(world["q_vids"])
    naive = (t - t0 + 1) * C * n_q
    print(f"[{name}] {t - t0 + 1} ticks, {n_q} queries, {C} cameras: "
          + " ".join(f"{k}={v}" for k, v in got.items()))
    print(f"[{name}] savings {naive / max(got['admitted_steps'], 1):.1f}x "
          f"vs all-camera ({naive} camera-steps); compile "
          f"{clock.seconds - c0:.2f} s in {clock.events - e0} programs, "
          f"{warm_wall:.2f} s of compiling ticks")
    print(f"[{name}] steady tick (smoke timing, host clock): "
          f"{_pcts(steady)}")
    check(got == expect, f"{name}: counts {got} differ from the CPU run's "
                         f"{expect}")
    return eng, got


def phase_serving(clock):
    import jax
    from benchmarks.scenarios import soak_city
    from repro import api as rexcam
    from repro.launch.serve import duke_world

    duke = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    run_phase("duke8", duke_world(DUKE_QUERIES), duke, TICKS, clock)
    run_phase("duke8_tiles8", duke_world(DUKE_QUERIES, tile_grid=8), duke,
              TICKS, clock, tile_grid=8)

    city = soak_city()
    city_policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05,
                                      t_thresh=.02, exit_t=120)
    cam, cam_counts = run_phase("city130", city, city_policy, TICKS, clock)
    tiles, tile_counts = run_phase("city130_tiles8", city, city_policy,
                                   TICKS, clock, tile_grid=8)
    for k in ("admitted_steps", "unique_frames", "matches", "rescues"):
        check(cam_counts[k] == tile_counts[k],
              f"all-tiles-admitted city run changed {k}: "
              f"{tile_counts[k]} vs camera {cam_counts[k]}")
    for label, eng in (("camera", cam), ("tile_grid=8", tiles)):
        leaves = [x for x in jax.tree.leaves(eng.model)
                  if isinstance(x, jax.Array)]
        print(f"[city130] model on the device ({label}): "
              f"{sum(x.nbytes for x in leaves)} bytes in {len(leaves)} "
              f"arrays (cdf {eng.model.cdf.shape} "
              f"{eng.model.cdf.nbytes} bytes)")


# ---------------------------------------------------------------------------
# --chips 4: the fleet against the single engine
# ---------------------------------------------------------------------------

def _drive(eng, world, ticks, lose_at=None):
    """Tick ``eng`` through the world's stream, recording the trace; at
    step ``lose_at`` the fleet loses its busiest live worker.  Returns
    (trace, per-tick seconds, the lost worker's report row)."""
    from repro.launch.serve import ingest_tick

    trace, lat, lost = [], [], None
    t0 = eng.t
    for step, t in enumerate(range(t0, t0 + ticks)):
        if step == lose_at:
            lost = max((r for r in eng.shard_report() if r["alive"]),
                       key=lambda r: r["admitted_steps"])
            eng.lose_worker(lost["worker"])
        ingest_tick(eng, world, t)
        w0 = time.perf_counter()
        eng.tick(record_trace=trace)
        lat.append(time.perf_counter() - w0)
    return trace, lat, lost


def phase_fleet():
    import jax
    from repro import api as rexcam
    from repro.launch.serve import duke_world, serve_world
    from repro.runtime.engine import trace_key

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs four chips, JAX sees {len(devs)}")
    world = duke_world(DUKE_QUERIES)
    policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    single = serve_world(world, policy=policy)
    fleet = serve_world(world, policy=policy, shards=4)
    for eng in (single, fleet):
        eng.prime_batch(DUKE_QUERIES)
        eng.prime_gallery(EXPECTED["duke8"]["gallery_rows"])
    ref_trace, ref_lat, _ = _drive(single, world, TICKS)
    fl_trace, fl_lat, lost = _drive(fleet, world, TICKS, lose_at=LOSE_AT)
    check(lost["admitted_steps"] > 0,
          f"{lost['worker']} served no round before it was lost")

    check(single.model.cdf.devices() == {devs[0]},
          "the single engine's model is not on chip 0")
    check(trace_key(fl_trace) == trace_key(ref_trace),
          "fleet trace diverged from the single engine on chip 0")
    for k in ("admitted_steps", "unique_frames", "content_steps",
              "replay_steps"):
        check(getattr(fleet, k) == getattr(single, k),
              f"fleet {k} {getattr(fleet, k)} != single {getattr(single, k)}")
    check(np.array_equal(fleet.rescue_pairs, single.rescue_pairs),
          "fleet rescue attribution differs from the single engine")
    print(f"[fleet] trace-identical to the single engine over "
          f"{len(ref_trace)} query-rounds, {lost['worker']} (chip "
          f"{lost['device']}, {lost['admitted_steps']} admitted steps) lost "
          f"at tick {LOSE_AT}; "
          f"admitted_steps={fleet.admitted_steps} "
          f"unique_frames={fleet.unique_frames}")

    live = list(fleet.mesh.devices.flat)
    check(len(set(live)) == 3 and set(live) <= set(devs),
          f"fleet mesh after the loss spans {live}, expected 3 chips")
    check(fleet.model.cdf.sharding.device_set == set(live),
          "the fleet's model is not replicated on every live chip")
    per_worker = fleet.gallery_report()["per_worker"]
    holders = set()
    for row in fleet.shard_report():
        gw = per_worker[row["worker"]]
        print(f"  {row['worker']} on chip {row['device']} "
              f"[{'live' if row['alive'] else 'lost'}]: "
              f"owned_frames={row['owned_frames']} "
              f"admitted_steps={row['admitted_steps']} "
              f"gallery={gw['blocks']} blocks/{gw['bytes']}B "
              f"({gw['cameras']} cams, {gw['misplaced']} off-chip)")
        check(gw["misplaced"] == 0,
              f"{row['worker']}: {gw['misplaced']} gallery blocks are not "
              f"on the owner's chip")
        if gw["blocks"]:
            holders.add(row["device"])
    check(len(holders) >= 2,
          f"every gallery block sits on chip(s) {sorted(holders)}")
    print(f"[fleet] steady tick (smoke timing, host clock): single "
          f"{_pcts(ref_lat[10:])}; fleet {_pcts(fl_lat[10:])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the fleet phase over four chips")
    args = ap.parse_args(argv)

    device = tpu_device()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.serve import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")

    t_all = time.perf_counter()
    if args.chips == 4:
        phase_fleet()
    else:
        clock = CompileClock()
        print("[A] ranking kernels against the host oracle")
        phase_kernels()
        phase_serving(clock)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
