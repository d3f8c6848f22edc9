"""Shared benchmark scenarios (built once, cached in-process and on disk).

Each scenario = (network, profile model, live visits, gallery, features,
queries) — profiling runs on a dedicated historical partition, live tracking
on held-out traffic, exactly the paper's §8.1 methodology.

``policy_sweep`` additionally exercises every admission scheme through the
``repro.api`` facade and reports compute-savings multipliers vs the
all-camera baseline (paper targets: 8.3x on Duke, 23-38x at city scale).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import api as rexcam
from repro.core import (anoncampus_like_network, build_gallery, build_model,
                        clustered_city_network, concat_visits,
                        duke_like_network, permute_network,
                        porto_like_network, simulate_network)
from repro.core.features import FeatureParams, make_features
from repro.core.simulate import restrict_network
from repro.core.tracker import make_queries
from repro.runtime.engine import trace_key


# ---------------------------------------------------------------------------
# Machine-readable benchmark records (BENCH_<scenario>.json via run.py).
# ---------------------------------------------------------------------------

#: scenario name -> list of record dicts appended by ``bench_record`` while a
#: sweep runs; ``benchmarks/run.py --bench-dir`` drains this into
#: ``BENCH_<scenario>.json`` after the sweep returns.
BENCH_RECORDS: dict = {}

#: The golden record schema: every measured BENCH row carries these, so the
#: perf trajectory (one BENCH_*.json per scenario, uploaded by CI) stays
#: joinable across scenarios and across time.  Rows that summarize OTHER
#: rows rather than a measured run (ratios, gates) opt out with
#: ``derived=True``.  ``scripts/bench_schema_check.py`` re-validates the
#: emitted JSON in CI, and ``tests/test_system.py`` audits every
#: ``bench_record`` call site against this tuple.
REQUIRED_BENCH_KEYS = ("scenario", "admitted_steps", "unique_frames",
                       "wall_s", "p50_tick_ms", "p99_tick_ms")


def bench_record(sweep: str, **fields) -> None:
    """Append one machine-readable record for ``BENCH_<sweep>.json``.
    Measured rows must carry every ``REQUIRED_BENCH_KEYS`` field; derived
    summary rows (``derived=True``) are exempt."""
    if not fields.get("derived"):
        missing = [k for k in REQUIRED_BENCH_KEYS if k not in fields]
        if missing:
            raise ValueError(
                f"bench_record({sweep!r}): measured record missing required "
                f"keys {missing} (pass derived=True for summary rows)")
    BENCH_RECORDS.setdefault(sweep, []).append(fields)


def pop_bench_records(sweep: str):
    """Drain (and clear) the records a sweep accumulated — run.py calls this
    both before a sweep (drop stale in-process state) and after (collect)."""
    return BENCH_RECORDS.pop(sweep, [])


def _tick_pcts(tick_lat):
    """(p50_ms, p99_ms) over a list of per-tick wall latencies in seconds."""
    if not tick_lat:
        return 0.0, 0.0
    p50, p99 = np.percentile(np.asarray(tick_lat) * 1e3, [50, 99])
    return float(p50), float(p99)


@functools.lru_cache(maxsize=None)
def duke(n_queries: int = 100):
    net = duke_like_network()
    vis = simulate_network(net, 2700, 5100, seed=0)   # 85 min @ 1 step/s
    gal, _ = build_gallery(vis, 24)
    model = build_model(vis.ent, vis.cam, vis.t_in, vis.t_out, net.n_cams,
                        time_limit=3000)               # profile partition
    feats, _ = make_features(vis, 2700, FeatureParams())
    q_vids, gt_vids = make_queries(vis, n_queries, seed=1)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, name="duke")


@functools.lru_cache(maxsize=None)
def anoncampus(n_queries: int = 20):
    net = anoncampus_like_network()
    vis = simulate_network(net, 700, 2100, seed=5)     # 35 min @ 1 step/s
    gal, _ = build_gallery(vis, 24)
    model = build_model(vis.ent, vis.cam, vis.t_in, vis.t_out, net.n_cams,
                        time_limit=1300)
    # indoor occlusions: noisier features (paper §8.2 recall note)
    feats, _ = make_features(vis, 700, FeatureParams(noise_sigma=0.55, seed=5))
    q_vids, gt_vids = make_queries(vis, n_queries, seed=6)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, name="anoncampus")


@functools.lru_cache(maxsize=None)
def porto(n_cams: int = 130, n_queries: int = 100):
    net = porto_like_network(130)
    cams = np.arange(n_cams)
    if n_cams < 130:
        net = restrict_network(net, cams)
    # dedicated historical partition for profiling (denser statistics)
    hist = simulate_network(net, 6000, 7200, seed=11)
    model = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, net.n_cams)
    vis = simulate_network(net, 2000, 3600, seed=2)
    gal, _ = build_gallery(vis, 16)
    # city-scale identity diversity: more lookalike groups than the campus
    # sims (keeps the baseline near the paper's ~50% precision at 130 cams)
    feats, _ = make_features(vis, 2000, FeatureParams(n_clusters=400, seed=2))
    q_vids, gt_vids = make_queries(vis, n_queries, seed=3)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, name=f"porto{n_cams}")


# ---------------------------------------------------------------------------
# policy_sweep: every admission scheme through the repro.api facade.
# ---------------------------------------------------------------------------

SWEEP_POLICIES = (
    ("all", rexcam.SearchPolicy(scheme="all")),
    ("geo", rexcam.SearchPolicy(scheme="geo")),
    ("spatial_only", rexcam.SearchPolicy(scheme="spatial_only", s_thresh=.05)),
    ("rexcam", rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)),
)


def policy_sweep(scenarios=("duke", "porto130")):
    """(name, us_per_call, derived) rows: per scenario, each scheme's cost,
    recall/precision, and savings multiplier vs the all-camera baseline
    (paper Table targets: 8.3x Duke spatio-temporal, 23-38x at 130 cams)."""
    builders = {"duke": lambda: duke(60), "anoncampus": lambda: anoncampus(20),
                "porto130": lambda: porto(130, 60)}
    rows = []
    for sc_name in scenarios:
        sc = builders[sc_name]()
        base_cost = None
        for pname, policy in SWEEP_POLICIES:
            t0 = time.perf_counter()
            r = rexcam.track(sc["model"], sc["vis"], sc["gal"], sc["feats"],
                             sc["q_vids"], sc["gt_vids"], policy,
                             geo_adj=sc["net"].geo_adjacent)
            # per-query us, matching the other benchmark tables' convention
            us = (time.perf_counter() - t0) * 1e6 / max(len(sc["q_vids"]), 1)
            if pname == "all":
                base_cost = r.total_cost
            savings = base_cost / max(r.total_cost, 1.0)
            rows.append((f"policy_sweep/{sc['name']}/{pname}", us,
                         f"savings={savings:.1f}x recall={r.recall:.2f} "
                         f"precision={r.precision:.2f} "
                         f"rescued={int(r.rescued.sum())}"))
    return rows


# ---------------------------------------------------------------------------
# serving_sweep: the live engine's cost accounting, per scheme.
# ---------------------------------------------------------------------------

def _drive_serving(sc, policy, n_queries, steps, shards=None,
                   gallery="auto", transport=None, prefetch=False,
                   guard_steady_after=None, tile_grid=0, model=None,
                   topk_rerank=False, prime_gal=0):
    """The one engine-driving loop every serving benchmark shares: build the
    engine (fleet when ``shards``), submit the scenario's queries, replay the
    live stream tick by tick.  Returns (engine, matches, wall seconds
    including engine construction and jit warmup, per-tick wall latencies).

    ``transport=``/``prefetch=`` pass straight through to ``rexcam.serve`` —
    the transport_sweep drives the same loop with a ``FakeRpcTransport`` so
    its walls are comparable against every other serving row.

    ``tile_grid=T > 0`` serves through the sub-frame spatial admission plane
    (per-detection tile labels from the scenario's ground-truth positions
    ride along with every ingest); ``model=`` overrides the scenario's
    profile — tile_sweep passes a tile-carrying re-profile of the same
    visits.  ``topk_rerank=`` turns on §5.2 confidence re-ranking.

    ``guard_steady_after=N`` arms a ``RecompileGuard`` over every registered
    jit entry (plus the fleet's shard_map jits) once tick N is reached: the
    remaining ticks are the benchmark's steady state, and a compile-cache
    miss there (shape churn, a kwarg leaking out of the statics) raises
    instead of silently poisoning the reported walls."""
    from repro.analysis import RecompileGuard

    vis, gal, feats, net = sc["vis"], sc["gal"], sc["feats"], sc["net"]
    q_vids = sc["q_vids"][:n_queries]
    vis_tiles = None
    if tile_grid > 0:
        from repro.core.simulate import tile_index
        vis_tiles = tile_index(vis.tile_xy, tile_grid)
    wall0 = time.perf_counter()
    eng = rexcam.serve(sc["model"] if model is None else model,
                       embed_fn=lambda x: x, policy=policy,
                       geo_adj=net.geo_adjacent, shards=shards,
                       gallery=gallery, transport=transport,
                       prefetch=prefetch, tile_grid=tile_grid,
                       topk_rerank=topk_rerank)
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    # pre-size the padded batch: round cohorts form lazily (a 3-query
    # cohort may first appear hundreds of ticks in), and every pow2 growth
    # mints a jit signature — priming moves them all into warmup so the
    # RecompileGuard-ed steady half compiles nothing
    eng.prime_batch(len(q_vids))
    if prime_gal:
        # the gallery side has the same lazy-growth problem: a late phase-2
        # rescue can admit the largest round gallery yet — callers that
        # guard their steady state pass the high-water mark of an unguarded
        # warmup drive so the rank signature is minted once, up front
        eng.prime_gallery(prime_gal)
    matches = 0
    tick_lat = []
    guard = None
    for step_i, t in enumerate(range(t0, min(t0 + steps, vis.horizon))):
        if guard_steady_after is not None and step_i == guard_steady_after:
            # each entry may mint at most ONE more signature after warmup
            # (a genuinely new shape class, e.g. the round gallery growing
            # past its high-water mark); per-tick churn trips immediately
            guard = RecompileGuard.for_engine(
                eng, max_new=1, label=f"steady after tick {step_i}")
            guard.__enter__()
        frames, tiles = {}, {}
        for c in range(net.n_cams):
            vids = gal[c, t][gal[c, t] >= 0]
            if len(vids):
                frames[c] = feats[vids]
                if vis_tiles is not None:
                    tiles[c] = vis_tiles[vids]
        if tile_grid > 0:
            eng.ingest(frames, tiles)
        else:
            eng.ingest(frames)
        tk0 = time.perf_counter()
        matches += eng.tick()["matches"]
        tick_lat.append(time.perf_counter() - tk0)
    if guard is not None:
        guard.__exit__(None, None, None)
    return eng, matches, time.perf_counter() - wall0, tick_lat


def _match_delay(eng) -> float:
    """Mean ticks from submit to the first confirmed match (the Fig. 15
    detection-delay metric) over the queries that ever matched; -1 when
    none did."""
    d = [q.first_match_t - q.submit_t for q in eng.queries.values()
         if q.first_match_t >= 0]
    return float(np.mean(d)) if d else -1.0


#: §5.3 replay catch-up modes for the Fig. 15-style serving rows: real-time
#: replay, fast-forward (parallelism — extra content rounds per wall tick)
#: and frame-skip (sample every k-th content frame while behind).
REPLAY_MODES = (
    ("base", {}),
    ("ff", dict(replay_speed=4.0)),
    ("skip", dict(replay_skip=4)),
)


def serving_sweep(scenarios=("duke",), n_queries=16, steps=400):
    """Engine-plane sweep: drive the live ``ServingEngine`` per scheme over
    real ingest and report the two cost conventions separately —
    ``admitted_steps`` (per-query camera-steps, directly comparable with the
    tracker's cost and ``policy_sweep``'s savings multipliers) and
    ``unique_frames`` (deduplicated inference load), plus the multipliers
    the serving plane adds on top: cross-query dedup and the FrameStore
    embedding-cache hit rate on replay re-reads.

    A second block of rows replays Fig. 15 ON THE SERVING PLANE: the rexcam
    scheme under each §5.3 replay catch-up mode (real-time, fast-forward,
    frame-skip), reporting cost (admitted/content/replay steps) against the
    detection delay (mean ticks from submit to first confirmed match) —
    one ``BENCH_serving_sweep.json`` record per replay mode."""
    builders = {"duke": lambda: duke(60)}
    rows = []
    for sc_name in scenarios:
        sc = builders[sc_name]()
        n_q = min(n_queries, len(sc["q_vids"]))
        base = None
        for pname, policy in SWEEP_POLICIES:
            eng, matches, wall, lat = _drive_serving(
                sc, policy, n_q, steps, guard_steady_after=steps // 2)
            us = wall * 1e6 / max(n_q, 1)
            if pname == "all":
                base = eng.admitted_steps
            savings = base / max(eng.admitted_steps, 1)
            dedup = eng.admitted_steps / max(eng.unique_frames, 1)
            # hit rate over replay re-reads only — live first-embeds can
            # never be cache hits and would just dilute the number
            hot = eng.cache_hits / max(eng.cache_hits + eng.replay_embeds, 1)
            p50, p99 = _tick_pcts(lat)
            bench_record("serving_sweep", scenario=sc["name"], policy=pname,
                         admitted_steps=int(eng.admitted_steps),
                         unique_frames=int(eng.unique_frames),
                         wall_s=round(wall, 4), p50_tick_ms=round(p50, 3),
                         p99_tick_ms=round(p99, 3), matches=int(matches))
            rows.append((f"serving_sweep/{sc['name']}/{pname}", us,
                         f"savings={savings:.1f}x "
                         f"admitted_steps={eng.admitted_steps} "
                         f"unique_frames={eng.unique_frames} "
                         f"dedup={dedup:.1f}x replay_cache_hot={hot:.2f} "
                         f"matches={matches}"))
        # Fig. 15 on the serving plane: cost vs detection delay per §5.3
        # replay mode (ff buys delay with extra content rounds per tick,
        # skip buys cost by sampling every k-th content frame while behind)
        for mode, knobs in REPLAY_MODES:
            policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05,
                                         t_thresh=.02, **knobs)
            eng, matches, wall, lat = _drive_serving(
                sc, policy, n_q, steps, guard_steady_after=steps // 2)
            delay = _match_delay(eng)
            p50, p99 = _tick_pcts(lat)
            bench_record("serving_sweep", scenario=sc["name"],
                         policy="rexcam", replay_mode=mode,
                         replay_speed=float(policy.replay_speed),
                         replay_skip=int(policy.replay_skip),
                         admitted_steps=int(eng.admitted_steps),
                         unique_frames=int(eng.unique_frames),
                         content_steps=int(eng.content_steps),
                         replay_steps=int(eng.replay_steps),
                         skipped_steps=int(eng.skipped_steps),
                         detection_delay_ticks=round(delay, 2),
                         matches=int(matches), wall_s=round(wall, 4),
                         p50_tick_ms=round(p50, 3),
                         p99_tick_ms=round(p99, 3))
            rows.append((f"serving_sweep/{sc['name']}/replay_{mode}",
                         wall * 1e6 / max(n_q, 1),
                         f"delay={delay:.1f}ticks "
                         f"admitted_steps={eng.admitted_steps} "
                         f"content_steps={eng.content_steps} "
                         f"replay_steps={eng.replay_steps} "
                         f"skipped={eng.skipped_steps} matches={matches}"))
    return rows


# ---------------------------------------------------------------------------
# tile_sweep: sub-frame spatial admission — tile-granular pixel load vs the
# camera-granular baseline, at equal recall.
# ---------------------------------------------------------------------------

def tile_sweep(n_queries=16, steps=400, tile_grid=8, tile_keep=1.0):
    """The sub-frame spatial admission tentpole, measured and asserted on
    duke:

    * DIFFERENTIAL — serving with ``tile_grid=T`` over the scenario's
      tile-less profile (the engine synthesizes the all-tiles-admitted
      tensor) must reproduce the camera-granular baseline exactly: same
      admitted_steps / unique_frames / matches, with
      ``admitted_tiles == T*T * admitted_steps`` (the tile plane is a pure
      refinement — asserted end to end, mirroring the fleet differential);
    * LEARNED MASKS — re-profiling the same visits with
      ``profile(..., tile_grid=T)`` learns per (src, dst) camera-pair
      entry-region masks; serving through them must cut the admitted
      pixel-load proxy (tiles actually scored, vs the camera-granular T*T
      ceiling at the same admissions) by >= 2x at recall no worse than the
      baseline's — both ASSERTED, the acceptance gate the CI smoke greps.

    The pixel-load convention: a camera-granular admitted step decodes/
    scores all T*T tiles of the frame; a tile-granular step touches only
    the fused cells the model admits.  ``unique_tiles`` is the same under
    the deduplicated convention (per-key tile unions vs T*T per unique
    frame)."""
    sc = duke(60)
    vis = sc["vis"]
    n_q = min(n_queries, len(sc["q_vids"]))
    q_vids, gt_vids = sc["q_vids"][:n_q], sc["gt_vids"][:n_q]
    T, TT = tile_grid, tile_grid * tile_grid
    policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    rows = []

    # unguarded warmup drive: learns the round-gallery row high-water mark
    # (gallery shapes grow lazily — the largest round gallery can first
    # appear deep in the run) so the three guarded drives below can prime
    # both sides of every jit signature up front and compile nothing in
    # their steady halves
    warm, _, _, _ = _drive_serving(sc, policy, n_q, steps)
    gal_rows = warm.padded_gallery_rows

    # camera-granular baseline
    base, m_base, wall_b, lat_b = _drive_serving(
        sc, policy, n_q, steps, guard_steady_after=steps // 2,
        prime_gal=gal_rows)
    recall_b = _serving_recall(base, vis, q_vids, gt_vids)
    p50_b, p99_b = _tick_pcts(lat_b)
    bench_record("tile_sweep", scenario=sc["name"], config="camera",
                 tile_grid=0, admitted_steps=int(base.admitted_steps),
                 unique_frames=int(base.unique_frames),
                 admitted_tiles=TT * int(base.admitted_steps),
                 recall=round(recall_b, 4), matches=int(m_base),
                 wall_s=round(wall_b, 4), p50_tick_ms=round(p50_b, 3),
                 p99_tick_ms=round(p99_b, 3))
    rows.append((f"tile_sweep/{sc['name']}/camera",
                 wall_b * 1e6 / max(n_q, 1),
                 f"recall={recall_b:.2f} "
                 f"admitted_steps={base.admitted_steps} "
                 f"pixel_load={TT * base.admitted_steps}tiles "
                 f"matches={m_base}"))

    # all-tiles-admitted differential: the tile execution path over the
    # SAME tile-less model must change nothing but the counters' units
    alladm, m_all, wall_a, lat_a = _drive_serving(
        sc, policy, n_q, steps, tile_grid=T, guard_steady_after=steps // 2,
        prime_gal=gal_rows)
    assert alladm.admitted_steps == base.admitted_steps, \
        "tile path changed admitted_steps under all-admitted tiles"
    assert alladm.unique_frames == base.unique_frames, \
        "tile path changed unique_frames under all-admitted tiles"
    assert m_all == m_base, "tile path changed match outcomes"
    assert alladm.admitted_tiles == TT * alladm.admitted_steps
    assert alladm.unique_tiles == TT * alladm.unique_frames
    p50_a, p99_a = _tick_pcts(lat_a)
    bench_record("tile_sweep", scenario=sc["name"], config="all_admitted",
                 tile_grid=T, admitted_steps=int(alladm.admitted_steps),
                 unique_frames=int(alladm.unique_frames),
                 admitted_tiles=int(alladm.admitted_tiles),
                 unique_tiles=int(alladm.unique_tiles),
                 recall=round(recall_b, 4), matches=int(m_all),
                 wall_s=round(wall_a, 4), p50_tick_ms=round(p50_a, 3),
                 p99_tick_ms=round(p99_a, 3))
    rows.append((f"tile_sweep/{sc['name']}/all_admitted",
                 wall_a * 1e6 / max(n_q, 1),
                 f"differential=ok admitted_tiles={alladm.admitted_tiles} "
                 f"(=TT*admitted_steps) matches={m_all} "
                 f"wall={wall_a:.2f}s vs camera {wall_b:.2f}s"))

    # learned entry-region masks, profiled on the scenario's own profile
    # partition (same time_limit as the camera model)
    tile_model = rexcam.profile(vis, time_limit=3000, tile_grid=T,
                                tile_keep=tile_keep)
    learned, m_t, wall_t, lat_t = _drive_serving(
        sc, policy, n_q, steps, tile_grid=T, model=tile_model,
        guard_steady_after=steps // 2, prime_gal=gal_rows)
    recall_t = _serving_recall(learned, vis, q_vids, gt_vids)
    pixel_base = TT * base.admitted_steps
    reduction = pixel_base / max(learned.admitted_tiles, 1)
    dedup_red = (TT * learned.unique_frames) / max(learned.unique_tiles, 1)
    p50_t, p99_t = _tick_pcts(lat_t)
    bench_record("tile_sweep", scenario=sc["name"], config="learned",
                 tile_grid=T, tile_keep=tile_keep,
                 admitted_steps=int(learned.admitted_steps),
                 unique_frames=int(learned.unique_frames),
                 admitted_tiles=int(learned.admitted_tiles),
                 unique_tiles=int(learned.unique_tiles),
                 pixel_reduction=round(reduction, 2),
                 recall=round(recall_t, 4), matches=int(m_t),
                 wall_s=round(wall_t, 4), p50_tick_ms=round(p50_t, 3),
                 p99_tick_ms=round(p99_t, 3))
    rows.append((f"tile_sweep/{sc['name']}/learned",
                 wall_t * 1e6 / max(n_q, 1),
                 f"pixel_reduction={reduction:.1f}x "
                 f"admitted_tiles={learned.admitted_tiles} "
                 f"of {pixel_base} camera-granular "
                 f"dedup_reduction={dedup_red:.1f}x "
                 f"recall={recall_t:.2f} (camera {recall_b:.2f}) "
                 f"matches={m_t} wall={wall_t:.2f}s"))

    # --- the acceptance asserts ----------------------------------------
    assert reduction >= 2.0, \
        f"tile_sweep: learned masks cut pixel load only {reduction:.2f}x " \
        f"({learned.admitted_tiles} of {pixel_base} tiles) — need >= 2x"
    assert recall_t >= recall_b, \
        f"tile_sweep: tile recall {recall_t:.3f} dropped below the " \
        f"camera-granular baseline's {recall_b:.3f}"
    rows.append((f"tile_sweep/{sc['name']}/acceptance", 0.0,
                 f"tile_gate=ok reduction={reduction:.1f}x>=2x "
                 f"recall_delta={recall_t - recall_b:+.3f}"))
    return rows


# ---------------------------------------------------------------------------
# serving_shard_sweep: the fleet vs one engine, per shard count.
# ---------------------------------------------------------------------------

def serving_shard_sweep(scenarios=("duke",), n_queries=16, steps=300,
                        shard_counts=(1, 2, 4, 8)):
    """Shard the live query axis over {1, 2, 4, 8} devices and report, per
    shard count: wall-clock speedup vs the single-process engine, the fleet
    totals (which must EQUAL the single engine's — the differential-harness
    invariant, asserted here too), and the per-shard ``admitted_steps`` /
    ``unique_frames`` split (each worker's shard-local demand).

    Shard counts above the visible device count are reported as skipped —
    run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (and
    ``JAX_PLATFORMS=cpu``) to sweep the full fleet on one host."""
    import jax

    builders = {"duke": lambda: duke(60)}
    rows = []
    n_dev = len(jax.devices())
    for sc_name in scenarios:
        sc = builders[sc_name]()
        n_q = min(n_queries, len(sc["q_vids"]))
        policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05,
                                     t_thresh=.02)
        base_eng, _, base_wall, _ = _drive_serving(sc, policy, n_q, steps)
        for S in shard_counts:
            if S > n_dev:
                rows.append((f"serving_shard_sweep/{sc['name']}/shards{S}",
                             0.0, f"skipped: {n_dev} devices visible "
                             f"(set xla_force_host_platform_device_count)"))
                continue
            eng, _, wall, _ = _drive_serving(sc, policy, n_q, steps, shards=S)
            assert eng.admitted_steps == base_eng.admitted_steps, \
                "fleet diverged from the single engine (admitted_steps)"
            assert eng.unique_frames == base_eng.unique_frames, \
                "fleet diverged from the single engine (unique_frames)"
            rep = eng.shard_report()
            per_adm = "/".join(str(r["admitted_steps"]) for r in rep)
            per_uni = "/".join(str(r["unique_frames"]) for r in rep)
            rows.append((f"serving_shard_sweep/{sc['name']}/shards{S}",
                         wall * 1e6 / max(n_q, 1),
                         f"speedup={base_wall / max(wall, 1e-9):.2f}x "
                         f"wall={wall:.2f}s "
                         f"admitted_steps={eng.admitted_steps} "
                         f"unique_frames={eng.unique_frames} "
                         f"per_shard_admitted={per_adm} "
                         f"per_shard_unique={per_uni}"))
    return rows


# ---------------------------------------------------------------------------
# drift_sweep: the §6 degradation argument on the SERVING plane — inject a
# mid-run traffic-pattern shift and compare frozen vs recalibrating engines.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def drifted_duke(n_queries: int = 32, t_shift: int = 400,
                 post_horizon: int = 1800):
    """Duke-like world whose live stream shifts topology at ``t_shift``:
    cameras are re-permuted (a derangement — every pair the frozen profile
    trusts becomes wrong), while the model stays profiled on dedicated
    PRE-shift history.  Queries are drawn from the post-shift traffic, so
    every reported recall is "after the injected shift"."""
    net = duke_like_network()
    shifted = permute_network(net, np.roll(np.arange(net.n_cams), 3))
    hist = simulate_network(net, 2000, 4000, seed=31)
    model = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, net.n_cams)
    vis_a = simulate_network(net, 300, t_shift, seed=32)
    vis_b = simulate_network(shifted, 800, post_horizon, seed=33)
    vis = concat_visits(vis_a, vis_b, t_shift)
    gal, _ = build_gallery(vis, 24)
    feats, _ = make_features(vis, int(vis.ent.max()) + 1,
                             FeatureParams(seed=33))
    q_b, gt_b = make_queries(vis_b, n_queries, seed=34)
    q_vids = q_b + len(vis_a)
    gt_vids = np.where(gt_b >= 0, gt_b + len(vis_a), gt_b)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, t_shift=t_shift,
                name="duke-drift")


def _serving_recall(eng, vis, q_vids, gt_vids) -> float:
    """Tracker-comparable recall for the live engine: a ground-truth visit
    counts as retrieved when some confirmed match (cam, frame) lands inside
    it."""
    hits = total = 0
    for i in range(len(q_vids)):
        gts = gt_vids[i][gt_vids[i] >= 0]
        total += len(gts)
        ms = eng.queries[i].matches
        hits += sum(any(c == vis.cam[v] and vis.t_in[v] <= f <= vis.t_out[v]
                        for c, f in ms) for v in gts)
    return hits / max(total, 1)


def drift_sweep(n_queries: int = 32, shards: int = 8):
    """Paper §6 end-to-end ON THE SERVING PLANE: a re-permuted camera
    topology mid-run makes the frozen profile prune exactly the frames the
    traffic now uses; with ``recalibrate=`` on, the engine's live rescue
    matrix trips the drift trigger, a model re-profiled from the recent
    window hot-swaps in (epoch-bumped, queries in flight), and post-shift
    recall recovers — at LOWER admission cost, because the fresh model also
    prunes correctly again.  Reported rows: frozen baseline, recalibrating
    single engine, recalibrating ``shards``-way fleet (identical totals —
    the swap is atomic across the mesh).

    The recovery is asserted, not just reported: recalibrated recall must
    be strictly above the frozen-model row's (the CI drift smoke runs this).
    """
    import jax

    sc = drifted_duke(n_queries)
    vis, gal, feats, net = sc["vis"], sc["gal"], sc["feats"], sc["net"]
    q_vids, gt_vids = sc["q_vids"], sc["gt_vids"]
    policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    # trigger tuned to the duke profile's density: the hot drifted pairs
    # carry ~10-30 historical transitions, so a handful of rescues there
    # scores ~0.1-0.15 (see RecalibrationPolicy.drift_threshold's scale note)
    recal = rexcam.RecalibrationPolicy(drift_threshold=.06, min_rescues=8,
                                       cooldown=300, poll_every=20,
                                       window=600)

    def drive(recalibrate, n_shards=None):
        wall0 = time.perf_counter()
        eng = rexcam.serve(sc["model"], embed_fn=lambda x: x, policy=policy,
                           geo_adj=net.geo_adjacent, shards=n_shards,
                           recalibrate=recalibrate,
                           visit_source=rexcam.visits_window_source(vis)
                           if recalibrate is not None else None)
        t0 = int(vis.t_out[q_vids].min())
        eng.t = t0
        for i, q in enumerate(q_vids):
            eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
        tick_lat = []
        for t in range(t0, vis.horizon):
            frames = {}
            for c in range(net.n_cams):
                vids = gal[c, t][gal[c, t] >= 0]
                if len(vids):
                    frames[c] = feats[vids]
            eng.ingest(frames)
            tk0 = time.perf_counter()
            eng.tick()
            tick_lat.append(time.perf_counter() - tk0)
        return eng, time.perf_counter() - wall0, tick_lat

    def record(config, eng, wall, tick_lat, recall, **extra):
        p50, p99 = _tick_pcts(tick_lat)
        bench_record("drift_sweep", scenario=sc["name"], config=config,
                     admitted_steps=int(eng.admitted_steps),
                     unique_frames=int(eng.unique_frames),
                     wall_s=round(wall, 4), p50_tick_ms=round(p50, 3),
                     p99_tick_ms=round(p99, 3), recall=round(recall, 4),
                     epoch=int(eng.model_epoch), **extra)

    rows = []
    frozen, wall_f, lat_f = drive(None)
    r_frozen = _serving_recall(frozen, vis, q_vids, gt_vids)
    record("frozen", frozen, wall_f, lat_f, r_frozen)
    rows.append((f"drift_sweep/{sc['name']}/frozen",
                 wall_f * 1e6 / max(len(q_vids), 1),
                 f"recall={r_frozen:.2f} admitted_steps={frozen.admitted_steps} "
                 f"rescues={int(frozen.rescue_pairs.sum())} epoch=0 "
                 f"note=stale model degrades silently (no re-profiling)"))

    fresh, wall_r, lat_r = drive(recal)
    r_fresh = _serving_recall(fresh, vis, q_vids, gt_vids)
    record("recalibrated", fresh, wall_r, lat_r, r_fresh,
           swaps=len(fresh.model_swaps))
    ev = fresh.recal.events
    swaps = ";".join(f"t={e['t']}:epoch{e['epoch']}(score={e['score']:.2f})"
                     for e in ev)
    rows.append((f"drift_sweep/{sc['name']}/recalibrated",
                 wall_r * 1e6 / max(len(q_vids), 1),
                 f"recall={r_fresh:.2f} admitted_steps={fresh.admitted_steps} "
                 f"epoch={fresh.model_epoch} swaps=[{swaps}] "
                 f"note=rescue spike -> re-profile -> hot-swap restores the "
                 f"operating point"))
    assert ev, "drift_sweep: the injected shift never tripped the trigger"
    assert r_fresh > r_frozen, \
        f"drift_sweep: recalibrated recall {r_fresh:.3f} must beat the " \
        f"frozen model's {r_frozen:.3f} after the injected shift"

    if shards <= len(jax.devices()):
        fleet, wall_s, lat_s = drive(recal, n_shards=shards)
        r_fleet = _serving_recall(fleet, vis, q_vids, gt_vids)
        assert fleet.admitted_steps == fresh.admitted_steps, \
            "recalibrating fleet diverged from the single engine"
        assert fleet.model_swaps == fresh.model_swaps, \
            "fleet model swaps did not land on the single engine's ticks"
        assert r_fleet == r_fresh
        record(f"recalibrated_shards{shards}", fleet, wall_s, lat_s, r_fleet,
               swaps=len(fleet.model_swaps))
        rows.append((f"drift_sweep/{sc['name']}/recalibrated_shards{shards}",
                     wall_s * 1e6 / max(len(q_vids), 1),
                     f"recall={r_fleet:.2f} "
                     f"admitted_steps={fleet.admitted_steps} "
                     f"epoch={fleet.model_epoch} "
                     f"note=swap atomic across the mesh (same ticks as the "
                     f"single engine)"))
    else:
        rows.append((f"drift_sweep/{sc['name']}/recalibrated_shards{shards}",
                     0.0, f"skipped: {len(jax.devices())} devices visible "
                     f"(set xla_force_host_platform_device_count)"))
    return rows


# ---------------------------------------------------------------------------
# gallery_sweep: one fleet-wide embedding plane vs the replicated baseline.
# ---------------------------------------------------------------------------

def gallery_sweep(scenarios=("duke",), n_queries=16, steps=300, shards=4):
    """The gallery plane's win, quantified: drive the fleet with the
    fleet-shared ``ShardedGalleryStore`` and with the replicated-baseline
    ``LocalGalleryStore`` and report, per mode:

    * embed-call reduction — fleet-global embed calls (``frames_processed``)
      vs what a replicated per-worker cache would embed (the sum of each
      shard's shard-LOCAL deduplicated demand, ``unique_frames`` in
      ``shard_report()``),
    * per-worker cache memory — each owner's resident blocks/bytes under
      the sharded store vs the whole cache replicated onto every worker.

    Both modes must stay trace-identical to the single engine (asserted via
    the fleet totals).  Needs ``shards`` visible devices — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on a CPU host."""
    import jax

    builders = {"duke": lambda: duke(60)}
    rows = []
    n_dev = len(jax.devices())
    for sc_name in scenarios:
        if shards > n_dev:
            rows.append((f"gallery_sweep/{sc_name}", 0.0,
                         f"skipped: {n_dev} devices visible "
                         f"(set xla_force_host_platform_device_count)"))
            continue
        sc = builders[sc_name]()
        n_q = min(n_queries, len(sc["q_vids"]))
        policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05,
                                     t_thresh=.02)
        single, _, _, _ = _drive_serving(sc, policy, n_q, steps)
        for mode in ("local", "sharded"):
            eng, _, wall, lat = _drive_serving(sc, policy, n_q, steps,
                                               shards=shards, gallery=mode)
            assert eng.unique_frames == single.unique_frames, \
                f"gallery={mode} fleet diverged from the single engine"
            assert eng.frames_processed == single.frames_processed, \
                f"gallery={mode} fleet re-embedded (no longer one plane)"
            rep = eng.shard_report()
            replicated_embeds = sum(r["unique_frames"] for r in rep)
            reduction = replicated_embeds / max(eng.frames_processed, 1)
            g = eng.gallery_report()
            if mode == "sharded":
                per_w = g["per_worker"]
                mem = "/".join(f"{per_w[r['worker']]['bytes']}" for r in rep)
                peak = max(v["bytes"] for v in per_w.values())
            else:
                # replicated baseline: every worker would hold the full cache
                mem = "/".join(str(g["bytes"]) for _ in rep)
                peak = g["bytes"]
            p50, p99 = _tick_pcts(lat)
            bench_record("gallery_sweep", scenario=sc["name"], gallery=mode,
                         shards=shards,
                         admitted_steps=int(eng.admitted_steps),
                         unique_frames=int(eng.unique_frames),
                         wall_s=round(wall, 4), p50_tick_ms=round(p50, 3),
                         p99_tick_ms=round(p99, 3),
                         embed_calls=int(eng.frames_processed),
                         cache_hits=int(eng.cache_hits),
                         peak_worker_bytes=int(peak))
            rows.append((f"gallery_sweep/{sc['name']}/{mode}",
                         wall * 1e6 / max(n_q, 1),
                         f"embed_calls={eng.frames_processed} "
                         f"replicated_demand={replicated_embeds} "
                         f"embed_reduction={reduction:.1f}x "
                         f"cache_hits={eng.cache_hits} "
                         f"per_worker_bytes={mem} peak_worker_bytes={peak}"))
    return rows


# ---------------------------------------------------------------------------
# transport_sweep: latency hiding — speculative prefetch vs blocking fetches.
# ---------------------------------------------------------------------------

def transport_sweep(scenarios=("duke",), n_queries=16, steps=600, shards=4,
                    rtt_scales=(1, 4, 8)):
    """The transport plane's wall-clock argument, measured and asserted:
    drive the fleet through a real-clock ``FakeRpcTransport`` whose injected
    RTT is pegged to the measured p50 round latency ("comparable to one
    ranking pass"), and show

    * the BLOCKING fetch path degrades ~linearly in injected RTT — every
      owner-shard cache hit stalls the round for a full round trip, so the
      extra wall across ``rtt_scales`` tracks ``cache_hits x RTT`` (the
      slope between the smallest and largest scale is asserted), while
    * the PREFETCHED path (double-buffered speculative fetch issued at the
      end of the previous round) hides the latency behind compute: at
      RTT = one ranking pass its wall must land within 25% of the
      zero-latency baseline (asserted), with misspeculation exactly
      accounted (``prefetch_wasted``).

    Every run must stay trace-identical — admitted_steps/unique_frames are
    asserted EQUAL across the baseline, every blocking RTT and the
    prefetched run (transport moves WHEN blocks arrive, never WHAT is
    ranked).  Uses ``steps=600`` so the replay phase re-reads enough
    owner-shard blocks (~130 remote fetches) for the walls to separate.
    Needs ``shards`` visible devices (xla_force_host_platform_device_count).
    """
    import jax

    builders = {"duke": lambda: duke(60)}
    rows = []
    n_dev = len(jax.devices())
    for sc_name in scenarios:
        if shards > n_dev:
            rows.append((f"transport_sweep/{sc_name}", 0.0,
                         f"skipped: {n_dev} devices visible "
                         f"(set xla_force_host_platform_device_count)"))
            continue
        sc = builders[sc_name]()
        n_q = min(n_queries, len(sc["q_vids"]))
        policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05,
                                     t_thresh=.02)
        # warmup run absorbs jit compilation so the walls below compare
        # injected latency, not tracing
        _drive_serving(sc, policy, n_q, min(steps, 120), shards=shards)

        base, _, wall0, lat0 = _drive_serving(sc, policy, n_q, steps,
                                              shards=shards,
                                              guard_steady_after=steps // 2)
        hits = base.cache_hits
        p50_0, p99_0 = _tick_pcts(lat0)
        # "RTT comparable to one ranking pass": the measured p50 tick
        rtt = max(0.002, p50_0 / 1e3)
        rows.append((f"transport_sweep/{sc['name']}/baseline",
                     wall0 * 1e6 / max(n_q, 1),
                     f"wall={wall0:.2f}s cache_hits={hits} "
                     f"p50_tick={p50_0:.1f}ms rtt_unit={rtt * 1e3:.1f}ms"))
        bench_record("transport_sweep", scenario=sc["name"],
                     config="baseline", rtt_ms=0.0,
                     admitted_steps=int(base.admitted_steps),
                     unique_frames=int(base.unique_frames),
                     wall_s=round(wall0, 4), p50_tick_ms=round(p50_0, 3),
                     p99_tick_ms=round(p99_0, 3), cache_hits=int(hits))

        def run(config, transport, prefetch, rtt_s):
            eng, _, wall, lat = _drive_serving(sc, policy, n_q, steps,
                                               shards=shards,
                                               transport=transport,
                                               prefetch=prefetch,
                                               guard_steady_after=steps // 2)
            assert eng.admitted_steps == base.admitted_steps, \
                f"transport config {config} changed admitted_steps"
            assert eng.unique_frames == base.unique_frames, \
                f"transport config {config} changed unique_frames"
            c = eng.gallery.counters()
            p50, p99 = _tick_pcts(lat)
            bench_record("transport_sweep", scenario=sc["name"],
                         config=config, rtt_ms=round(rtt_s * 1e3, 3),
                         admitted_steps=int(eng.admitted_steps),
                         unique_frames=int(eng.unique_frames),
                         wall_s=round(wall, 4), p50_tick_ms=round(p50, 3),
                         p99_tick_ms=round(p99, 3),
                         remote_fetches=int(c["remote_fetches"]),
                         prefetch_hits=int(c["prefetch_hits"]),
                         prefetch_wasted=int(c["prefetch_wasted"]),
                         retries=int(c["retries"]),
                         timeouts=int(c["timeouts"]))
            return eng, wall, c, p99

        # zero-latency control for the prefetched path: same speculation
        # machinery through the in-proc transport, no injected RTT — the
        # 25% bound below isolates the *latency* cost, not the (small)
        # cost of speculating itself
        _, wall_p0, _, _ = run("prefetch_rtt0", rexcam.InProcTransport(),
                               True, 0.0)

        walls_b = {}
        for s in rtt_scales:
            lat_s = rtt * s
            tr = rexcam.FakeRpcTransport(
                default=rexcam.FaultProfile(latency=lat_s),
                timeout=4 * lat_s + 1.0)
            _, wall_b, cb, p99_b = run(f"blocking_rtt{s}x", tr, False, lat_s)
            walls_b[s] = wall_b
            rows.append((f"transport_sweep/{sc['name']}/blocking_rtt{s}x",
                         wall_b * 1e6 / max(n_q, 1),
                         f"wall={wall_b:.2f}s rtt={lat_s * 1e3:.1f}ms "
                         f"extra={wall_b - wall0:+.2f}s "
                         f"stall_floor={cb['remote_fetches'] * lat_s:.2f}s "
                         f"remote_fetches={cb['remote_fetches']} "
                         f"p99_tick={p99_b:.1f}ms"))

        tr = rexcam.FakeRpcTransport(
            default=rexcam.FaultProfile(latency=rtt), timeout=4 * rtt + 1.0)
        _, wall_p, cp, p99_p = run("prefetch_rtt1x", tr, True, rtt)
        hidden = walls_b[min(rtt_scales)] - wall_p
        rows.append((f"transport_sweep/{sc['name']}/prefetch_rtt1x",
                     wall_p * 1e6 / max(n_q, 1),
                     f"wall={wall_p:.2f}s rtt={rtt * 1e3:.1f}ms "
                     f"vs_blocking={hidden:+.2f}s "
                     f"prefetch_hits={cp['prefetch_hits']} "
                     f"wasted={cp['prefetch_wasted']} p99_tick={p99_p:.1f}ms"))

        # --- the two acceptance asserts -------------------------------
        # blocking degrades ~linearly in RTT: the slope between the
        # smallest and largest injected RTT must carry most of the
        # deterministic stall floor (remote_fetches x delta-RTT; 0.6
        # tolerates wall noise on top of the exact injected sleeps)
        lo, hi = min(rtt_scales), max(rtt_scales)
        d_rtt = rtt * (hi - lo)
        floor = 0.6 * cp["remote_fetches"] * d_rtt
        assert walls_b[hi] - walls_b[lo] >= floor, \
            f"blocking path did not degrade linearly: " \
            f"{walls_b[hi]:.2f}s @ {hi}x vs {walls_b[lo]:.2f}s @ {lo}x " \
            f"(expected >= {floor:.2f}s of injected stall)"
        # prefetch hides the latency: within 25% of the zero-latency
        # baseline (the speculation-enabled control; wall0 guards the
        # degenerate case of a slow control run)
        bound = 1.25 * max(wall_p0, wall0)
        assert wall_p <= bound, \
            f"prefetched wall {wall_p:.2f}s exceeds 1.25x the " \
            f"zero-latency baseline ({max(wall_p0, wall0):.2f}s)"
        assert cp["prefetch_hits"] >= 0.8 * max(hits, 1), \
            f"speculation mispredicted: {cp['prefetch_hits']} prefetch " \
            f"hits vs {hits} cache hits"
    return rows


# ---------------------------------------------------------------------------
# query_churn_sweep: per-round cost vs live query count under churn — the
# consolidation tentpole's headline number.
# ---------------------------------------------------------------------------

def _drive_churn(sc, policy, pool, n_queries, steps, t0, *, wave_at,
                 shards=None, consolidate=True, guard_after=None):
    """Churn-capable drive loop: submits HALF the queries up front and the
    other half mid-sweep (tick ``wave_at``, so the late joiners enter in
    replay), records the full round trace, and returns per-tick walls so
    callers can carve out a steady-state window.  ``_drive_serving`` can't
    express mid-sweep submits, hence the local loop.  Query ``i`` anchors on
    ``pool[i % len(pool)]`` — cycling a bounded pool of distinct anchor
    visits is exactly the consolidation-friendly regime the tentpole targets
    (many live queries, far fewer distinct (cam, frame) demands)."""
    from repro.analysis import RecompileGuard

    vis, gal, feats, net = sc["vis"], sc["gal"], sc["feats"], sc["net"]
    wall0 = time.perf_counter()
    eng = rexcam.serve(sc["model"], embed_fn=lambda x: x, policy=policy,
                       geo_adj=net.geo_adjacent, shards=shards,
                       consolidate=consolidate)
    eng.t = t0

    def submit(lo, hi):
        for i in range(lo, hi):
            q = pool[i % len(pool)]
            eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))

    first = max(1, n_queries // 2)
    submit(0, first)
    trace, tick_lat, matches = [], [], 0
    guard = None
    for step_i, t in enumerate(range(t0, min(t0 + steps, vis.horizon))):
        if step_i == wave_at:
            submit(first, n_queries)      # mid-sweep churn: the second wave
        if guard_after is not None and step_i == guard_after:
            guard = RecompileGuard.for_engine(
                eng, max_new=1, label=f"churn steady after tick {step_i}")
            guard.__enter__()
        frames = {}
        for c in range(net.n_cams):
            vids = gal[c, t][gal[c, t] >= 0]
            if len(vids):
                frames[c] = feats[vids]
        eng.ingest(frames)
        tk0 = time.perf_counter()
        matches += eng.tick(record_trace=trace)["matches"]
        tick_lat.append(time.perf_counter() - tk0)
    if guard is not None:
        guard.__exit__(None, None, None)
    return eng, trace, tick_lat, matches, time.perf_counter() - wall0


def query_churn_sweep(n_levels=(8, 64, 256), steps=180, shards=8,
                      pool_size=32):
    """The consolidation tentpole, measured and asserted: drive N live
    queries (N in ``n_levels``) over the duke topology with mid-sweep
    submits (a second wave joins at ``steps//3`` and replays in) and
    mid-sweep completions (``exit_t`` retires queries while others run),
    comparing the CONSOLIDATED fleet (one segment-masked ``reid_topk`` call
    per round over the fleet-global RoundPlan) against the UNCONSOLIDATED
    single engine (the per-frame reference ranking path).

    Asserted per N: the two are TRACE-IDENTICAL (same rounds, same
    admissions, same match values/tie-breaks — consolidation is a pure
    execution-plan change) with equal admitted/unique/embed totals.
    Asserted across N: fleet-wide embed calls and steady-state wall grow
    SUBLINEARLY in the live query count — cost at the largest N must stay
    under (hi/lo)x the second-largest's, because object-level consolidation
    keys the round's work on unique (camera, frame) demand, not on the
    query count.  A ``RecompileGuard(max_new=1)`` arms after warmup on the
    consolidated run: steady state must reuse compiled shapes.

    Shard counts above the visible device count degrade to the device count
    (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and
    ``JAX_PLATFORMS=cpu`` to sweep the full 8-way fleet on one host)."""
    import jax

    sc = duke(60)
    vis = sc["vis"]
    # anchor pool: distinct visits all exiting inside one short window, so
    # every query — including the late second wave — is actively ranking
    # the same stretch of live stream instead of idling on a far anchor
    cand = np.flatnonzero((vis.t_out >= 120) & (vis.t_out < 180))
    pool = cand[np.random.default_rng(7).permutation(len(cand))[:pool_size]]
    assert len(pool) >= 8, f"anchor window too sparse: {len(pool)} visits"
    t0 = int(vis.t_out[pool].min())
    # exit_t counts from the LAST sighting (matches re-anchor the search),
    # so a moderate horizon retires the pool's quieter entities mid-sweep
    # while dense-transit ones keep tracking: real completion churn
    policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                                 exit_t=45)
    wave_at = steps // 3
    guard_after = (2 * steps) // 3
    steady_from = wave_at + 5          # skip the wave's one growth compile

    n_dev = len(jax.devices())
    S = min(shards, n_dev)
    rows = []
    if S < shards:
        rows.append(("query_churn_sweep/duke/shards", 0.0,
                     f"degraded: {n_dev} devices visible, fleet runs "
                     f"shards={S} (set xla_force_host_platform_device_count)"))
    emb, wall = {}, {}
    for N in n_levels:
        eng_c, tr_c, lat_c, m_c, wall_c = _drive_churn(
            sc, policy, pool, N, steps, t0, wave_at=wave_at, shards=S,
            consolidate=True, guard_after=guard_after)
        eng_r, tr_r, lat_r, m_r, wall_r = _drive_churn(
            sc, policy, pool, N, steps, t0, wave_at=wave_at, shards=None,
            consolidate=False)
        assert trace_key(tr_c) == trace_key(tr_r), \
            f"N={N}: consolidated fleet trace diverged from the " \
            f"unconsolidated single engine"
        assert eng_c.admitted_steps == eng_r.admitted_steps
        assert eng_c.unique_frames == eng_r.unique_frames
        assert eng_c.frames_processed == eng_r.frames_processed, \
            f"N={N}: consolidation changed the embed-call count"
        done = sum(q.done for q in eng_c.queries.values())
        assert done > 0, f"N={N}: no mid-sweep completions (exit_t too big)"
        assert eng_c.replay_steps > 0, \
            f"N={N}: second wave never replayed (wave_at too early)"
        emb[N] = int(eng_c.frames_processed)
        wall[N] = float(sum(lat_c[steady_from:]))
        steady_r = float(sum(lat_r[steady_from:]))
        p50, p99 = _tick_pcts(lat_c)
        for config, eng, w, steady, lat, m in (
                ("consolidated_fleet", eng_c, wall_c, wall[N], lat_c, m_c),
                ("unconsolidated_single", eng_r, wall_r, steady_r, lat_r,
                 m_r)):
            cp50, cp99 = _tick_pcts(lat)
            bench_record("query_churn_sweep", scenario=sc["name"],
                         config=config, n_queries=N,
                         shards=S if config == "consolidated_fleet" else 0,
                         admitted_steps=int(eng.admitted_steps),
                         unique_frames=int(eng.unique_frames),
                         embed_calls=int(eng.frames_processed),
                         replay_steps=int(eng.replay_steps),
                         wall_s=round(w, 4), steady_wall_s=round(steady, 4),
                         p50_tick_ms=round(cp50, 3),
                         p99_tick_ms=round(cp99, 3), matches=int(m),
                         done=int(done))
        rows.append((f"query_churn_sweep/{sc['name']}/n{N}/consolidated",
                     wall[N] * 1e6 / max(N, 1),
                     f"embed_calls={emb[N]} steady_wall={wall[N]:.3f}s "
                     f"admitted_steps={eng_c.admitted_steps} "
                     f"unique_frames={eng_c.unique_frames} "
                     f"replay_steps={eng_c.replay_steps} done={done}/{N} "
                     f"matches={m_c} p99_tick={p99:.1f}ms trace=identical"))
        rows.append((f"query_churn_sweep/{sc['name']}/n{N}/unconsolidated",
                     steady_r * 1e6 / max(N, 1),
                     f"steady_wall={steady_r:.3f}s "
                     f"note=per-frame reference path, same trace"))
    # --- the acceptance asserts: sublinear in live query count ---------
    lo, hi = n_levels[-2], n_levels[-1]
    factor = hi / lo
    er = emb[hi] / max(emb[lo], 1)
    wr = wall[hi] / max(wall[lo], 1e-9)
    assert er < factor, \
        f"embed calls grew superlinearly: {emb[hi]} @ N={hi} vs " \
        f"{emb[lo]} @ N={lo} ({er:.2f}x >= {factor:.1f}x)"
    assert wr < factor, \
        f"steady wall grew superlinearly: {wall[hi]:.3f}s @ N={hi} vs " \
        f"{wall[lo]:.3f}s @ N={lo} ({wr:.2f}x >= {factor:.1f}x)"
    bench_record("query_churn_sweep", scenario=sc["name"],
                 config="sublinearity", n_lo=lo, n_hi=hi,
                 embed_ratio=round(er, 3), wall_ratio=round(wr, 3),
                 bound=factor, derived=True)
    rows.append((f"query_churn_sweep/{sc['name']}/sublinearity", 0.0,
                 f"sublinear=ok embed_n{hi}/n{lo}={er:.2f}x "
                 f"steady_wall_n{hi}/n{lo}={wr:.2f}x bound={factor:.1f}x"))
    return rows


# ---------------------------------------------------------------------------
# soak_130: the 130-camera soak — large synthetic topology, simultaneous
# churn + worker loss + drift, targeted row-wise re-profiling vs full
# rebuilds, paper-bracket savings asserted.
# ---------------------------------------------------------------------------

def reroute_hub_traffic(net, n_drift_hubs=4, moved_frac=0.7):
    """Localized drift injection for the city soaks: on the first
    ``n_drift_hubs`` hub rows, move ``moved_frac`` of the strongest (arterial)
    outgoing mass onto that hub's three WEAKEST leaf edges.  Those edges sit
    just below ``s_thresh`` in the profiled model, so after the shift phase 1
    prunes the now-dominant hops while the relaxed replay phase still admits
    them — rescues keep recall alive AND accumulate the §6 drift signal on
    exactly the rerouted source rows.  Travel times are untouched (the
    temporal windows stay truthful), which is what makes this a ROW-local
    drift: the right-sized response is re-profiling the hub rows, not the
    fleet-wide model.  Returns (shifted_net, drifted_row_ids)."""
    C = net.n_cams
    # hubs carry the concentrated entry mass — identifiable without the
    # generator's internals
    hubs = np.flatnonzero(net.entry > 1.0 / C)
    drift_rows = hubs[:n_drift_hubs]
    T = net.trans.copy()
    for h in drift_rows:
        row = T[h, :C]
        dests = np.flatnonzero(row)
        order = np.argsort(row[dests])
        boost = dests[order[:3]]           # weakest leaf edges
        take = dests[order[-3:]]           # strongest (corridor) edges
        moved = moved_frac * row[take].sum()
        row[take] *= 1.0 - moved_frac
        row[boost] += moved / len(boost)
    return dataclasses.replace(net, trans=T), drift_rows


@functools.lru_cache(maxsize=None)
def soak_city(n_cams=130, n_queries=12, t_shift=260, horizon=900, seed=9,
              anchor_hi=160):
    """The 130-camera soak world: ``clustered_city_network`` (neighborhood
    clusters + arterial corridors) with a LOCALIZED drift injection at
    ``t_shift`` — ``reroute_hub_traffic`` redirects four hub rows' arterial
    mass onto their weakest leaf edges, so most source-camera rows stay
    truthful and a row-targeted re-profile is the right-sized response.

    The profile model trains on DENSE pre-shift history (6000 entities):
    at 130 cameras the per-pair travel-time support is what bounds chain
    survival — each hop dies with probability ~1/(N+1) when the observed
    travel time falls past the N profiled samples, and that compounds over
    an entity's ~1/exit_p hops.  Queries come from the post-shift traffic
    and anchor EARLY (``t_out <= anchor_hi`` inside the shifted segment), so
    every tracked chain has runway across the drift and every reported
    recall is after the injected drift."""
    net = clustered_city_network(n_cams=n_cams, seed=seed)
    shifted, drift_rows = reroute_hub_traffic(net)
    hist = simulate_network(net, 6000, 4000, seed=seed + 1)
    model = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, n_cams)
    vis_a = simulate_network(net, 150, t_shift, seed=seed + 2)
    vis_b = simulate_network(shifted, 300, horizon - t_shift, seed=seed + 3)
    vis = concat_visits(vis_a, vis_b, t_shift)
    gal, _ = build_gallery(vis, 24)
    feats, _ = make_features(vis, int(vis.ent.max()) + 1,
                             FeatureParams(seed=seed + 3))
    q_b, gt_b = make_queries(vis_b, 8 * n_queries, seed=seed + 4)
    keep = np.flatnonzero(vis_b.t_out[q_b] <= anchor_hi)[:n_queries]
    q_b, gt_b = q_b[keep], gt_b[keep]
    q_vids = q_b + len(vis_a)
    gt_vids = np.where(gt_b >= 0, gt_b + len(vis_a), gt_b)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, t_shift=t_shift,
                drift_rows=drift_rows, name=f"city-{n_cams}")


def _drive_soak(sc, policy, *, shards=None, recal=None, churn_wave=None,
                lose_at=None, lose_worker=1):
    """Drive one engine through the soak's full churn program: half the
    queries submit at t0, the rest ``churn_wave`` steps in (replaying to
    catch up), and ``lose_at`` kills a fleet worker mid-run.  Returns
    (engine, wall_s, per-tick latencies)."""
    vis, gal, feats, net = sc["vis"], sc["gal"], sc["feats"], sc["net"]
    q_vids = sc["q_vids"]
    wall0 = time.perf_counter()
    eng = rexcam.serve(sc["model"], embed_fn=lambda x: x, policy=policy,
                       geo_adj=net.geo_adjacent, shards=shards,
                       recalibrate=recal,
                       visit_source=rexcam.visits_window_source(vis)
                       if recal is not None else None)
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    first = len(q_vids) if churn_wave is None else max(1, len(q_vids) // 2)
    for i in range(first):
        q = q_vids[i]
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    tick_lat = []
    for step, t in enumerate(range(t0, vis.horizon)):
        if churn_wave is not None and step == churn_wave:
            for j in range(first, len(q_vids)):
                q = q_vids[j]
                eng.submit_query(j, feats[q], int(vis.cam[q]),
                                 int(vis.t_out[q]))
        if lose_at is not None and step == lose_at and shards is not None:
            eng.lose_worker(lose_worker)
        frames = {}
        for c in range(net.n_cams):
            vids = gal[c, t][gal[c, t] >= 0]
            if len(vids):
                frames[c] = feats[vids]
        eng.ingest(frames)
        tk0 = time.perf_counter()
        eng.tick()
        tick_lat.append(time.perf_counter() - tk0)
    return eng, time.perf_counter() - wall0, tick_lat


def soak_130(n_queries=12, shards=8, churn_wave=60, lose_at=120):
    """The 130-camera soak (paper §8.1's simulated-scale bracket, 23x-38x):
    drive the clustered city topology through query churn, mid-run worker
    loss and drift injection SIMULTANEOUSLY, under three configurations —

      * ``exhaustive``      scheme="all" single engine (the cost baseline
                            and the recall ceiling: no model to go stale);
      * ``targeted_fleet``  rexcam on the sharded fleet with row-TARGETED
                            recalibration (merge_reprofiled_rows) + loss;
      * ``full_single``     rexcam with FULL-rebuild recalibration, same
                            churn program (the re-profiling cost baseline).

    Asserted, per the acceptance bracket: admitted-steps savings vs
    exhaustive >= 20x at recall within 5% of exhaustive; targeted recall
    matches full-rebuild recall (within 2%) while re-profiling only a
    strict subset of rows per swap (profiler call accounting) at lower
    per-swap profiling wall.  Emits one BENCH_soak_130.json record per
    configuration plus a derived gate row — the persistent perf trajectory
    CI uploads per commit."""
    import jax

    sc = soak_city(n_queries=n_queries)
    vis, net = sc["vis"], sc["net"]
    q_vids, gt_vids = sc["q_vids"], sc["gt_vids"]
    C = net.n_cams
    n_q = len(q_vids)
    policy = rexcam.SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                                 exit_t=120)
    exhaustive = rexcam.SearchPolicy(scheme="all", exit_t=120)
    # city-scale trigger: localized drift on a handful of rows — a dense
    # prior keeps normalized per-pair scores small, so the trip gates on the
    # sustained rescue count; the WIDE re-profiling window matters — merged
    # rows need enough live samples that their travel-time support does not
    # regress the dense prior they replace; row_threshold keeps the targeted
    # selection to the spiking rows
    recal_kw = dict(drift_threshold=.01, min_rescues=5, cooldown=150,
                    poll_every=20, window=450)
    recal_t = rexcam.RecalibrationPolicy(targeted=True, row_threshold=.05,
                                         **recal_kw)
    recal_f = rexcam.RecalibrationPolicy(targeted=False, **recal_kw)

    n_dev = len(jax.devices())
    S = min(shards, n_dev)
    rows = []
    if S < shards:
        rows.append(("soak_130/shards", 0.0,
                     f"degraded: {n_dev} devices visible, fleet runs "
                     f"shards={S} (set xla_force_host_platform_device_count)"))

    def record(config, eng, wall, lat, recall, **extra):
        p50, p99 = _tick_pcts(lat)
        bench_record("soak_130", scenario=sc["name"], config=config,
                     n_cams=C, n_queries=n_q,
                     admitted_steps=int(eng.admitted_steps),
                     unique_frames=int(eng.unique_frames),
                     replay_steps=int(eng.replay_steps),
                     wall_s=round(wall, 4), p50_tick_ms=round(p50, 3),
                     p99_tick_ms=round(p99, 3), recall=round(recall, 4),
                     epoch=int(eng.model_epoch), **extra)

    ex, wall_e, lat_e = _drive_soak(sc, exhaustive, churn_wave=churn_wave)
    r_ex = _serving_recall(ex, vis, q_vids, gt_vids)
    record("exhaustive", ex, wall_e, lat_e, r_ex, shards=0)
    rows.append((f"soak_130/{sc['name']}/exhaustive",
                 wall_e * 1e6 / n_q,
                 f"recall={r_ex:.2f} admitted_steps={ex.admitted_steps} "
                 f"note=all-camera baseline, the recall ceiling"))

    fleet_shards = S if S >= 2 else None
    tg, wall_t, lat_t = _drive_soak(
        sc, policy, shards=fleet_shards, recal=recal_t,
        churn_wave=churn_wave,
        lose_at=lose_at if fleet_shards else None, lose_worker=1)
    r_tg = _serving_recall(tg, vis, q_vids, gt_vids)
    ctl_t = tg.recal
    record("targeted_fleet", tg, wall_t, lat_t, r_tg,
           shards=fleet_shards or 1, swaps=ctl_t.targeted_swaps,
           rows_reprofiled=int(ctl_t.rows_reprofiled),
           profile_wall_s=round(ctl_t.profile_wall, 4))

    fu, wall_f, lat_f = _drive_soak(sc, policy, recal=recal_f,
                                    churn_wave=churn_wave)
    r_fu = _serving_recall(fu, vis, q_vids, gt_vids)
    ctl_f = fu.recal
    record("full_single", fu, wall_f, lat_f, r_fu, shards=0,
           swaps=ctl_f.full_rebuilds,
           rows_reprofiled=int(ctl_f.rows_reprofiled),
           profile_wall_s=round(ctl_f.profile_wall, 4))

    # --- the acceptance gate ------------------------------------------
    savings = ex.admitted_steps / max(tg.admitted_steps, 1)
    assert savings >= 20.0, \
        f"soak_130: savings {savings:.1f}x below the 20x floor " \
        f"(paper brackets 23x-38x at city scale)"
    assert r_tg >= r_ex - 0.05, \
        f"soak_130: targeted recall {r_tg:.3f} more than 5% below the " \
        f"exhaustive ceiling {r_ex:.3f}"
    # the soak actually soaked: churn replayed, the fleet rebalanced, and
    # drift tripped at least one swap under both re-profiling modes
    assert tg.replay_steps > 0, "soak_130: late wave never replayed"
    if fleet_shards:
        assert tg.rebalances == 1, "soak_130: worker loss never rebalanced"
    assert ctl_t.targeted_swaps >= 1 and ctl_t.full_rebuilds == 0
    assert ctl_f.full_rebuilds >= 1 and ctl_f.targeted_swaps == 0
    # targeted re-profiling: same recall as full rebuilds while touching a
    # strict subset of rows, at lower per-swap profiling wall
    assert r_tg >= r_fu - 0.02, \
        f"soak_130: targeted recall {r_tg:.3f} fell behind full-rebuild " \
        f"recall {r_fu:.3f}"
    assert ctl_t.rows_reprofiled < C * ctl_t.targeted_swaps, \
        f"soak_130: targeted recal touched {ctl_t.rows_reprofiled} rows " \
        f"over {ctl_t.targeted_swaps} swaps — no better than full (C={C})"
    assert ctl_f.rows_reprofiled == C * ctl_f.full_rebuilds
    per_t = ctl_t.profile_wall / ctl_t.targeted_swaps
    per_f = ctl_f.profile_wall / ctl_f.full_rebuilds
    assert per_t < per_f, \
        f"soak_130: targeted per-swap profiling wall {per_t * 1e3:.1f}ms " \
        f"not below full-rebuild {per_f * 1e3:.1f}ms"

    bench_record("soak_130", scenario=sc["name"], config="gate",
                 savings_x=round(savings, 2),
                 recall_exhaustive=round(r_ex, 4),
                 recall_targeted=round(r_tg, 4),
                 recall_full=round(r_fu, 4),
                 rows_per_targeted_swap=round(
                     ctl_t.rows_reprofiled / ctl_t.targeted_swaps, 1),
                 profile_ms_targeted=round(per_t * 1e3, 2),
                 profile_ms_full=round(per_f * 1e3, 2), derived=True)
    rows.append((f"soak_130/{sc['name']}/gate", 0.0,
                 f"soak_gate=ok savings={savings:.1f}x "
                 f"recall_ex={r_ex:.2f} recall_targeted={r_tg:.2f} "
                 f"recall_full={r_fu:.2f} "
                 f"rows/swap={ctl_t.rows_reprofiled / ctl_t.targeted_swaps:.0f}"
                 f"/{C} profile_ms={per_t * 1e3:.1f}vs{per_f * 1e3:.1f}"))
    return rows
