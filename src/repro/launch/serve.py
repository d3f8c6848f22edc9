"""Serving driver: ReXCam-filtered cross-camera analytics on live streams.

Replays a calibrated camera-network simulation through the ServingEngine via
the ``repro.api`` facade: one SearchPolicy decides which (camera, frame)
pairs reach the inference plane; the engine vector-admits all queries at
once, batches and embeds the deduplicated frames (feature oracle or a smoke
backbone), ranks with the re-id kernel semantics, and replays the FrameStore
ring buffer when a query escalates to phase 2.

  PYTHONPATH=src python -m repro.launch.serve --queries 8 --steps 600

``--shards k`` runs the sharded fleet instead (shard_map over the query
axis, trace-identical to the single engine) and prints per-shard cost.  On
a CPU host, fake the devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    PYTHONPATH=src python -m repro.launch.serve --queries 8 --shards 4

``--transport fake --rtt 0.01 --prefetch`` (fleet only) routes every
owner-shard gallery fetch through a ``FakeRpcTransport`` with injected
latency/jitter/drop and turns on the double-buffered speculative prefetch;
the transport-plane line prints remote fetches, prefetch hits/waste,
retries, timeouts and dead peers.

``--recalibrate`` closes the paper's §6 drift loop: a
``RecalibrationController`` watches the engine's live rescue matrix and
hot-swaps a model re-profiled from the recent window when the drift score
trips the trigger (knobs: ``--drift-threshold``, ``--recal-cooldown``,
``--recal-window``); swap events and the final model epoch are printed.
"""
from __future__ import annotations

import argparse
import os
import time

import jax

from repro import api as rexcam
from repro.core import build_gallery, duke_like_network, simulate_network
from repro.core.features import FeatureParams, make_features
from repro.core.simulate import tile_index

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path, so every
    process of this checkout reuses what an earlier one compiled.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no
    directory is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Either way every program is cached, however
    fast it compiled: the serving steps compile in well under JAX's default
    one-second floor, which would keep none of them (a
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` in the environment still
    wins).  Returns the directory in use."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_line() -> str:
    """The device JAX runs on, as every run should report it."""
    d = jax.devices()
    return (f"device: platform={d[0].platform} kind={d[0].device_kind} "
            f"count={len(d)}")


def duke_world(n_queries: int, tile_grid: int = 0) -> dict:
    """The simulated 8-camera Duke-like campus this CLI replays: 1,500
    identities over a 3,000 s horizon, up to 24 detections per
    camera-step, the model profiled on the first 2,000 s (``tile_grid=T``
    also learns T x T entry-region masks), and ``n_queries`` query
    sightings drawn with seed 1."""
    net = duke_like_network()
    vis = simulate_network(net, 1500, 3000, seed=0)
    gal, _ = build_gallery(vis, 24)
    model = rexcam.profile(vis, time_limit=2000, tile_grid=tile_grid)
    feats, _ = make_features(vis, 1500, FeatureParams())
    q_vids, _ = rexcam.make_queries(vis, n_queries, seed=1)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids)


def serve_world(world: dict, **serve_kw):
    """Serve ``world`` (``duke_world``'s keys) through ``repro.api.serve``
    with the identity embedder over its precomputed features, start the
    clock at the earliest query sighting and submit every query there.
    ``recalibrate=`` re-profiles from the world's own visits."""
    vis, feats, q_vids = world["vis"], world["feats"], world["q_vids"]
    if serve_kw.get("recalibrate"):
        serve_kw["visit_source"] = rexcam.visits_window_source(vis)
    eng = rexcam.serve(world["model"], embed_fn=lambda x: x,
                       geo_adj=world["net"].geo_adjacent, **serve_kw)
    eng.t = int(vis.t_out[q_vids].min())
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    return eng


def ingest_tick(eng, world: dict, t: int) -> None:
    """Ingest step ``t`` of the world's live stream: every camera's
    detections as feature rows, plus their sub-frame tile labels when the
    engine serves a tile grid."""
    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    frames, tiles = {}, {}
    for c in range(vis.n_cams):
        vids = gal[c, t]
        vids = vids[vids >= 0]
        if len(vids):
            frames[c] = feats[vids]
            if eng.tile_grid > 0:
                tiles[c] = tile_index(vis.tile_xy[vids], eng.tile_grid)
    if eng.tile_grid > 0:
        eng.ingest(frames, tiles)
    else:
        eng.ingest(frames)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--s-thresh", type=float, default=0.05)
    ap.add_argument("--t-thresh", type=float, default=0.02)
    ap.add_argument("--scheme", default="rexcam",
                    choices=["rexcam", "all", "geo", "spatial_only"])
    ap.add_argument("--shards", type=int, default=None,
                    help="partition the query axis over this many devices "
                         "(default: single-process engine)")
    ap.add_argument("--gallery", default="auto",
                    choices=["auto", "local", "sharded"],
                    help="embedding plane: auto (local for one engine, "
                         "fleet-shared sharded store for --shards), local "
                         "(replicated baseline) or sharded (fleet only)")
    ap.add_argument("--topk", type=int, default=1,
                    help="surface the k best (value, cam, frame) candidate "
                         "bands per round in trace records (argmax path "
                         "unchanged)")
    ap.add_argument("--topk-rerank", action="store_true",
                    help="§5.2 top-k confidence re-ranking: passing bands "
                         "vote by summed score per camera and the match "
                         "re-anchors to the winning camera's best band "
                         "(bit-identical to argmax at --topk 1)")
    ap.add_argument("--tile-grid", type=int, default=0,
                    help="sub-frame spatial admission: T > 0 profiles per "
                         "camera-pair entry-region masks on a TxT tile grid "
                         "and serves through the tile-masked kernel, "
                         "scoring only detections inside admitted tiles")
    ap.add_argument("--transport", default="none",
                    choices=["none", "inproc", "fake"],
                    help="gallery fetch plane (fleet only): none (direct "
                         "zero-copy reads), inproc (same behavior through "
                         "the Transport contract, counters tick) or fake "
                         "(FakeRpcTransport with --rtt/--jitter/--drop "
                         "injected per fetch, timeout/retry/backoff)")
    ap.add_argument("--rtt", type=float, default=0.005,
                    help="injected one-way fetch latency in seconds "
                         "(--transport fake)")
    ap.add_argument("--jitter", type=float, default=0.0,
                    help="uniform extra latency bound in seconds "
                         "(--transport fake)")
    ap.add_argument("--drop", type=float, default=0.0,
                    help="per-attempt drop probability; dropped fetches "
                         "time out and retry with backoff (--transport fake)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffered speculative fetch: issue round "
                         "N+1's predicted gallery reads at the end of round "
                         "N so transport latency hides behind compute")
    ap.add_argument("--recalibrate", action="store_true",
                    help="close the §6 drift loop: watch the live rescue "
                         "matrix and hot-swap a re-profiled model when the "
                         "drift score trips the trigger")
    ap.add_argument("--drift-threshold", type=float, default=0.1,
                    help="recalibration trigger: max drift_score to trip at")
    ap.add_argument("--recal-cooldown", type=int, default=240,
                    help="min ticks between model swaps (hysteresis)")
    ap.add_argument("--recal-window", type=int, default=1200,
                    help="sliding re-profile window (recent steps)")
    args = ap.parse_args()
    use_compile_cache()
    print(device_line())

    world = duke_world(args.queries, args.tile_grid)
    net, vis, q_vids = world["net"], world["vis"], world["q_vids"]
    policy = rexcam.SearchPolicy(scheme=args.scheme, s_thresh=args.s_thresh,
                                 t_thresh=args.t_thresh)
    recal = rexcam.RecalibrationPolicy(
        drift_threshold=args.drift_threshold, cooldown=args.recal_cooldown,
        window=args.recal_window) if args.recalibrate else None
    if args.transport == "fake":
        transport = rexcam.FakeRpcTransport(
            default=rexcam.FaultProfile(latency=args.rtt, jitter=args.jitter,
                                        drop=args.drop),
            timeout=max(4 * (args.rtt + args.jitter), 1.0))
    else:
        transport = None if args.transport == "none" else args.transport
    eng = serve_world(world, policy=policy, shards=args.shards,
                      gallery=args.gallery, topk=args.topk,
                      transport=transport, prefetch=args.prefetch,
                      tile_grid=args.tile_grid,
                      topk_rerank=args.topk_rerank, recalibrate=recal)
    t0 = eng.t

    wall0 = time.time()
    matches = 0
    for t in range(t0, min(t0 + args.steps, vis.horizon)):
        ingest_tick(eng, world, t)
        stats = eng.tick()
        matches += stats["matches"]
    wall = time.time() - wall0

    # two cost conventions (don't mix them): admitted_steps is per-query
    # camera-steps (comparable with the tracker / policy_sweep); the frame
    # counts are the serving plane's deduplicated inference load
    naive_steps = args.steps * net.n_cams * len(q_vids)
    naive_frames = args.steps * net.n_cams
    print(f"steps={args.steps} queries={args.queries} scheme={policy.scheme}")
    print(f"admission: {eng.admitted_steps} camera-steps "
          f"(naive all-camera: {naive_steps}; "
          f"savings {naive_steps/max(eng.admitted_steps,1):.1f}x)")
    print(f"inference plane: {eng.unique_frames} unique frames "
          f"({eng.frames_processed} embedded + {eng.cache_hits} cache-hot; "
          f"dedup {eng.admitted_steps/max(eng.unique_frames,1):.1f}x; "
          f"naive per-camera: {naive_frames}; "
          f"savings {naive_frames/max(eng.frames_processed,1):.1f}x)")
    if args.tile_grid > 0:
        TT = args.tile_grid * args.tile_grid
        base_tiles = TT * eng.admitted_steps
        print(f"spatial plane [T={args.tile_grid}]: {eng.admitted_tiles} "
              f"admitted tiles of {base_tiles} camera-granular "
              f"(pixel-load savings "
              f"{base_tiles/max(eng.admitted_tiles,1):.1f}x; "
              f"{eng.unique_tiles} deduplicated of "
              f"{TT * eng.unique_frames})")
    print(f"matches flagged: {matches} "
          f"(replay rescues: {sum(q.rescued for q in eng.queries.values())}, "
          f"replay misses past retention: {eng.replay_misses})")
    print(f"frame-store residency: {eng.store.memory_frames()} frames "
          f"(retention {eng.cfg.retention}s — paper §5.3 'last few minutes')")
    g = eng.gallery_report()
    print(f"gallery plane [{g['kind']}]: {g['cached']} blocks resident "
          f"({g['bytes']} bytes), {g['hits']} hits / {g['misses']} misses, "
          f"{g['evictions']} evictions")
    if args.transport != "none" or args.prefetch:
        c = eng.gallery.counters()
        kind = getattr(getattr(eng.gallery, "transport", None), "kind",
                       "local")
        print(f"transport plane [{kind}]: {c['remote_fetches']} remote "
              f"fetches ({c['prefetch_hits']} served by prefetch, "
              f"{c['prefetch_wasted']} wasted speculations), "
              f"{c['retries']} retries, {c['timeouts']} timeouts, "
              f"{c.get('dead_peers', 0)} dead peers")
    print(f"host reads: {eng.readback_bytes} bytes copied back from the "
          f"device over {eng.ticks} ticks")
    print(f"wall: {wall:.2f}s ({args.steps/max(wall,1e-9):.0f} steps/s)")
    if args.recalibrate:
        ev = eng.recal.events
        print(f"recalibration [epoch {eng.model_epoch}]: {len(ev)} swaps, "
              f"{len(eng.recal.polls)} polls "
              f"(threshold {args.drift_threshold}, "
              f"cooldown {args.recal_cooldown}, window {args.recal_window})")
        for e in ev:
            print(f"  t={e['t']}: epoch {e['epoch']} "
                  f"(score {e['score']:.2f}, {e['rescues']} rescues, "
                  f"re-profiled {e['visits']} visits in "
                  f"[{e['window'][0]}, {e['window'][1]}))")
    if args.shards is not None:
        # per-shard demand is shard-LOCAL dedup: a frame two shards both
        # want counts once per shard here but once in the engine totals;
        # owned_frames is each worker's slice of the fleet-global dedup
        # (sums to the engine total when the gallery is sharded)
        print(f"fleet: {eng.n_shards} shards (data axis), "
              f"{eng.rebalances} rebalances")
        per_worker = g.get("per_worker", {})
        for row in eng.shard_report():
            state = "live" if row["alive"] else "lost"
            gw = per_worker.get(row["worker"])
            gal = (f" gallery={gw['blocks']} blocks/{gw['bytes']}B "
                   f"({gw['cameras']} cams)" if gw else "")
            print(f"  {row['worker']} [{state}]: {row['queries']} queries, "
                  f"admitted_steps={row['admitted_steps']} "
                  f"unique_frames={row['unique_frames']} "
                  f"owned_frames={row['owned_frames']} "
                  f"query_rounds={row['query_rounds']}{gal}")
    for qid, q in eng.queries.items():
        lag = max(eng.t - 1 - q.f_curr, 0)
        state = "done" if q.done else f"tracking (phase {q.phase}, lag {lag}s)"
        print(f"  query {qid}: {len(q.matches)} matches, {state}")


if __name__ == "__main__":
    main()
