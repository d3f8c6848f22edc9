import collections
import dataclasses
import functools
import os
import sys

# NOTE: no xla_force_host_platform_device_count here — smoke tests and benches
# must see 1 device.  Sharding tests spawn subprocesses that set the flag.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# Fleet differential harness (tests/test_sharded_engine.py + its subprocess
# re-entry).  Everything below is import-safe — jax/repro imports stay inside
# the functions so collecting this conftest never initializes a jax backend.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_serving_world(n_entities=100, horizon=360, seed=0, n_queries=4):
    """Small duke-like world for engine differential tests (process-cached).

    Returns plain arrays (model, visits, gallery, features, query vids) —
    the same scenario shape the benchmarks use, sized for tick-by-tick
    double (single + fleet) runs."""
    from repro.core import (build_gallery, build_model, duke_like_network,
                            simulate_network)
    from repro.core.features import FeatureParams, make_features
    from repro.core.tracker import make_queries

    net = duke_like_network()
    vis = simulate_network(net, n_entities, horizon, seed=seed)
    gal, _ = build_gallery(vis, 16)
    model = build_model(vis.ent, vis.cam, vis.t_in, vis.t_out, net.n_cams,
                        time_limit=int(horizon * 0.7))
    feats, _ = make_features(vis, n_entities, FeatureParams(seed=seed))
    q_vids, gt_vids = make_queries(vis, n_queries, seed=seed + 1)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids)


def make_drifted_world(n_entities=80, t_shift=150, horizon=420, seed=0,
                       n_queries=6):
    """Serving world whose live stream SHIFTS topology mid-run (a camera
    permutation at ``t_shift``) while the profile model stays frozen on the
    pre-shift world — the §6 drift injection the recalibration differential
    runs on.  Queries are drawn from the post-shift traffic."""
    from repro.core import (build_gallery, build_model, concat_visits,
                            duke_like_network, permute_network,
                            simulate_network)
    from repro.core.features import FeatureParams, make_features
    from repro.core.tracker import make_queries

    net = duke_like_network()
    shifted = permute_network(net, np.roll(np.arange(net.n_cams), 3))
    hist = simulate_network(net, 400, 900, seed=seed + 50)
    model = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, net.n_cams)
    vis_a = simulate_network(net, n_entities // 2, t_shift, seed=seed + 51)
    vis_b = simulate_network(shifted, n_entities, horizon - t_shift,
                             seed=seed + 52)
    vis = concat_visits(vis_a, vis_b, t_shift)
    gal, _ = build_gallery(vis, 16)
    feats, _ = make_features(vis, int(vis.ent.max()) + 1,
                             FeatureParams(seed=seed + 52))
    q_b, gt_b = make_queries(vis_b, n_queries, seed=seed + 53)
    q_vids = q_b + len(vis_a)
    gt_vids = np.where(gt_b >= 0, gt_b + len(vis_a), gt_b)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, t_shift=t_shift)


def make_soak_world(n_cams=32, n_entities=90, t_shift=160, horizon=480,
                    seed=0, n_queries=8, anchor_hi=140):
    """Scaled-down 130-camera soak world: the clustered city topology
    (``clustered_city_network``) with a LOCALIZED mid-run drift — two hub
    rows' arterial mass is rerouted onto their weakest leaf edges (edges
    that sit below ``s_thresh`` in the profiled model but above the relaxed
    replay threshold), so phase 1 misses the shifted hops while phase-2
    rescues keep the chains alive AND pile the §6 drift signal onto exactly
    those source rows.  Most rows stay truthful, so a row-targeted
    re-profile is the right response.  The profile trains on dense history
    (travel-time support bounds chain survival at this scale) and queries
    anchor early in the post-shift traffic so every chain has runway across
    the drift."""
    from repro.core import (build_gallery, build_model,
                            clustered_city_network, concat_visits,
                            simulate_network)
    from repro.core.features import FeatureParams, make_features
    from repro.core.tracker import make_queries

    # 3 big neighborhoods: the hub fanout must be wide enough that the
    # weakest leaf edges straddle s_thresh (the same regime the 130-camera
    # city hits naturally) — that is what makes the rerouted hops phase-2
    # rescues rather than silent phase-1 admits
    net = clustered_city_network(n_cams=n_cams, n_clusters=3, seed=seed + 40)
    hubs = np.flatnonzero(net.entry > 1.0 / n_cams)
    drift_rows = hubs[:2]
    T = net.trans.copy()
    for h in drift_rows:
        row = T[h, :n_cams]
        dests = np.flatnonzero(row)
        order = np.argsort(row[dests])
        boost, take = dests[order[:3]], dests[order[-3:]]
        moved = 0.7 * row[take].sum()
        row[take] *= 0.3
        row[boost] += moved / len(boost)
    shifted = dataclasses.replace(net, trans=T)
    hist = simulate_network(net, n_entities * 16, 2000, seed=seed + 50)
    model = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, n_cams)
    vis_a = simulate_network(net, n_entities // 2, t_shift, seed=seed + 51)
    vis_b = simulate_network(shifted, n_entities, horizon - t_shift,
                             seed=seed + 52)
    vis = concat_visits(vis_a, vis_b, t_shift)
    gal, _ = build_gallery(vis, 16)
    feats, _ = make_features(vis, int(vis.ent.max()) + 1,
                             FeatureParams(seed=seed + 52))
    q_b, gt_b = make_queries(vis_b, 8 * n_queries, seed=seed + 53)
    keep = np.flatnonzero(vis_b.t_out[q_b] <= anchor_hi)[:n_queries]
    q_b, gt_b = q_b[keep], gt_b[keep]
    q_vids = q_b + len(vis_a)
    gt_vids = np.where(gt_b >= 0, gt_b + len(vis_a), gt_b)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids, t_shift=t_shift,
                drift_rows=drift_rows)


def drive_serving_trace(world, policy, *, shards=None, lose_at=None,
                        lose_worker=0, extra_ticks=500, gallery="auto",
                        topk=1, embed_fn=None, recalibrate=None,
                        transport=None, prefetch=False, consolidate=True,
                        tile_grid=0, topk_rerank=False, model=None,
                        churn_wave=None):
    """Run one engine (single-process when ``shards`` is None, else the
    sharded fleet) over the world's live stream and return (engine, trace,
    summary).  ``lose_at`` kills one worker that many ticks into the run —
    the fleet rebalances; the single engine ignores it.  ``gallery`` picks
    the embedding plane ("auto": local for one engine, fleet-shared sharded
    store for the fleet).  ``recalibrate`` (a RecalibrationPolicy) attaches
    the §6 drift loop, re-profiling from the world's ground-truth visits.
    ``transport`` routes the fleet's gallery fetches through a
    ``runtime.transport.Transport`` — pass a zero-arg FACTORY (callable or
    class) so every drive gets fresh transport state; ``prefetch`` turns on
    the double-buffered speculative fetch pipeline.  ``tile_grid=T > 0``
    serves through the sub-frame spatial admission plane (per-detection
    tile labels from the world's ground-truth positions ride along with
    every ingest); ``model`` overrides the world's profile (e.g. a
    tile-carrying re-profile of the same visits).  ``churn_wave`` splits the
    submits: the first half goes in at t0 and the rest that many steps in
    (the late wave replays to catch up) — query churn for the soak cases."""
    from repro import api as rexcam

    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    q_vids = world["q_vids"]
    if callable(transport):
        transport = transport()
    vis_tiles = None
    if tile_grid > 0:
        from repro.core.simulate import tile_index
        vis_tiles = tile_index(vis.tile_xy, tile_grid)
    eng = rexcam.serve(world["model"] if model is None else model,
                       embed_fn=embed_fn if embed_fn is not None
                       else lambda x: x,
                       policy=policy,
                       geo_adj=world["net"].geo_adjacent, shards=shards,
                       gallery=gallery, topk=topk, recalibrate=recalibrate,
                       transport=transport, prefetch=prefetch,
                       consolidate=consolidate, tile_grid=tile_grid,
                       topk_rerank=topk_rerank,
                       visit_source=rexcam.visits_window_source(vis)
                       if recalibrate is not None else None)
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    first = len(q_vids) if churn_wave is None else max(1, len(q_vids) // 2)
    for i in range(first):
        q = q_vids[i]
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    trace = []
    for step, t in enumerate(range(t0, vis.horizon + extra_ticks)):
        if churn_wave is not None and step == churn_wave:
            for j in range(first, len(q_vids)):
                q = q_vids[j]
                eng.submit_query(j, feats[q], int(vis.cam[q]),
                                 int(vis.t_out[q]))
        if lose_at is not None and step == lose_at and shards is not None:
            eng.lose_worker(lose_worker)
        if t < vis.horizon:
            frames, tiles = {}, {}
            for c in range(vis.n_cams):
                vids = gal[c, t][gal[c, t] >= 0]
                if len(vids):
                    frames[c] = feats[vids]
                    if vis_tiles is not None:
                        tiles[c] = vis_tiles[vids]
            if tile_grid > 0:
                eng.ingest(frames, tiles)
            else:
                eng.ingest(frames)
        eng.tick(record_trace=trace)
        if all(q.done for q in eng.queries.values()) and \
                (churn_wave is None or step >= churn_wave):
            break
    summary = dict(
        admitted_steps=eng.admitted_steps, unique_frames=eng.unique_frames,
        content_steps=eng.content_steps, replay_steps=eng.replay_steps,
        rescue_pairs=eng.rescue_pairs.copy(),
        model_epoch=eng.model_epoch, model_swaps=list(eng.model_swaps),
        per_query=[(q.matches, q.rescued, q.done, q.phase, q.f_curr)
                   for q in eng.queries.values()])
    return eng, trace, summary


def trace_key(trace):
    """The engine's canonical per-round key (``engine.trace_key``): what
    two runs must agree on to be trace-identical."""
    from repro.runtime.engine import trace_key as key
    return key(trace)


def assert_fleet_trace_identical(world, policy, shards, *, lose_at=None,
                                 lose_worker=0, single=None, gallery="auto",
                                 recalibrate=None, transport=None,
                                 prefetch=False, consolidate=True,
                                 single_consolidate=True, churn_wave=None):
    """THE differential assertion: the sharded fleet's rounds are
    bit-identical to the single-process engine's — admissions, match
    indices/values (tie-breaks included), rescue attribution, model-epoch
    boundaries (recalibration swaps land on the same round), and both
    cost conventions.  Returns (fleet engine, single (trace, summary)) so
    callers can layer fleet-specific asserts on top; pass ``single`` (a
    prior return) to reuse the reference run across shard counts.
    ``transport``/``prefetch`` apply to the FLEET run only (the reference
    single engine has no remote owners) — transport must never change what
    is ranked, only when it arrives, so the assertion is unchanged."""
    from repro.runtime.gallery import ShardedGalleryStore

    if single is None:
        _, ref_trace, ref_sum = drive_serving_trace(
            world, policy, recalibrate=recalibrate,
            consolidate=single_consolidate, churn_wave=churn_wave)
        single = (ref_trace, ref_sum)
    ref_trace, ref_sum = single
    eng, fl_trace, fl_sum = drive_serving_trace(
        world, policy, shards=shards, lose_at=lose_at,
        lose_worker=lose_worker, gallery=gallery, recalibrate=recalibrate,
        transport=transport, prefetch=prefetch, consolidate=consolidate,
        churn_wave=churn_wave)
    assert trace_key(fl_trace) == trace_key(ref_trace), \
        f"fleet (shards={shards}) trace diverged from the single engine"
    assert fl_sum["admitted_steps"] == ref_sum["admitted_steps"]
    assert fl_sum["unique_frames"] == ref_sum["unique_frames"]
    assert fl_sum["content_steps"] == ref_sum["content_steps"]
    assert fl_sum["replay_steps"] == ref_sum["replay_steps"]
    np.testing.assert_array_equal(fl_sum["rescue_pairs"],
                                  ref_sum["rescue_pairs"])
    assert fl_sum["model_epoch"] == ref_sum["model_epoch"]
    assert fl_sum["model_swaps"] == ref_sum["model_swaps"], \
        "recalibration swaps did not land on the same ticks fleet-wide"
    assert fl_sum["per_query"] == ref_sum["per_query"]
    # per-shard accounting must tile the fleet totals (admitted) / at least
    # cover them (unique frames are shard-local dedup, so >= the global);
    # owner attribution tiles the fleet-GLOBAL dedup set exactly
    rep = eng.shard_report()
    assert sum(r["admitted_steps"] for r in rep) == eng.admitted_steps
    assert sum(r["unique_frames"] for r in rep) >= eng.unique_frames
    if isinstance(eng.gallery, ShardedGalleryStore):
        assert sum(r["owned_frames"] for r in rep) == eng.unique_frames
    return eng, single


def _require_devices(n):
    import jax
    assert len(jax.devices()) >= n, (
        f"need {n} devices, have {len(jax.devices())} — set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu")


def fleet_case_shard_counts(shard_counts=(1, 2, 4, 8), n_queries=5, seed=0):
    """Differential case: every shard count in ``shard_counts`` is
    trace-identical to the single engine — with a query count NOT divisible
    by any shard count > 1 (5 % {2,4,8} != 0, so shard blocks carry ragged
    padding), then once more with an exactly-divisible count."""
    from repro.core.policy import SearchPolicy

    _require_devices(max(shard_counts))
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    single = None
    for shards in shard_counts:
        eng, single = assert_fleet_trace_identical(world, policy, shards,
                                                   single=single)
        # submit-time placement is least-loaded: never more than one query
        # of imbalance between live workers (counted over the placement map,
        # which survives query completion — shard_report loads go to 0)
        counts = collections.Counter(eng._placement.values())
        loads = [counts.get(r["worker"], 0)
                 for r in eng.shard_report() if r["alive"]]
        assert max(loads) - min(loads) <= 1, loads
    divisible = make_serving_world(seed=seed + 10, n_queries=4)
    assert_fleet_trace_identical(world=divisible, policy=policy, shards=4)


def fleet_case_worker_loss(shards=4, lose_worker=1, lose_at=50,
                           n_queries=7, seed=1):
    """Differential case: killing a worker mid-run shrinks the data axis to
    ``shards - 1`` and re-scatters its queries — and the trace stays
    bit-identical to the single engine (placement never changes results)."""
    from repro.core.policy import SearchPolicy

    _require_devices(shards)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    eng, _ = assert_fleet_trace_identical(world, policy, shards,
                                          lose_at=lose_at,
                                          lose_worker=lose_worker)
    assert eng.n_shards == shards - 1
    assert eng.rebalances == 1
    rep = {r["worker"]: r for r in eng.shard_report()}
    lost = f"w{lose_worker}"
    assert not rep[lost]["alive"]
    assert rep[lost]["admitted_steps"] > 0, \
        "the lost worker never served a round — lose_at fired too early"
    live = {w for w, r in rep.items() if r["alive"]}
    assert set(eng._placement.values()) <= live, "orphans not re-scattered"
    # the gallery plane re-homed alongside the query re-scatter: the lost
    # worker owns no cameras anymore (fleet default gallery is sharded)
    assert eng.gallery.kind == "sharded"
    assert lost not in set(eng.gallery._owner.values())


def fleet_case_consolidation(shard_counts=(1, 2, 4, 8), n_queries=5, seed=3,
                             lose_at=50, lose_worker=1):
    """The tentpole differential: the consolidated segment-ID path (one
    ``reid_topk_segments`` call over the fleet-global RoundPlan) is
    trace-identical to the UNCONSOLIDATED per-frame reference engine — the
    reference single run here uses ``consolidate=False`` so the assertion
    crosses both the fleet/single boundary AND the segment/frame-tag kernel
    boundary in one differential.  Covers a query count not divisible by any
    shard count > 1 (ragged shard padding) plus a mid-run worker-loss leg."""
    from repro.core.policy import SearchPolicy

    _require_devices(max(shard_counts))
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    single = None
    for shards in shard_counts:
        _, single = assert_fleet_trace_identical(
            world, policy, shards, single=single,
            consolidate=True, single_consolidate=False)
    # consolidated single engine against the same unconsolidated reference
    _, c_trace, c_sum = drive_serving_trace(world, policy, consolidate=True)
    ref_trace, ref_sum = single
    assert trace_key(c_trace) == trace_key(ref_trace), \
        "consolidated single engine diverged from the per-frame path"
    assert c_sum["per_query"] == ref_sum["per_query"]
    assert c_sum["admitted_steps"] == ref_sum["admitted_steps"]
    assert c_sum["unique_frames"] == ref_sum["unique_frames"]
    assert c_sum["content_steps"] == ref_sum["content_steps"]
    assert c_sum["replay_steps"] == ref_sum["replay_steps"]
    np.testing.assert_array_equal(c_sum["rescue_pairs"],
                                  ref_sum["rescue_pairs"])
    # worker loss mid-run with the consolidated fleet path
    world2 = make_serving_world(seed=seed + 1, n_queries=7)
    eng, _ = assert_fleet_trace_identical(
        world2, policy, max(shard_counts) // 2, lose_at=lose_at,
        lose_worker=lose_worker, consolidate=True, single_consolidate=False)
    assert eng.rebalances == 1


def fleet_case_tiles(shard_counts=(1, 2, 4, 8), T=4, n_queries=5, seed=3,
                     lose_at=50, lose_worker=1):
    """The sub-frame spatial admission differential: serving with
    ``tile_grid=T`` over a model WITHOUT tile data (the engine synthesizes
    the all-tiles-admitted tensor) is trace-identical to camera-granular
    serving — admissions, match indices/values (tie-breaks included),
    rescue attribution, both cost conventions — for the single engine AND
    every shard count, plus a mid-run worker-loss leg.  All-admitted tile
    accounting must tile exactly: T*T tiles per admitted camera-step and
    per unique frame (the camera-granular pixel-load ceiling the learned
    masks are measured against)."""
    from repro.core.policy import SearchPolicy

    _require_devices(max(shard_counts))
    TT = T * T
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    _, ref_trace, ref_sum = drive_serving_trace(world, policy)
    for shards in (None,) + tuple(shard_counts):
        eng, tr, sm = drive_serving_trace(world, policy, shards=shards,
                                          tile_grid=T)
        assert trace_key(tr) == trace_key(ref_trace), \
            f"tile path (shards={shards}) diverged from the camera path"
        for f in ("admitted_steps", "unique_frames", "content_steps",
                  "replay_steps", "model_epoch", "per_query"):
            assert sm[f] == ref_sum[f], f"tile path changed {f}"
        np.testing.assert_array_equal(sm["rescue_pairs"],
                                      ref_sum["rescue_pairs"])
        assert eng.admitted_tiles == TT * eng.admitted_steps, \
            "all-admitted tile accounting does not tile admitted_steps"
        assert eng.unique_tiles == TT * eng.unique_frames, \
            "all-admitted tile dedup does not tile unique_frames"
    # worker loss mid-run on the tile path
    world2 = make_serving_world(seed=seed + 1, n_queries=7)
    _, r2_trace, r2_sum = drive_serving_trace(world2, policy)
    eng, tr, sm = drive_serving_trace(
        world2, policy, shards=max(shard_counts) // 2, lose_at=lose_at,
        lose_worker=lose_worker, tile_grid=T)
    assert trace_key(tr) == trace_key(r2_trace), \
        "tile fleet diverged from the camera path across a worker loss"
    assert sm["per_query"] == r2_sum["per_query"]
    assert eng.rebalances == 1
    assert eng.admitted_tiles == TT * eng.admitted_steps


def fleet_case_plan_conservation(shard_counts=(1, 2, 4, 8), n_queries=5,
                                 seed=4):
    """Satellite regression: every RoundPlan conserves admission mass.  Per
    round, ``sum(want_count.values())`` (how many (query, camera) steps
    each unique (cam, frame) key serves) must equal ``plan.admitted`` (the
    admission mask's popcount over live rows) and the per-query camera
    lists; ``work`` must be exactly the sorted key set; and the per-plan
    admitted sum over a whole run must reproduce the engine's
    ``admitted_steps`` total — across consolidate on/off and every shard
    count, because dedup/consolidation is an execution-plan change that may
    never create or lose an admission step."""
    from repro.core.policy import SearchPolicy
    from repro.runtime.engine import ServingEngine

    _require_devices(max(shard_counts))
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    orig = ServingEngine._plan_round
    total = [0]

    def checked(self, qs):
        plan = orig(self, qs)
        per_key = sum(plan.want_count.values())
        assert per_key == plan.admitted == int(plan.mask[plan.slots].sum()), \
            f"plan lost admission mass: {per_key} keyed vs {plan.admitted}"
        assert plan.work == sorted(plan.want_count), \
            "work queue is not exactly the sorted want_count key set"
        assert plan.admitted == sum(len(c) for c in plan.cams_by_q), \
            "per-query camera lists do not tile the admitted count"
        total[0] += plan.admitted
        return plan

    ServingEngine._plan_round = checked
    try:
        for consolidate in (True, False):
            for shards in (None,) + tuple(shard_counts):
                total[0] = 0
                eng, _, _ = drive_serving_trace(world, policy, shards=shards,
                                                consolidate=consolidate)
                assert total[0] == eng.admitted_steps, \
                    (f"consolidate={consolidate} shards={shards}: per-plan "
                     f"admitted {total[0]} != engine admitted_steps "
                     f"{eng.admitted_steps}")
    finally:
        ServingEngine._plan_round = orig


def fleet_case_recalibration(shard_counts=(2, 4, 8), n_queries=8, seed=0):
    """Differential case for the §6 recalibration loop: on a mid-run
    topology shift, the controller re-profiles and hot-swaps M — and the
    fleet stays bit-identical to the single engine INCLUDING the model-epoch
    boundaries in every trace record (the swap lands on the same round on
    every shard).  The single run must actually swap (epoch > 0), both
    pre- and post-swap rounds must appear in the trace, and the hot-swap
    must never drop an in-flight query."""
    from repro.core.policy import SearchPolicy
    from repro.runtime.recal import RecalibrationPolicy

    _require_devices(max(shard_counts))
    # exit_t must outlast duke travel times (~44 +- 10 plus dwell) or phase-2
    # replay expires before the entity reappears and no rescues ever accrue
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=120)
    # test-world trigger: tiny fleet -> few rescues, so trip early and often
    # enough that at least one swap lands mid-trace
    recal = RecalibrationPolicy(drift_threshold=.02, min_rescues=2,
                                cooldown=60, poll_every=10, window=200)
    world = make_drifted_world(seed=seed, n_queries=n_queries, horizon=500)
    _, ref_trace, ref_sum = drive_serving_trace(world, policy,
                                                recalibrate=recal)
    single = (ref_trace, ref_sum)
    assert ref_sum["model_epoch"] >= 1, \
        "drifted world never tripped the recalibration trigger"
    epochs = {r["epoch"] for r in ref_trace}
    assert len(epochs) >= 2, "no pre/post-swap rounds both present in trace"
    live_at_swap = ref_sum["model_swaps"][0][0]
    n_alive = sum(1 for (_m, _r, done, _p, f) in ref_sum["per_query"]
                  if f > live_at_swap)
    assert n_alive > 0, "swap landed after every query finished"
    for shards in shard_counts:
        eng, single = assert_fleet_trace_identical(
            world, policy, shards, single=single, recalibrate=recal)
        assert eng.model_epoch == ref_sum["model_epoch"]
        assert int(eng.model.epoch) == eng.model_epoch


def fleet_case_soak(shard_counts=(1, 2, 4, 8), n_queries=8, seed=3,
                    churn_wave=40, lose_at=90, lose_worker=1):
    """The scaled-down soak differential: query churn (a late submit wave),
    worker loss, and a TARGETED recalibration swap all in ONE run — and
    the fleet trace stays bit-identical to the single engine at every shard
    count.  The single reference is reused across legs; loss only applies
    on the multi-shard legs (a 1-shard fleet has no worker to spare).
    On top of the differential, asserts the soak actually soaked: a swap
    landed mid-trace, the late wave replayed, the lossy legs rebalanced
    exactly once, and the targeted controller re-profiled a strict subset
    of the model's rows."""
    from repro.core.policy import SearchPolicy
    from repro.runtime.recal import RecalibrationPolicy

    _require_devices(max(shard_counts))
    # exit_t must outlast the city network's corridor travel times (30-70s)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=120)
    # the dense prior keeps normalized per-pair scores small — gate the trip
    # on the sustained rescue count, and keep the re-profiling window wide
    # enough that merged rows carry real travel-time support
    recal = RecalibrationPolicy(drift_threshold=.005, min_rescues=2,
                                cooldown=80, poll_every=10, window=250,
                                targeted=True, row_threshold=.02)
    world = make_soak_world(seed=seed, n_queries=n_queries)
    C = world["net"].n_cams
    single = None
    eng = None
    for shards in shard_counts:
        loss = lose_at if shards >= 2 else None
        eng, single = assert_fleet_trace_identical(
            world, policy, shards, single=single, recalibrate=recal,
            churn_wave=churn_wave, lose_at=loss, lose_worker=lose_worker)
        if loss is not None:
            assert eng.rebalances == 1
    ref_trace, ref_sum = single
    assert ref_sum["model_epoch"] >= 1, \
        "soak world never tripped the recalibration trigger"
    assert len({r["epoch"] for r in ref_trace}) >= 2, \
        "no pre/post-swap rounds both present in trace"
    assert ref_sum["replay_steps"] > 0, "late wave never replayed"
    # targeted accounting: every swap re-profiled a strict subset of rows
    ctl = eng.recal
    assert ctl.targeted_swaps >= 1 and ctl.full_rebuilds == 0
    assert ctl.rows_reprofiled < C * ctl.targeted_swaps, \
        f"targeted recal touched {ctl.rows_reprofiled} rows over " \
        f"{ctl.targeted_swaps} swaps — no better than a full rebuild (C={C})"
    for ev in ctl.events:
        assert ev["mode"] == "targeted" and 0 < ev["rows"] < C


def _drive_counting(world, policy, *, shards=None, gallery="auto",
                    extra_ticks=500):
    """Like ``drive_serving_trace`` but every ingested (cam, t) frame batch
    carries a tag column and ``embed_fn`` counts embed EVENTS per tag —
    the instrument for "no (cam, frame) pair is ever embedded twice" and
    "fleet-global embed calls == the single engine's".  Returns
    (engine, trace, Counter{tag: embed events})."""
    from repro import api as rexcam

    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    q_vids = world["q_vids"]
    H = vis.horizon + 1
    embedded = collections.Counter()

    def embed_fn(x):
        for tag in sorted(set(x[:, -1].tolist())):
            embedded[int(tag)] += 1
        return x[:, :-1]

    eng = rexcam.serve(world["model"], embed_fn=embed_fn, policy=policy,
                       geo_adj=world["net"].geo_adjacent, shards=shards,
                       gallery=gallery)
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    trace = []
    for t in range(t0, vis.horizon + extra_ticks):
        if t < vis.horizon:
            frames = {}
            for c in range(vis.n_cams):
                vids = gal[c, t][gal[c, t] >= 0]
                if len(vids):
                    crops = feats[vids]
                    tag = np.full((len(crops), 1), c * H + t, np.float32)
                    frames[c] = np.concatenate([crops, tag], 1)
            eng.ingest(frames)
        eng.tick(record_trace=trace)
        if all(q.done for q in eng.queries.values()):
            break
    return eng, trace, embedded


def fleet_case_gallery_modes(shards=4, n_queries=5, seed=0):
    """The gallery-plane differential (the PR-4 tentpole contract): with the
    fleet-shared ``ShardedGalleryStore`` AND with the replicated-baseline
    ``LocalGalleryStore``, the fleet is trace-identical to the single
    engine, no (cam, frame) pair ever reaches ``embed_fn`` twice fleet-wide,
    and fleet-global embed calls EQUAL the single engine's (one embedding
    plane — no per-shard re-embedding of the deduplicated demand)."""
    from repro.core.policy import SearchPolicy

    _require_devices(shards)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    single, s_trace, s_counter = _drive_counting(world, policy)
    assert single.frames_processed > 0
    assert s_counter and max(s_counter.values()) == 1, \
        "single engine re-embedded a (cam, frame) pair"
    for mode in ("sharded", "local"):
        eng, f_trace, f_counter = _drive_counting(world, policy,
                                                  shards=shards, gallery=mode)
        assert eng.gallery.kind == mode
        assert trace_key(f_trace) == trace_key(s_trace), \
            f"gallery={mode} fleet trace diverged from the single engine"
        assert max(f_counter.values()) == 1, \
            f"gallery={mode} fleet re-embedded a (cam, frame) pair"
        assert f_counter == s_counter, \
            f"gallery={mode} fleet embed calls differ from the single engine"
        assert eng.frames_processed == single.frames_processed
        assert eng.unique_frames == single.unique_frames
        assert eng.cache_hits == single.cache_hits
        rep = eng.shard_report()
        if mode == "sharded":
            # owner attribution tiles the fleet-global dedup set exactly,
            # and the resident blocks live where their camera's owner is
            assert sum(r["owned_frames"] for r in rep) == eng.unique_frames
            per_w = eng.gallery.per_worker_report()
            assert sum(v["blocks"] for v in per_w.values()) == \
                eng.store.cached_embeddings()
            assert sum(v["cameras"] for v in per_w.values()) == eng.C
        else:
            assert all(r["owned_frames"] == 0 for r in rep)


def fleet_case_gallery_rehome(shards=4, lose_worker=1, warmup=60,
                              n_queries=6, seed=1):
    """Worker loss re-homes the gallery plane: the lost worker's cameras
    (and their device-resident blocks) migrate to survivors chosen by the
    camera hash, block VALUES survive the move bit-exactly, and surviving
    owners keep their cameras (only the lost shard moves)."""
    from repro import api as rexcam

    _require_devices(shards)
    from repro.core.policy import SearchPolicy

    world = make_serving_world(seed=seed, n_queries=n_queries)
    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    eng = rexcam.serve(world["model"], embed_fn=lambda x: x, policy=policy,
                       geo_adj=world["net"].geo_adjacent, shards=shards)
    q_vids = world["q_vids"]
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
    for t in range(t0, t0 + warmup):
        frames = {}
        for c in range(vis.n_cams):
            vids = gal[c, t][gal[c, t] >= 0]
            if len(vids):
                frames[c] = feats[vids]
        eng.ingest(frames)
        eng.tick()

    store = eng.gallery
    lost = f"w{lose_worker}"
    pre_owner = dict(store._owner)
    owned_keys = [k for k in store._blocks if store.owner_of(k[0]) == lost]
    assert owned_keys, \
        f"warmup never cached a block owned by {lost} — warmup too short?"
    pre_vals = {k: store._fetch(*k).copy() for k in owned_keys}
    rehomed_before = store.rehomed_blocks

    eng.lose_worker(lose_worker)

    assert store.rehomed_blocks - rehomed_before == len(owned_keys)
    assert lost not in set(store._owner.values())
    for cam, w in pre_owner.items():
        if w != lost:       # survivors keep their cameras
            assert store._owner[cam] == w
    for k in owned_keys:
        new_owner = store.owner_of(k[0])
        assert new_owner in eng._workers
        arr, _n = store._blocks[k]
        assert {d for d in arr.devices()} == \
            {eng._device_of[new_owner]}, f"block {k} not on its owner device"
        np.testing.assert_array_equal(store._fetch(*k), pre_vals[k])


def fleet_case_load_accounting(shards=4, n_queries=7, seed=2, lose_at=40,
                               lose_worker=2):
    """Satellite: ``_load`` is O(1) counter-backed and must equal the brute
    placement-map scan at every tick — across submits, query completions
    (both the device round and the host skip fast path) and a mid-run
    worker loss rebalance."""
    from repro import api as rexcam
    from repro.core.policy import SearchPolicy

    _require_devices(shards)

    def brute(eng, worker):
        return sum(1 for qid, w in eng._placement.items()
                   if w == worker and qid in eng.queries
                   and not eng.queries[qid].done)

    world = make_serving_world(seed=seed, n_queries=n_queries)
    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60, replay_skip=2)   # exercise _skip_round
    eng = rexcam.serve(world["model"], embed_fn=lambda x: x, policy=policy,
                       geo_adj=world["net"].geo_adjacent, shards=shards)
    q_vids = world["q_vids"]
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
        assert all(eng._load(w) == brute(eng, w) for w in eng._workers)
    for step, t in enumerate(range(t0, vis.horizon + 500)):
        if step == lose_at:
            eng.lose_worker(lose_worker)
        if t < vis.horizon:
            frames = {}
            for c in range(vis.n_cams):
                vids = gal[c, t][gal[c, t] >= 0]
                if len(vids):
                    frames[c] = feats[vids]
            eng.ingest(frames)
        eng.tick()
        assert all(eng._load(w) == brute(eng, w) for w in eng._workers), \
            f"load counters diverged from the placement scan at step {step}"
        if all(q.done for q in eng.queries.values()):
            break
    assert all(q.done for q in eng.queries.values())
    assert all(eng._load(w) == 0 for w in eng._workers)


def fleet_property_suite(max_examples=6):
    """Satellite property test, shared between the in-process (8-device CI
    step) and subprocess entry: random scheme/seed/shard-count/replay-skip
    draws must keep the fleet bit-identical to one engine.  Uses real
    hypothesis when importable, else the deterministic fallback shim."""
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st
    except ImportError:
        from _hypothesis_fallback import given, settings, st

    from repro.core.policy import SearchPolicy

    singles: dict[tuple, tuple] = {}   # (seed, policy) -> reference run

    @settings(max_examples=max_examples, deadline=None)
    @given(st.sampled_from(["rexcam", "all", "spatial_only", "geo"]),
           st.integers(0, 2),                  # world seed stream
           st.sampled_from([1, 2, 4, 8]),      # shard counts
           st.sampled_from([1, 2]))            # §5.3 skip mode on/off
    def prop(scheme, seed, shards, replay_skip):
        world = make_serving_world(n_entities=80, horizon=300, seed=seed,
                                   n_queries=3)
        policy = SearchPolicy(scheme=scheme, s_thresh=.05, t_thresh=.02,
                              exit_t=60, replay_skip=replay_skip)
        key = (seed, policy)
        _, singles[key] = assert_fleet_trace_identical(
            world, policy, shards, single=singles.get(key))

    prop()


def fleet_case_recompile_guard(shard_counts=(1, 2, 4, 8), n_queries=5,
                               seed=0, warmup=150, steady=150):
    """Compile-discipline case (tests/test_analysis.py + the CI fleet step):
    for every shard count, the serving loop's jit entries — module-level
    AND the fleet's shard_map step bodies — compile each abstract signature
    at most ONCE after warmup.  ``RecompileGuard`` raises on steady-state
    cache misses; warmup absorbs tracing plus the batch/gallery high-water
    marks' growth phase (the hwm layout keeps shapes monotone, so by steady
    state the signature set is frozen up to one genuinely-new shape class
    per entry)."""
    from repro import api as rexcam
    from repro.analysis import RecompileGuard
    from repro.core.policy import SearchPolicy

    _require_devices(max(shard_counts))
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    q_vids = world["q_vids"]
    for shards in shard_counts:
        eng = rexcam.serve(world["model"], embed_fn=lambda x: x,
                           policy=policy,
                           geo_adj=world["net"].geo_adjacent, shards=shards)
        t0 = int(vis.t_out[q_vids].min())
        eng.t = t0
        for i, q in enumerate(q_vids):
            eng.submit_query(i, feats[q], int(vis.cam[q]),
                             int(vis.t_out[q]))

        def run(ticks, start):
            for t in range(start, start + ticks):
                if t < vis.horizon:
                    frames = {}
                    for c in range(vis.n_cams):
                        vids = gal[c, t][gal[c, t] >= 0]
                        if len(vids):
                            frames[c] = feats[vids]
                    eng.ingest(frames)
                eng.tick()

        run(warmup, t0)
        with RecompileGuard.for_engine(eng, max_new=1,
                                       label=f"shards={shards}"):
            run(steady, t0 + warmup)


def _fake_rpc_factory(profiles=None, **kw):
    """Zero-arg factory for a VIRTUAL-clock ``FakeRpcTransport`` — each
    drive gets fresh transport state and injected latency costs no real
    wall time.  ``profiles`` maps peer -> FaultProfile kwargs."""
    def make():
        from repro.runtime.transport import (FakeRpcTransport, FaultProfile,
                                             manual_clock)
        clock, sleep = manual_clock()
        faults = {w: FaultProfile(**p) for w, p in (profiles or {}).items()}
        kw2 = dict(kw)
        if isinstance(kw2.get("default"), dict):
            kw2["default"] = FaultProfile(**kw2["default"])
        return FakeRpcTransport(faults=faults, clock=clock, sleep=sleep, **kw2)
    return make


def fleet_case_transport_shard_counts(shard_counts=(1, 2, 4, 8), n_queries=5,
                                      seed=0):
    """The transport differential across the whole shard matrix: a fake-RPC
    fleet with per-peer latency+jitter AND the prefetch pipeline on stays
    bit-identical to the single engine for shards {1, 2, 4, 8}; the named
    in-proc transport (with and without prefetch) likewise.  Transport must
    change WHEN blocks arrive, never WHAT is ranked."""
    from repro.core.policy import SearchPolicy
    from repro.runtime.transport import InProcTransport

    _require_devices(max(shard_counts))
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    fake = _fake_rpc_factory(default=dict(latency=.01, jitter=.005))
    single = None
    for shards in shard_counts:
        eng, single = assert_fleet_trace_identical(
            world, policy, shards, single=single, transport=fake,
            prefetch=True)
        c = eng.gallery.counters()
        assert c["remote_fetches"] > 0, "no fetch ever crossed the transport"
        assert c["dead_peers"] == 0 and c["timeouts"] == 0
        # cache-hit parity: every hit was served through the fetch plane,
        # either prefetched or as the blocking fallback
        assert c["prefetch_hits"] <= eng.cache_hits
    eng, _ = assert_fleet_trace_identical(world, policy, 4, single=single,
                                          transport=InProcTransport,
                                          prefetch=True)
    assert eng.gallery.counters()["remote_fetches"] > 0
    assert_fleet_trace_identical(world, policy, 4, single=single,
                                 transport=InProcTransport, prefetch=False)


def fleet_case_transport_faults(shards=4, n_queries=5, seed=0):
    """The fault-injection matrix, each configuration trace-identical to
    the single engine: drop+retry (lost attempts re-issue after
    timeout+backoff), reorder (responses overtake each other), and blocking
    heavy latency with no prefetch (pure slowdown)."""
    from repro.core.policy import SearchPolicy

    _require_devices(shards)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    single = None
    cases = [
        ("drop+retry",
         _fake_rpc_factory(default=dict(latency=.01, drop=.3),
                           timeout=.05, max_retries=6), True),
        ("reorder",
         _fake_rpc_factory(default=dict(latency=.01, jitter=.01, reorder=.5,
                                        reorder_delay=.2),
                           timeout=1.0), True),
        ("blocking-latency",
         _fake_rpc_factory(default=dict(latency=.05)), False),
    ]
    for name, factory, prefetch in cases:
        eng, single = assert_fleet_trace_identical(
            world, policy, shards, single=single, transport=factory,
            prefetch=prefetch)
        c = eng.gallery.counters()
        assert c["remote_fetches"] > 0, f"{name}: transport never used"
        assert c["dead_peers"] == 0, f"{name}: a peer unexpectedly died"
        if name == "drop+retry":
            assert c["retries"] > 0 and c["timeouts"] > 0, \
                "drop=.3 produced no retries — fault injection inert"
        # per-worker fetch traffic is surfaced in the shard report
        rep = eng.shard_report()
        assert sum(r["remote_fetches"] for r in rep) == c["remote_fetches"]


def fleet_case_transport_timeout_rehome(shards=4, n_queries=6, seed=1,
                                        warmup=None):
    """timeout -> dead-peer -> rehome, end to end: one peer drops EVERY
    attempt, so the first fetch against it exhausts the retry budget
    mid-round, fires ``on_dead``, the gallery re-homes immediately (the
    blocked fetch retries against the new owner and succeeds), and the
    fleet scales down at the end of the tick — trace stays bit-identical."""
    from repro.core.policy import SearchPolicy

    _require_devices(shards)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    victim = "w1"
    factory = _fake_rpc_factory({victim: dict(drop=1.0)},
                                timeout=.05, max_retries=2, backoff=.01)
    eng, _ = assert_fleet_trace_identical(world, policy, shards,
                                          transport=factory, prefetch=False)
    c = eng.gallery.counters()
    assert c["dead_peers"] == 1, \
        f"the all-drop peer never died (counters: {c})"
    assert c["timeouts"] >= 3 and c["retries"] >= 2
    assert victim not in eng._workers, "dead peer still in the fleet"
    assert eng.n_shards == shards - 1
    assert victim not in set(eng.gallery._owner.values()), \
        "dead peer still owns cameras"
    assert eng.gallery.rehomed_blocks > 0 or c["remote_fetches"] > 0


def fleet_case_transport_midfetch_loss(shards=4, lose_at=50, lose_worker=1,
                                       n_queries=7, seed=1):
    """Mid-fetch worker loss: with prefetch handles in flight, the fleet
    loses a worker (``lose_worker`` marks the peer dead on the transport) —
    in-flight handles to it fail fast with ``PeerDeadError`` at consume
    time and the round falls back to a blocking fetch from the re-homed
    owner.  Trace stays bit-identical; waste is exactly accounted."""
    from repro.core.policy import SearchPolicy

    _require_devices(shards)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    world = make_serving_world(seed=seed, n_queries=n_queries)
    factory = _fake_rpc_factory(default=dict(latency=.01, jitter=.005))
    eng, _ = assert_fleet_trace_identical(
        world, policy, shards, lose_at=lose_at, lose_worker=lose_worker,
        transport=factory, prefetch=True)
    tr = eng.gallery.transport
    assert tr.is_dead(f"w{lose_worker}"), \
        "lose_worker did not mark the peer dead on the transport"
    c = eng.gallery.counters()
    assert c["prefetch_hits"] > 0, "prefetch never served a block"
    assert f"w{lose_worker}" not in set(eng.gallery._owner.values())


@pytest.fixture(scope="session")
def duke_sim():
    """Small-but-real duke-like scenario shared across tests (session-cached)."""
    from repro.core import (duke_like_network, simulate_network, build_gallery,
                            build_model)
    from repro.core.features import FeatureParams, make_features
    from repro.core.tracker import make_queries

    net = duke_like_network()
    vis = simulate_network(net, n_entities=900, horizon=2400, seed=0)
    gal, _ = build_gallery(vis, max_slots=24)
    model = build_model(vis.ent, vis.cam, vis.t_in, vis.t_out, net.n_cams,
                        time_limit=1600)
    feats, emb = make_features(vis, 900, FeatureParams())
    q_vids, gt_vids = make_queries(vis, 40, seed=1)
    return dict(net=net, vis=vis, gal=gal, model=model, feats=feats,
                q_vids=q_vids, gt_vids=gt_vids)
