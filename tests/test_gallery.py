"""The gallery plane (PR-4 tentpole): GalleryStore semantics, FrameStore
delegation, engine/api wiring and the top-k trace bands — everything that
runs on one device.  The fleet-level differential contracts (sharded vs
local gallery trace identity, counting embed_fn, shard re-homing, O(1)
load accounting under rebalance) live in tests/test_sharded_engine.py via
the 8-fake-device harness."""
import numpy as np
import pytest

from repro.runtime import FrameStore
from repro.runtime.gallery import (LocalGalleryStore, RoundStaging,
                                   ShardedGalleryStore,
                                   assemble_round_gallery, pow2)


# -- GalleryStore contract ---------------------------------------------------

def test_local_gallery_store_counters_and_horizon():
    g = LocalGalleryStore(n_cams=2, retention=10)
    e5 = np.ones((3, 4), np.float32)
    assert g.put(0, 5, e5)
    assert g.get(0, 5) is e5 and g.hits == 1
    assert g.get(0, 6) is None and g.misses == 1
    assert g.get(1, 5) is None               # cameras are independent
    # a put far behind the horizon is refused, not silently dropped
    assert g.put(0, 100, np.zeros((1, 4), np.float32))
    assert not g.put(0, 5, e5)
    assert g.rejected == 1
    # ...and the horizon-advance evicted the old entry
    assert g.get(0, 5) is None
    assert g.evictions == 1
    assert g.cached_embeddings() == 1
    c = g.counters()
    assert c["cached"] == 1 and c["bytes"] == 4 * 4


def test_gallery_store_out_of_order_deferred_eviction():
    """The FrameStore invariants, on the store itself: an out-of-order put
    below a later horizon is rejected; one ABOVE the horizon is accepted
    but its eviction may defer until the deque head catches up — during
    which ``get`` re-checks the horizon and never serves it stale."""
    g = LocalGalleryStore(n_cams=1, retention=60)
    g.put(0, 100, "e100")
    assert g.put(0, 50, "e50")               # out of order, still retained
    assert g.get(0, 50) == "e50"
    g.put(0, 120, "e120")                    # horizon -> 60: 50 is now stale
    # deferred eviction: the deque head (100) hasn't crossed the horizon,
    # so the entry is still resident... but get re-checks and refuses it
    assert g.cached_embeddings() == 3
    assert g.get(0, 50) is None
    # deque catch-up: horizon passes 100, popping it AND the deferred 50
    g.put(0, 165, "e165")
    assert g.cached_embeddings() == 2        # {120, 165}
    assert g.get(0, 120) == "e120" and g.get(0, 165) == "e165"


def test_sharded_gallery_store_device_blocks_roundtrip():
    """Single-worker sharded store: blocks live on the owner device, rows
    pow2-padded, and round-trip bit-exactly (what keeps the sharded-gallery
    fleet trace-identical)."""
    import jax

    dev = jax.devices()[0]
    g = ShardedGalleryStore(n_cams=3, retention=50, workers=["w0"],
                            device_of={"w0": dev})
    assert all(g.owner_of(c) == "w0" for c in range(3))
    rng = np.random.default_rng(0)
    emb = rng.normal(size=(5, 8)).astype(np.float32)
    assert g.put(1, 7, emb)
    arr, n = g._blocks[(1, 7)]
    assert n == 5 and arr.shape == (pow2(5), 8)      # pow2 row padding
    assert {d for d in arr.devices()} == {dev}
    np.testing.assert_array_equal(g.get(1, 7), emb)  # bit-exact roundtrip
    rep = g.per_worker_report()
    assert rep["w0"]["cameras"] == 3 and rep["w0"]["blocks"] == 1
    assert rep["w0"]["rows"] == 5 and rep["w0"]["bytes"] == arr.nbytes
    assert rep["w0"]["misplaced"] == 0               # block on owner's device
    assert g.memory_bytes() == arr.nbytes
    with pytest.raises(RuntimeError):
        g.rehome("w0", [])                   # no survivors: fail loudly


def test_sharded_gallery_rehome_moves_only_the_lost_shard():
    import jax

    dev = jax.devices()[0]
    g = ShardedGalleryStore(n_cams=8, retention=50, workers=["w0", "w1"],
                            device_of={"w0": dev, "w1": dev})
    owners = dict(g._owner)
    assert set(owners.values()) == {"w0", "w1"}      # hash spreads cameras
    for cam in range(8):
        g.put(cam, 3, np.full((2, 4), cam, np.float32))
    lost_cams = [c for c, w in owners.items() if w == "w0"]
    moved = g.rehome("w0", ["w1"])
    assert moved == len(lost_cams) == g.rehomed_blocks
    assert set(g._owner.values()) == {"w1"}
    for cam, w in owners.items():
        if w != "w0":                        # survivors keep their cameras
            assert g._owner[cam] == w
    for cam in range(8):                     # values survive the migration
        np.testing.assert_array_equal(g.get(cam, 3),
                                      np.full((2, 4), cam, np.float32))


def test_assemble_round_gallery_camera_major_and_pow2():
    keys = [(0, 5), (1, 5), (2, 4)]
    key_emb = {(0, 5): np.ones((2, 4), np.float32),
               (1, 5): np.full((1, 4), 2, np.float32),
               (2, 4): np.full((2, 4), 3, np.float32)}
    gal, gal_cam, gal_frame = assemble_round_gallery(keys, key_emb)
    assert gal.shape == (8, 4)               # 5 rows padded to pow2
    np.testing.assert_array_equal(gal_cam[:5], [0, 0, 1, 2, 2])
    np.testing.assert_array_equal(gal_frame[:5], [5, 5, 5, 4, 4])
    assert (gal_cam[5:] == -1).all() and (gal_frame[5:] == -1).all()
    assert (gal[5:] == 0).all()
    # the in-place form: two fills of one staging, the second shorter,
    # leave zero rows and -1 tags (segment and cell tags too) past its end
    st = RoundStaging()
    assemble_round_gallery(keys, key_emb, min_rows=8, out=st)
    st.seg[:5] = 7
    st.ct[:5] = 9
    gal2, cam2, frame2 = assemble_round_gallery([(2, 4)], key_emb,
                                                min_rows=8, out=st)
    assert st.allocs == 1 and gal2.shape == (8, 4)
    assert np.shares_memory(gal2, st.gal)
    np.testing.assert_array_equal(gal2[:2], key_emb[(2, 4)])
    np.testing.assert_array_equal(cam2[:2], [2, 2])
    np.testing.assert_array_equal(frame2[:2], [4, 4])
    assert (gal2[2:] == 0).all()
    for tag in (cam2, frame2, st.seg[:8], st.ct[:8]):
        assert (tag[2:] == -1).all()
    np.testing.assert_array_equal(st.seg[:2], [7, 7])   # the engine's rows


# -- FrameStore delegation ---------------------------------------------------

def test_frame_store_put_emb_returns_cached_or_not():
    """Satellite: ``put_emb`` reports whether the write stuck — a frame
    never appended (or already evicted) is refused, not silently dropped."""
    fs = FrameStore(n_cams=1, retention=10)
    assert not fs.put_emb(0, 3, "orphan")    # frame never appended
    assert fs.get_emb(0, 3) is None
    fs.append(0, 3, "f3")
    assert fs.put_emb(0, 3, "e3")            # retained: accepted
    assert fs.get_emb(0, 3) == "e3"
    for t in range(4, 30):
        fs.append(0, t, f"f{t}")
    assert not fs.put_emb(0, 3, "stale")     # evicted since: refused
    assert fs.get_emb(0, 3) is None


def test_frame_store_out_of_order_append_deferred_eviction():
    """Satellite: the module-docstring invariants, pinned.  An out-of-order
    append stays correct — ``get`` re-checks the horizon — and its eviction
    defers until the deque head reaches it."""
    fs = FrameStore(n_cams=1, retention=60)
    fs.append(0, 100, "f100")
    fs.append(0, 50, "f50")                  # out of order, still retained
    assert fs.get(0, 50) == "f50"
    fs.append(0, 120, "f120")                # horizon -> 60
    # 50 is behind the horizon but the deque head (100) isn't: eviction is
    # deferred, the frame is still resident...
    assert fs.memory_frames() == 3
    with pytest.raises(KeyError):            # ...but get re-checks
        fs.get(0, 50)
    # range reads clamp to the horizon too: the deferred frame is invisible
    assert fs.range(0, 0, 200) == [(100, "f100"), (120, "f120")]
    # deque catch-up: horizon passes 100 -> pops 100, then the deferred 50
    fs.append(0, 165, "f165")
    assert fs.memory_frames() == 2           # {120, 165}
    assert fs.get(0, 120) == "f120" and fs.get(0, 165) == "f165"


def test_frame_store_out_of_order_embeddings_follow_frames():
    """Same invariants one layer down: embeddings cached for a deferred
    frame are refused on read and dropped on the deque catch-up."""
    fs = FrameStore(n_cams=1, retention=60)
    fs.append(0, 100, "f100")
    fs.append(0, 50, "f50")
    assert fs.put_emb(0, 50, "e50")
    fs.append(0, 120, "f120")                # 50 now behind the horizon
    assert fs.get_emb(0, 50) is None         # horizon re-check on read
    assert fs.cached_embeddings() == 1       # eviction deferred...
    fs.append(0, 165, "f165")                # ...until deque catch-up
    assert fs.cached_embeddings() == 0
    assert fs.gallery.evictions == 1


def test_frame_store_delegates_to_injected_store():
    inj = LocalGalleryStore(n_cams=2, retention=10)
    fs = FrameStore(n_cams=2, retention=10, gallery=inj)
    assert fs.gallery is inj
    fs.append(1, 4, "f")
    assert fs.put_emb(1, 4, "e")
    assert inj.get(1, 4) == "e"              # landed in the injected store
    assert fs.cached_embeddings() == inj.cached_embeddings() == 1
    assert inj.puts == 1 and inj.hits == 1


# -- engine / api wiring -----------------------------------------------------

def test_serve_gallery_knob():
    from repro import api as rexcam
    from repro.runtime.engine import EngineConfig, ServingEngine
    from conftest import make_serving_world

    world = make_serving_world(n_entities=60, horizon=240, seed=3,
                               n_queries=2)
    single = rexcam.serve(world["model"], embed_fn=lambda x: x)
    assert single.gallery.kind == "local"
    assert single.gallery_report()["kind"] == "local"
    # sharded is a fleet-only mode: the single engine fails loudly
    with pytest.raises(ValueError):
        rexcam.serve(world["model"], embed_fn=lambda x: x, gallery="sharded")
    with pytest.raises(ValueError):
        ServingEngine(world["model"], lambda x: x,
                      EngineConfig(gallery="bogus"))
    # the fleet defaults to the fleet-shared sharded store...
    fleet = rexcam.serve(world["model"], embed_fn=lambda x: x, shards=1)
    assert fleet.gallery.kind == "sharded"
    assert fleet.store.gallery is fleet.gallery
    assert "per_worker" in fleet.gallery_report()
    # ...and can be forced back to the replicated baseline
    local = rexcam.serve(world["model"], embed_fn=lambda x: x, shards=1,
                         gallery="local")
    assert local.gallery.kind == "local"
    with pytest.raises(ValueError):
        rexcam.serve(world["model"], embed_fn=lambda x: x, shards=1,
                     gallery="bogus")
    # topk < 1 fails at construction, not deep inside the jitted round
    with pytest.raises(ValueError):
        rexcam.serve(world["model"], embed_fn=lambda x: x, topk=0)


def test_fleet_sharded_gallery_lives_on_the_data_axis():
    """shards=1 fleet end-to-end on any device count: the engine's cache
    round-trips through the device-resident sharded store and the owner
    attribution tiles the global dedup exactly."""
    from repro.core.policy import SearchPolicy
    from conftest import assert_fleet_trace_identical, make_serving_world

    world = make_serving_world(n_entities=60, horizon=240, seed=3,
                               n_queries=2)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    eng, _ = assert_fleet_trace_identical(world, policy, shards=1)
    assert eng.gallery.kind == "sharded"
    rep = eng.shard_report()
    assert sum(r["owned_frames"] for r in rep) == eng.unique_frames
    g = eng.gallery_report()
    assert g["per_worker"]["w0"]["cameras"] == eng.C


def test_fleet_load_counters_track_completions():
    """Satellite (tier-1 slice): the O(1) live-load counters equal the
    brute placement scan across submits and query completions.  The
    rebalance leg runs in the 8-device harness."""
    from repro import api as rexcam
    from repro.core.policy import SearchPolicy
    from conftest import make_serving_world

    def brute(eng, worker):
        return sum(1 for qid, w in eng._placement.items()
                   if w == worker and qid in eng.queries
                   and not eng.queries[qid].done)

    world = make_serving_world(n_entities=60, horizon=240, seed=3,
                               n_queries=3)
    vis, gal, feats = world["vis"], world["gal"], world["feats"]
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=40)
    eng = rexcam.serve(world["model"], embed_fn=lambda x: x, policy=policy,
                       geo_adj=world["net"].geo_adjacent, shards=1)
    q_vids = world["q_vids"]
    t0 = int(vis.t_out[q_vids].min())
    eng.t = t0
    for i, q in enumerate(q_vids):
        eng.submit_query(i, feats[q], int(vis.cam[q]), int(vis.t_out[q]))
        assert eng._load("w0") == brute(eng, "w0")
    for t in range(t0, vis.horizon + 200):
        if t < vis.horizon:
            frames = {}
            for c in range(vis.n_cams):
                vids = gal[c, t][gal[c, t] >= 0]
                if len(vids):
                    frames[c] = feats[vids]
            eng.ingest(frames)
        eng.tick()
        assert eng._load("w0") == brute(eng, "w0")
        if all(q.done for q in eng.queries.values()):
            break
    assert all(q.done for q in eng.queries.values())
    assert eng._load("w0") == 0


def test_replay_miss_conventions_per_key_and_per_step():
    """Satellite: an evicted (cam, frame) key wanted by k queries is ONE
    cold-storage miss in the per-key convention (``replay_misses``) but k
    failed rescue steps in admitted_steps' per-(query, camera) convention
    (``replay_miss_steps``) — both surface in ``gallery_report()``.  Pinned
    with 3 same-anchor queries replaying into a fully-evicted window: every
    round misses C keys but 3C steps."""
    from repro import api as rexcam
    from repro.core.policy import SearchPolicy
    from conftest import make_serving_world

    world = make_serving_world(n_entities=60, horizon=240, seed=3,
                               n_queries=2)
    C = world["model"].n_cams
    p = SearchPolicy(scheme="all", exit_t=60, replay_speed=1)
    eng = rexcam.serve(world["model"], embed_fn=lambda x: x, policy=p,
                       retention=4)
    eng.t = 50
    # one fresh frame per camera pushes every horizon past the replay window
    eng.ingest({c: np.ones((2, 16), np.float32) for c in range(C)})
    eng.t = 51
    for qid in range(3):
        eng.submit_query(qid, np.ones(16, np.float32), 0, 0)
    R = 10
    for _ in range(R):
        stats = eng.tick()
        # per tick: one round, all 3 cursors on one frame, C admitted keys
        assert stats["replay_misses"] == C
        assert stats["replay_miss_steps"] == 3 * C
    assert eng.replay_misses == C * R
    assert eng.replay_miss_steps == 3 * C * R
    rep = eng.gallery_report()
    assert rep["replay_misses"] == C * R
    assert rep["replay_miss_steps"] == 3 * C * R


# -- top-k candidate bands ---------------------------------------------------

def test_topk_bands_surface_without_changing_argmax():
    """Satellite: topk=3 surfaces (value, cam, frame) candidate bands in
    every trace record while the argmax match path (and therefore the whole
    trace minus the bands) is bit-identical to topk=1."""
    from repro.core.policy import SearchPolicy
    from repro.kernels.reid_topk import NEG_INF
    from conftest import drive_serving_trace, make_serving_world, trace_key

    world = make_serving_world(n_entities=80, horizon=300, seed=4,
                               n_queries=3)
    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                          exit_t=60)
    _, tr1, sum1 = drive_serving_trace(world, policy, topk=1)
    _, tr3, sum3 = drive_serving_trace(world, policy, topk=3)

    strip = lambda key: [r[:-1] for r in key]    # drop the topk element
    assert strip(trace_key(tr3)) == strip(trace_key(tr1))
    assert sum3["per_query"] == sum1["per_query"]

    assert all(len(r["topk"]) == 3 for r in tr3)
    assert all(len(r["topk"]) == 1 for r in tr1)
    saw_multi = False
    for r in tr3:
        vals = [b[0] for b in r["topk"]]
        assert vals == sorted(vals, reverse=True)    # bands are descending
        assert r["topk"][0][0] == r["match_val"]     # band 0 IS the argmax
        if r["matched"]:
            assert r["topk"][0][1] == r["match_cam"]
            assert r["topk"][0][2] == r["f_curr"]    # candidates at cursor
        for v, cam, frame in r["topk"]:
            if v <= NEG_INF / 2:                     # empty band: sentinel
                assert cam == -1 and frame == -1
            else:
                assert 0 <= cam < world["net"].n_cams
                saw_multi = saw_multi or r["topk"][1][0] > NEG_INF / 2
    assert saw_multi, "no round ever had a second candidate — world too easy"
