"""The benchmark's generators: the copy of the simulator draws what the
program's simulator draws, traffic is fixed by its seed, and every seed
serves the same recording."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from harness import traffic, world  # noqa: E402


def _arrivals(seed, mix, ticks=400, ramp_until=200):
    cfg = tiny.config()
    w = world.build(cfg, seed)
    arr = traffic.Arrivals(w, cfg, mix)
    out = []
    for t in range(w["t0"], w["t0"] + ticks):
        out.extend((t, v) for v in arr.due(t, t < w["t0"] + ramp_until))
    return w, out


def test_same_seed_same_arrivals_and_anchors():
    for mix in (tiny.LIVE, tiny.REPLAY):
        _, a = _arrivals(5, mix)
        _, b = _arrivals(5, mix)
        assert a == b and len(a) > 10


def test_other_seed_other_anchors_same_ticks():
    """Another seed asks the same sightings on the same ticks, presented
    otherwise: on other camera labels, with other feature values."""
    wa, a = _arrivals(5, tiny.REPLAY)
    wb, b = _arrivals(6, tiny.REPLAY)
    assert [t for t, _ in a] == [t for t, _ in b]   # open-loop schedule
    va = np.asarray([v for _, v in a])
    vb = np.asarray([v for _, v in b])
    assert np.array_equal(wa["stream"].t_out[va], wb["stream"].t_out[vb])
    assert not np.array_equal(wa["stream"].cam[va], wb["stream"].cam[vb])
    assert not np.allclose(wa["feats"][va], wb["feats"][vb])


def test_every_seed_serves_the_same_work():
    """Two seeds' worlds are one recording under a camera relabeling and
    a signed permutation of the feature coordinates: the same detections
    per step, the same distances, the same profile."""
    for tile_grid in (0, 8):
        a = world.build(tiny.config(tile_grid), 5)
        b = world.build(tiny.config(tile_grid), 2 ** 40 + 6)
        ca, cb = a["stream"].cam, b["stream"].cam
        perm = np.zeros(a["net"].n_cams, np.int64)
        perm[ca] = cb                       # a's camera -> b's camera
        assert len(set(perm)) == len(perm) and not np.array_equal(ca, cb)
        assert np.array_equal(b["gal"][perm], a["gal"])
        assert np.array_equal(b["net"].geo_adjacent[np.ix_(perm, perm)],
                              a["net"].geo_adjacent)
        assert np.array_equal(b["net"].travel_mean[np.ix_(perm, perm)],
                              a["net"].travel_mean)
        assert np.array_equal(b["history"].cam,
                              perm[a["history"].cam])
        fa, fb = a["feats"][:300], b["feats"][:300]
        assert not np.allclose(fa, fb)
        np.testing.assert_allclose(fa @ fa.T, fb @ fb.T, atol=1e-5)
        if tile_grid:
            assert np.array_equal(a["tiles"], b["tiles"])


def test_anchor_lags_lie_in_the_mix_range():
    w, a = _arrivals(7, tiny.REPLAY)
    lo, hi = tiny.REPLAY["anchor_lag_s"]
    lags = [t - int(w["stream"].t_out[v]) for t, v in a]
    assert min(lags) >= lo and max(lags) <= hi + 5
    w, a = _arrivals(7, tiny.LIVE)
    lags = [t - int(w["stream"].t_out[v]) for t, v in a]
    # a sighting that just left the frame, or the latest before it
    assert min(lags) == 1 and np.mean(np.asarray(lags) == 1) > 0.8


def test_ramp_doubles_the_rate_until_the_target():
    w, a = _arrivals(8, tiny.LIVE, ticks=401, ramp_until=201)
    first = w["t0"] + 1                    # the live edge's first arrival
    early = sum(1 for t, _ in a if t < first + 200)
    late = sum(1 for t, _ in a if t >= first + 200)
    # float accumulation of 0.1 a tick may land one arrival a tick later
    assert abs(early - 20) <= 1 and abs(late - 10) <= 1


def test_mixes_hold_no_configuration_key():
    """A configuration is added by adding its file: its arrival rate is
    its own, and every mix applies to it unchanged."""
    import glob
    import json

    keys = {"rate_scale", "ramp_factor", "anchor_lag_s", "replay_speed",
            "warmup_ticks"}
    paths = glob.glob(os.path.join(tiny.BENCH, "traffic", "*.json"))
    assert paths
    for p in paths:
        with open(p) as f:
            assert set(json.load(f)) <= keys, p
    for p in glob.glob(os.path.join(tiny.BENCH, "configs", "*.json")):
        with open(p) as f:
            assert float(json.load(f)["queries"]["arrivals_per_tick"]) > 0, p


def test_copy_matches_the_programs_simulator():
    from repro.core import simulate as S

    a = S.simulate_network(S.porto_like_network(130), 150, 900, seed=4)
    b = world.simulate(world.network({"builder": "porto_like"}), 150, 900,
                       np.random.default_rng(4),
                       np.random.default_rng([4, 0x7E11E5]))
    for k in ("ent", "cam", "t_in", "t_out", "tile_xy"):
        assert np.array_equal(getattr(a, k), getattr(b, k)), k
    ga, _ = S.build_gallery(a, 16)
    assert np.array_equal(ga, world.detections(b, 16))


def test_large_and_negative_seeds_are_accepted():
    for seed in (2 ** 31 + 11, 2 ** 40, -3):
        w = world.build(tiny.config(), seed)
        assert len(w["stream"]) > 0
