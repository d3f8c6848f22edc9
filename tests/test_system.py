"""End-to-end behaviour tests for the paper's system (ReXCam §5, §8)."""
import numpy as np
import pytest

from repro.core import TrackerParams, track_queries


def _run(duke_sim, p):
    return track_queries(duke_sim["model"], duke_sim["vis"], duke_sim["gal"],
                         duke_sim["feats"], duke_sim["q_vids"],
                         duke_sim["gt_vids"], p,
                         geo_adj=duke_sim["net"].geo_adjacent)


def test_rexcam_beats_baseline_cost(duke_sim):
    base = _run(duke_sim, TrackerParams(scheme="all"))
    rex = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.05, t_thresh=.02))
    savings = base.total_cost / max(rex.total_cost, 1)
    assert savings > 3.0, f"expected >3x savings, got {savings:.2f}x"


def test_rexcam_improves_precision(duke_sim):
    base = _run(duke_sim, TrackerParams(scheme="all"))
    rex = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.05, t_thresh=.02))
    assert rex.precision > base.precision + 0.05, (rex.precision, base.precision)


def test_rexcam_recall_close_to_baseline(duke_sim):
    base = _run(duke_sim, TrackerParams(scheme="all"))
    rex = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.05, t_thresh=.02))
    assert rex.recall > base.recall - 0.15, (rex.recall, base.recall)


def test_replay_rescues_reduce_recall_loss(duke_sim):
    """Disabling replay must lose recall vs replay-enabled ReXCam (§5.3)."""
    with_replay = _run(duke_sim, TrackerParams(scheme="rexcam"))
    without = _run(duke_sim, TrackerParams(scheme="rexcam", use_replay=False))
    assert with_replay.recall >= without.recall
    assert with_replay.rescued.sum() > 0


def test_replay_modes_tradeoffs(duke_sim):
    """Fig. 15: 2x skip cuts cost+delay; 2x ff cuts delay at same cost."""
    normal = _run(duke_sim, TrackerParams(scheme="rexcam"))
    skip = _run(duke_sim, TrackerParams(scheme="rexcam", replay_skip=2))
    ff = _run(duke_sim, TrackerParams(scheme="rexcam", replay_speed=2.0))
    assert skip.mean_delay <= normal.mean_delay + 1e-6
    assert ff.mean_delay <= normal.mean_delay + 1e-6
    assert skip.total_cost <= normal.total_cost + 1e-6


def test_more_aggressive_thresholds_cost_less(duke_sim):
    mild = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.01, t_thresh=.01))
    aggr = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.10, t_thresh=.10))
    assert aggr.total_cost < mild.total_cost


def test_spatial_only_saves_less_than_spatiotemporal(duke_sim):
    sp = _run(duke_sim, TrackerParams(scheme="spatial_only", s_thresh=.05))
    st = _run(duke_sim, TrackerParams(scheme="rexcam", s_thresh=.05, t_thresh=.02))
    assert st.total_cost < sp.total_cost


def test_exhaustive_final_recovers_more_but_costs_more(duke_sim):
    default = _run(duke_sim, TrackerParams(scheme="rexcam"))
    exha = _run(duke_sim, TrackerParams(scheme="rexcam", exhaustive_final=True))
    assert exha.total_cost >= default.total_cost
    assert exha.recall >= default.recall - 0.02


def test_drift_detection_signal(duke_sim):
    """§6: replay rescues accumulate per camera pair (re-profiling trigger)."""
    rex = _run(duke_sim, TrackerParams(scheme="rexcam"))
    assert rex.rescue_pairs.shape == (8, 8)
    assert rex.rescue_pairs.sum() == rex.rescued.sum()


def test_drift_detection_and_reprofiling():
    """Paper §6 end-to-end: a mid-run correlation change spikes replay
    rescues on the changed pair; re-profiling restores recall."""
    import dataclasses as _dc

    import numpy as np

    from repro.core import build_gallery, build_model, duke_like_network, simulate_network
    from repro.core.features import FeatureParams, make_features
    from repro.core.profiler import drift_score
    from repro.core.tracker import make_queries

    net = duke_like_network()
    T = net.trans.copy()
    moved = T[0, 1] * 0.9       # reroute into the uncorrelated c1->c5 pair
    T[0, 1] -= moved
    T[0, 4] += moved
    changed = _dc.replace(net, trans=T)

    hist = simulate_network(net, 800, 2000, seed=31)
    stale = build_model(hist.ent, hist.cam, hist.t_in, hist.t_out, net.n_cams)
    vis = simulate_network(changed, 800, 2000, seed=32)
    gal, _ = build_gallery(vis, 24)
    feats, _ = make_features(vis, 800, FeatureParams(seed=32))
    q, gt = make_queries(vis, 25, seed=33)
    p = TrackerParams(scheme="rexcam", s_thresh=.05, t_thresh=.02)

    r_stale = track_queries(stale, vis, gal, feats, q, gt, p,
                            geo_adj=net.geo_adjacent)
    score = drift_score(stale, r_stale.rescue_pairs)
    hot = np.unravel_index(np.argmax(score), score.shape)
    assert hot[0] == 0, f"drift localized to wrong source camera: {hot}"

    fresh_model = build_model(vis.ent, vis.cam, vis.t_in, vis.t_out, net.n_cams,
                              time_limit=1400)
    r_fresh = track_queries(fresh_model, vis, gal, feats, q, gt, p,
                            geo_adj=net.geo_adjacent)
    assert r_fresh.recall >= r_stale.recall - 0.02
    assert r_fresh.rescued.sum() <= r_stale.rescued.sum()


# ---------------------------------------------------------------------------
# the BENCH record golden schema (the persistent perf trajectory's contract)
# ---------------------------------------------------------------------------

def _bench_scenarios():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import scenarios
    return scenarios


def test_bench_record_rejects_missing_required_keys():
    scenarios = _bench_scenarios()
    with pytest.raises(ValueError, match="missing required keys"):
        scenarios.bench_record("_schema_probe", scenario="x",
                               admitted_steps=1)
    assert scenarios.pop_bench_records("_schema_probe") == []
    # a full measured row and a derived summary row both pass
    scenarios.bench_record("_schema_probe", scenario="x", admitted_steps=1,
                           unique_frames=1, wall_s=0.1, p50_tick_ms=1.0,
                           p99_tick_ms=2.0)
    scenarios.bench_record("_schema_probe", derived=True, savings_x=21.0)
    assert len(scenarios.pop_bench_records("_schema_probe")) == 2


def test_every_bench_record_call_site_satisfies_the_schema():
    """Static golden-schema audit: every ``bench_record(...)`` call in
    benchmarks/ passes all ``REQUIRED_BENCH_KEYS`` as explicit keywords (or
    opts out with ``derived=True``) — so a schema violation is caught at
    review time, not only when the offending sweep happens to run."""
    import ast
    import os

    scenarios = _bench_scenarios()
    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    required = set(scenarios.REQUIRED_BENCH_KEYS)
    audited = 0
    for fn in sorted(os.listdir(bench_dir)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(bench_dir, fn)) as f:
            tree = ast.parse(f.read(), filename=fn)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and ((isinstance(node.func, ast.Name)
                          and node.func.id == "bench_record")
                         or (isinstance(node.func, ast.Attribute)
                             and node.func.attr == "bench_record"))):
                continue
            kw = {k.arg for k in node.keywords if k.arg is not None}
            audited += 1
            derived = any(
                k.arg == "derived"
                and isinstance(k.value, ast.Constant) and k.value.value
                for k in node.keywords)
            if derived:
                continue
            # **extra splats may carry extras, but the required set must be
            # explicit at every call site so the audit stays static
            assert not (required - kw), \
                f"{fn}:{node.lineno}: bench_record missing explicit " \
                f"required keys {sorted(required - kw)}"
    assert audited >= 10, f"audit only found {audited} call sites"


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """The launchers' compile cache: JAX_COMPILATION_CACHE_DIR wins and is
    left to JAX; otherwise a fixed <checkout>/.jax_cache, never a
    per-process or temporary name.  Either way fast compiles are kept."""
    import os
    import jax
    from repro.launch.serve import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert use_compile_cache() == os.path.join(checkout, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == \
                os.path.join(checkout, ".jax_cache")
        else:
            path = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
            assert use_compile_cache() == path
            assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
