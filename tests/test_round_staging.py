"""The round's host staging: the engine fills one gallery buffer and one
query-feature buffer in place every round, hands the admission masks to
the rank step as the device arrays admit returned, and reads each matched
query's embedding from its host gallery instead of copying the step's
(N, D) ``match_emb`` back.  Every contract here is checked against the
engine as it serves a small world."""
import numpy as np
import pytest

from repro.core.policy import SearchPolicy
from repro.runtime import engine as engine_mod
from repro.runtime.engine import ServingEngine
from repro.runtime.gallery import RoundStaging

POLICY = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02, exit_t=60)

# the round paths the staging serves: which tag vectors a round fills
# follows from tile_grid and consolidate
PATHS = {
    "segments": dict(),
    "per_frame": dict(consolidate=False),
    "tiles": dict(tile_grid=4),
    "rerank_k3": dict(topk=3, topk_rerank=True),
}


def _world():
    from conftest import make_serving_world
    return make_serving_world(seed=0, n_queries=4)


def _signs(rows):
    """The run's gallery-size trend: +1 / -1 for each change of G."""
    d = np.diff(rows)
    return [int(np.sign(x)) for x in d if x]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_staging_trace_identical_to_fresh_buffers(monkeypatch, path):
    """Reused buffers serve exactly what fresh ones do, while the round
    gallery shrinks, grows and shrinks again; after every round the rows
    past the real ones are zero with tags -1, and so are the query rows no
    query holds."""
    from conftest import drive_serving_trace, trace_key

    world = _world()
    kw = PATHS[path]
    orig_body = ServingEngine._round_body
    orig_assemble = engine_mod.assemble_round_gallery
    sizes = []

    def assemble(batch_keys, key_emb, min_rows=1, out=None):
        sizes.append(sum(len(key_emb[k]) for k in batch_keys))
        return orig_assemble(batch_keys, key_emb, min_rows, out)

    def checked_body(self, qs, stats, trace):
        n = len(sizes)
        orig_body(self, qs, stats, trace)
        if len(sizes) == n:
            return                          # no gallery this round
        st = self._staging
        G = sizes[-1]
        assert st.rows == G
        assert not st.gal[G:].any()
        for tag in (st.cam, st.frame, st.seg, st.ct):
            assert (tag[G:] == -1).all()
        free = np.ones(len(st.q_feat), bool)
        free[self._slots] = False
        assert not st.q_feat[free].any()

    monkeypatch.setattr(engine_mod, "assemble_round_gallery", assemble)
    monkeypatch.setattr(ServingEngine, "_round_body", checked_body)
    _, trace, summary = drive_serving_trace(world, POLICY, **kw)
    signs = _signs(sizes)
    trend = [s for i, s in enumerate(signs) if i == 0 or s != signs[i - 1]]
    assert any(trend[i:i + 3] == [-1, 1, -1] for i in range(len(trend))), \
        f"the gallery never shrank, grew and shrank again: {sizes}"

    def fresh_body(self, qs, stats, trace):
        self._staging = RoundStaging()
        orig_body(self, qs, stats, trace)

    monkeypatch.setattr(ServingEngine, "_round_body", fresh_body)
    _, ref_trace, ref_summary = drive_serving_trace(world, POLICY, **kw)
    assert trace_key(trace) == trace_key(ref_trace)
    assert summary["per_query"] == ref_summary["per_query"]


def test_staging_allocs_hold_after_priming(monkeypatch):
    """Primed to the run's peaks, the engine allocates its gallery and
    query buffers once, at its first round, and never again; unprimed, the
    counter moves only at that first round or when a high-water mark
    grows."""
    from conftest import drive_serving_trace
    from repro import api as rexcam

    world = _world()
    reads = []
    orig_tick = ServingEngine.tick

    def tick(self, record_trace=None):
        out = orig_tick(self, record_trace)
        reads.append((self.staging_allocs, self.padded_gallery_rows,
                      self._batch_hwm))
        return out

    monkeypatch.setattr(ServingEngine, "tick", tick)
    eng, _, _ = drive_serving_trace(world, POLICY)
    assert reads[-1][0] >= 2
    for prev, cur in zip(reads, reads[1:]):
        if cur[0] != prev[0]:
            assert prev[0] == 0 or cur[1:] != prev[1:], (prev, cur)
    peak_rows, peak_batch = eng.padded_gallery_rows, eng._batch_hwm

    reads.clear()
    orig_serve = rexcam.serve

    def serve(*a, **kw):
        e = orig_serve(*a, **kw)
        e.prime_batch(peak_batch)
        e.prime_gallery(peak_rows)
        return e

    monkeypatch.setattr(rexcam, "serve", serve)
    drive_serving_trace(world, POLICY)
    allocs = [r[0] for r in reads if r[0]]
    assert allocs and set(allocs) == {2}, allocs
    assert {r[1:] for r in reads} == {(peak_rows, peak_batch)}


def test_results_held_across_refill(monkeypatch):
    """What a round hands out (its trace records, the queries' features,
    the step's own outputs) is unchanged after later rounds refill the
    staging it was ranked from — the case where an array built from a
    buffer aliases it, as the CPU backend may."""
    import jax
    from conftest import drive_serving_trace

    world = _world()
    held = []
    step = {}
    orig_rank = ServingEngine._dispatch_rank_advance_seg
    orig_body = ServingEngine._round_body

    def rank(self, *args):
        step["out"] = orig_rank(self, *args)
        return step["out"]

    def body(self, qs, stats, trace):
        n = len(trace)
        orig_body(self, qs, stats, trace)
        out = step.pop("out", None)
        held.append((
            out, None if out is None else jax.tree.map(np.array, out),
            [(q.feat, q.feat.copy()) for q in qs],
            [(r, {k: np.array(v) for k, v in r.items()})
             for r in trace[n:]]))

    monkeypatch.setattr(ServingEngine, "_dispatch_rank_advance_seg", rank)
    monkeypatch.setattr(ServingEngine, "_round_body", body)
    drive_serving_trace(world, POLICY)
    assert sum(h[0] is not None for h in held) > 10
    for out, copy, feats, records in held:
        if out is not None:
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(copy)):
                np.testing.assert_array_equal(np.asarray(a), b)
        for feat, feat_copy in feats:
            np.testing.assert_array_equal(feat, feat_copy)
        for r, c in records:
            for k in r:
                np.testing.assert_array_equal(np.asarray(r[k]), c[k])


@pytest.mark.parametrize("path", ["segments", "rerank_k3", "tiles"])
def test_host_match_rows_equal_device_match_emb(monkeypatch, path):
    """The embedding a matched query folds in, read from the host gallery
    at ``_match_rows``' index, is bit for bit the step's ``gallery[idx0]``
    row; and the rank step receives admit's own device masks."""
    import jax
    from conftest import drive_serving_trace

    world = _world()
    name = ("_dispatch_rank_advance_tiles" if path == "tiles"
            else "_dispatch_rank_advance_seg")
    orig_rank = getattr(ServingEngine, name)
    orig_scatter = ServingEngine._scatter
    orig_plan = ServingEngine._plan_round
    step = {}
    checked = [0]

    def plan_round(self, qs):
        step["plan"] = orig_plan(self, qs)
        return step["plan"]

    def rank(self, ps, q_feat, q_seg, mask, *rest):
        plan = step["plan"]
        assert isinstance(mask, jax.Array)
        assert mask is (plan.mask_ct_dev if path == "tiles"
                        else plan.mask_dev)
        out = orig_rank(self, ps, q_feat, q_seg, mask, *rest)
        step["match_emb"] = np.asarray(out[3])
        return out

    def scatter(self, qs, ps, matched, match_cam, emb_rows, gallery):
        if gallery is not None:
            me = step.pop("match_emb")
            for j in np.flatnonzero(matched):
                assert emb_rows[j] >= 0
                np.testing.assert_array_equal(gallery[emb_rows[j]], me[j])
                checked[0] += 1
        return orig_scatter(self, qs, ps, matched, match_cam, emb_rows,
                            gallery)

    monkeypatch.setattr(ServingEngine, "_plan_round", plan_round)
    monkeypatch.setattr(ServingEngine, name, rank)
    monkeypatch.setattr(ServingEngine, "_scatter", scatter)
    drive_serving_trace(world, POLICY, **PATHS[path])
    assert checked[0] > 0


@pytest.mark.parametrize("rerank", [False, True])
def test_match_rows_follow_the_rerank_vote(rerank):
    """One query, three passing rows: camera 0 holds the best single score
    (band 0), camera 1 the two next, whose summed vote wins under
    ``topk_rerank``.  ``match_rows`` names the row the step gathered as
    ``match_emb``: band 0's without the vote, camera 1's best with it."""
    import jax.numpy as jnp
    from repro.runtime.engine import match_rows, rank_round_seg

    s = np.array([0.95, 0.90, 0.89], np.float32)
    gal = np.zeros((4, 4), np.float32)
    gal[:3, 0], gal[:3, 1] = s, np.sqrt(1 - s * s)
    q_feat = np.eye(4, dtype=np.float32)[:1]
    gal_cam = np.array([0, 1, 1, -1], np.int32)
    gal_seg = np.array([0, 0, 0, -1], np.int32)
    m, mc, me, tv, ti, tc, tf = (np.asarray(a) for a in rank_round_seg(
        jnp.asarray(q_feat), jnp.zeros(1, jnp.int32), jnp.ones((1, 2), bool),
        jnp.asarray(gal), jnp.asarray(gal_cam), jnp.asarray(gal_seg),
        jnp.asarray(gal_seg), 0.2, k=3, topk_rerank=rerank))
    assert m[0] and tc[0, 0] == 0
    assert mc[0] == (1 if rerank else 0)
    rows = match_rows(m, mc, ti, tc, rerank)
    assert rows[0] == (1 if rerank else 0)
    np.testing.assert_array_equal(gal[rows[0]], me[0])
