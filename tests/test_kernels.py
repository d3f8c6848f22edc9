"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal image: deterministic fallback shim
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd,bq,bk", [
    (1, 4, 4, 128, 64, 64, 64),     # MHA
    (2, 8, 2, 256, 64, 64, 128),    # GQA
    (1, 16, 1, 128, 128, 32, 64),   # MQA
    (2, 4, 4, 192, 32, 64, 96),     # non-pow2 seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(dtype, B, H, KV, S, hd, bq, bk, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,T,hd,bk", [
    (2, 8, 2, 512, 64, 128),
    (1, 4, 4, 1024, 128, 256),
    (3, 16, 4, 256, 64, 64),
])
def test_decode_attention_sweep(dtype, B, H, KV, T, hd, bk):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, KV, T, hd), dtype)
    vc = jax.random.normal(ks[2], (B, KV, T, hd), dtype)
    length = jax.random.randint(ks[3], (B,), 1, T + 1)
    out = ops.decode_attention(q, kc, vc, length, block_k=bk)
    want = ref.decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               **_tol(dtype))


_SWEEP = [
    (64, 512, 64, 8, 32, 128),
    (128, 1024, 32, 16, 128, 256),
    (32, 256, 128, 4, 32, 64),
    (33, 517, 16, 5, 32, 128),      # ragged: internal padding both axes
    (7, 70, 8, 3, 128, 512),        # smaller than one block on both axes
    (1, 1, 64, 1, 128, 512),
]


@pytest.mark.parametrize("Q,G,D,k,bq,bg,ties", [
    *[pytest.param(*c, False, id="-".join(map(str, c))) for c in _SWEEP],
    # 0/1 features: exact float32 ties spread over all six gallery blocks,
    # so the running merge must keep the oracle's lowest-column order
    *[pytest.param(40, 700, 8, k, 16, 128, True, id=f"ties-k{k}")
      for k in (1, 3, 5)],
])
def test_reid_topk_sweep(Q, G, D, k, bq, bg, ties):
    ks = jax.random.split(KEY, 2)
    if ties:
        q = jax.random.bernoulli(ks[0], 0.5, (Q, D)).astype(jnp.float32)
        g = jax.random.bernoulli(ks[1], 0.5, (G, D)).astype(jnp.float32)
    else:
        q = jax.random.normal(ks[0], (Q, D))
        g = jax.random.normal(ks[1], (G, D))
    sv, si = ops.reid_topk(q, g, k, block_q=bq, block_g=bg)
    rv, ri = ref.reid_topk_ref(q, g, k)
    np.testing.assert_allclose(sv, rv, rtol=1e-5, atol=1e-5)
    if ties:
        np.testing.assert_array_equal(si, ri)
    # indices: permutation-tolerant on ties — compare the score multiset
    np.testing.assert_allclose(np.sort(sv, 1), np.sort(rv, 1), rtol=1e-5)
    # gathered scores must match the claimed scores
    got = np.take_along_axis(np.asarray(q @ g.T), np.asarray(si), 1)
    np.testing.assert_allclose(got, sv, rtol=1e-5, atol=1e-5)


def test_reid_topk_k_exceeds_gallery():
    """k > G: real entries first, padding surfaces as (NEG_INF, -1)."""
    ks = jax.random.split(KEY, 2)
    q = jax.random.normal(ks[0], (5, 16))
    g = jax.random.normal(ks[1], (3, 16))
    sv, si = ops.reid_topk(q, g, 8)
    rv, ri = ref.reid_topk_ref(q, g, 3)
    np.testing.assert_allclose(sv[:, :3], rv, rtol=1e-5, atol=1e-5)
    assert (np.asarray(si)[:, 3:] == -1).all()
    assert (np.asarray(sv)[:, 3:] < -1e29).all()


def test_reid_topk_masked_matches_ref():
    """Segment-masked variant == oracle on a mixed (cam, frame) batch."""
    rng = np.random.default_rng(3)
    Q, G, C, D, k = 11, 83, 6, 32, 4
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    q_frame = jnp.asarray(rng.integers(0, 4, Q), jnp.int32)
    gal_cam = jnp.asarray(rng.integers(0, C, G), jnp.int32)
    gal_frame = jnp.asarray(rng.integers(0, 4, G), jnp.int32)
    adm = jnp.asarray(rng.random((Q, C)) < 0.5)
    sv, si = ops.reid_topk_masked(q, q_frame, adm, g, gal_cam, gal_frame, k)
    rv, ri = ref.reid_topk_masked_ref(q, q_frame, adm, g, gal_cam, gal_frame, k)
    np.testing.assert_allclose(sv, rv, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(si, ri)


@pytest.mark.parametrize("case", [
    "mixed",
    # exact ties across gallery blocks (G > block_g), k in {1, 3, 5}
    "ties-k1", "ties-k3", "ties-k5",
    # real hits only in the first gallery block: every later tile is all
    # NEG_INF and must leave the running top-k (and its sentinels) alone
    "neg-inf-tail",
])
def test_reid_topk_segments_matches_ref(case):
    """Segment-ID variant == oracle on a mixed (cam, segment) batch, and
    bit-for-bit on the merge's edge cases."""
    rng = np.random.default_rng(13)
    Q, G, C, D, k, bg = 11, 83, 6, 32, 4, 512
    if case != "mixed":
        Q, G, D, bg = 21, 600, 8, 128
        k = int(case[-1]) if case.startswith("ties") else 5
    draw = (lambda s: rng.integers(0, 2, s)) if case != "mixed" \
        else (lambda s: rng.normal(size=s))
    q = jnp.asarray(draw((Q, D)), jnp.float32)
    g = jnp.asarray(draw((G, D)), jnp.float32)
    q_seg = rng.integers(0, 4, Q)
    gal_cam = jnp.asarray(rng.integers(0, C, G), jnp.int32)
    gal_seg = rng.integers(0, 4, G)
    if case == "neg-inf-tail":
        gal_seg[bg:] = 99                   # no query holds segment 99
        gal_seg[:bg] = np.where(rng.random(bg) < 0.05, gal_seg[:bg], 99)
    q_seg, gal_seg = jnp.asarray(q_seg, jnp.int32), jnp.asarray(gal_seg,
                                                                 jnp.int32)
    adm = jnp.asarray(rng.random((Q, C)) < 0.5)
    sv, si = ops.reid_topk_segments(q, q_seg, adm, g, gal_cam, gal_seg, k,
                                    block_q=8, block_g=bg)
    rv, ri = ref.reid_topk_segments_ref(q, q_seg, adm, g, gal_cam, gal_seg, k)
    np.testing.assert_allclose(sv, rv, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(si, ri)
    if case == "neg-inf-tail":              # rows short of k real hits
        assert ((np.asarray(si) == -1).any(axis=1)
                & (np.asarray(si) >= 0).any(axis=1)).any()


def test_reid_topk_segments_relabel_bit_identical_to_masked():
    """An injective frame -> segment relabeling changes NOTHING: same
    masked score matrix in, so the kernel's tie-breaks produce bit-identical
    (values, indices).  This is the consolidation plane's trace-identity
    contract — integer-valued features force exact float32 ties so the
    comparison is bit-for-bit, not allclose."""
    rng = np.random.default_rng(29)
    Q, G, C, D, k = 17, 131, 5, 8, 3
    q = jnp.asarray(rng.integers(0, 2, (Q, D)), jnp.float32)
    g = jnp.asarray(rng.integers(0, 2, (G, D)), jnp.float32)
    frames = np.array([3, 11, 40, 97], np.int32)       # sparse frame ids
    q_frame = frames[rng.integers(0, 4, Q)]
    gal_frame = frames[rng.integers(0, 4, G)]
    gal_cam = jnp.asarray(rng.integers(0, C, G), jnp.int32)
    adm = jnp.asarray(rng.random((Q, C)) < 0.6)
    # the RoundPlan relabeling: sorted unique frames -> compact segment ids
    seg_of = {int(f): s for s, f in enumerate(sorted(set(frames)))}
    q_seg = np.array([seg_of[int(f)] for f in q_frame], np.int32)
    gal_seg = np.array([seg_of[int(f)] for f in gal_frame], np.int32)
    msv, msi = ops.reid_topk_masked(
        q, jnp.asarray(q_frame), adm, g, gal_cam, jnp.asarray(gal_frame), k)
    ssv, ssi = ops.reid_topk_segments(
        q, jnp.asarray(q_seg), adm, g, gal_cam, jnp.asarray(gal_seg), k)
    np.testing.assert_array_equal(np.asarray(msv), np.asarray(ssv))
    np.testing.assert_array_equal(np.asarray(msi), np.asarray(ssi))


@pytest.mark.parametrize("case", [
    "mixed",
    # camera-sorted gallery and per-q-block camera admission: most
    # (q-block, g-block) pairs are dead and skip the GEMM + merge entirely
    "dead-blocks",
])
def test_reid_topk_tiles_matches_ref(case):
    """Tile-masked variant == oracle on a mixed (segment, fused-cell) batch
    — including unlabeled gallery rows (``gal_ct == -1``), which must match
    nothing rather than wrap into cell C*T*T - 1."""
    rng = np.random.default_rng(41)
    Q, G, C, T, D, k = 11, 83, 6, 3, 32, 4
    if case == "dead-blocks":
        Q, G, D, k = 40, 700, 8, 3
    TT = T * T
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    q_seg = jnp.asarray(rng.integers(0, 4, Q), jnp.int32)
    gal_seg = jnp.asarray(rng.integers(0, 4, G), jnp.int32)
    gal_cam = rng.integers(0, C, G)
    adm_ct = rng.random((Q, C * TT)) < 0.4
    if case == "dead-blocks":
        gal_cam = np.sort(gal_cam)
        blk_cam = rng.integers(0, C, Q // 8)    # one camera per q-block
        adm_ct &= np.repeat(np.repeat(blk_cam, 8)[:, None]
                            == np.arange(C)[None, :], TT, axis=1)
    gal_ct = jnp.asarray(
        np.where(rng.random(G) < 0.15, -1,
                 gal_cam * TT + rng.integers(0, TT, G)), jnp.int32)
    adm_ct = jnp.asarray(adm_ct)
    sv, si = ops.reid_topk_tiles(q, q_seg, adm_ct, g, gal_ct, gal_seg, k,
                                 block_q=8, block_g=128)
    rv, ri = ref.reid_topk_tiles_ref(q, q_seg, adm_ct, g, gal_ct, gal_seg, k)
    np.testing.assert_allclose(sv, rv, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(si, ri)
    # every unlabeled row stayed invisible: no claimed index points at one
    unlabeled = set(np.flatnonzero(np.asarray(gal_ct) == -1).tolist())
    assert not (set(np.asarray(si).ravel().tolist()) - {-1}) & unlabeled


def test_reid_topk_tiles_all_admitted_bit_identical_to_segments():
    """The tile plane's trace-identity contract: with every tile of every
    admitted camera open (``admit_ct = repeat(admit, T*T)``) the tile kernel
    is BIT-identical to ``reid_topk_segments`` — same flat-argmin
    tie-breaks, same (NEG_INF, -1) sentinels.  Integer-valued features force
    exact float32 ties so the comparison is bit-for-bit, not allclose."""
    rng = np.random.default_rng(53)
    Q, G, C, T, D, k = 17, 131, 5, 4, 8, 3
    TT = T * T
    q = jnp.asarray(rng.integers(0, 2, (Q, D)), jnp.float32)
    g = jnp.asarray(rng.integers(0, 2, (G, D)), jnp.float32)
    q_seg = jnp.asarray(rng.integers(0, 4, Q), jnp.int32)
    gal_seg = jnp.asarray(rng.integers(0, 4, G), jnp.int32)
    gal_cam = rng.integers(0, C, G)
    gal_tile = rng.integers(0, TT, G)
    gal_ct = jnp.asarray(gal_cam * TT + gal_tile, jnp.int32)
    adm = rng.random((Q, C)) < 0.6
    adm_ct = jnp.asarray(np.repeat(adm, TT, axis=1))
    ssv, ssi = ops.reid_topk_segments(
        q, q_seg, jnp.asarray(adm), g, jnp.asarray(gal_cam, jnp.int32),
        gal_seg, k)
    tsv, tsi = ops.reid_topk_tiles(q, q_seg, adm_ct, g, gal_ct, gal_seg, k)
    np.testing.assert_array_equal(np.asarray(ssv), np.asarray(tsv))
    np.testing.assert_array_equal(np.asarray(ssi), np.asarray(tsi))
    # and closing one camera's tiles is exactly closing the camera: the
    # fused-cell mask degrades to the camera mask it was built from
    adm2 = adm.copy()
    adm2[:, 2] = False
    adm_ct2 = np.repeat(adm, TT, axis=1)
    adm_ct2[:, 2 * TT:3 * TT] = False
    s2 = ops.reid_topk_segments(q, q_seg, jnp.asarray(adm2), g,
                                jnp.asarray(gal_cam, jnp.int32), gal_seg, k)
    t2 = ops.reid_topk_tiles(q, q_seg, jnp.asarray(adm_ct2), g, gal_ct,
                             gal_seg, k)
    np.testing.assert_array_equal(np.asarray(s2[0]), np.asarray(t2[0]))
    np.testing.assert_array_equal(np.asarray(s2[1]), np.asarray(t2[1]))


def test_reid_topk_tiles_fully_masked_surfaces_sentinels():
    """All-closed admission and all-unlabeled galleries both rank every row
    to the kernels' (NEG_INF, -1) padding convention."""
    rng = np.random.default_rng(59)
    Q, G, C, T, D, k = 5, 37, 4, 2, 16, 2
    TT = T * T
    q = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    q_seg = jnp.zeros(Q, jnp.int32)
    gal_seg = jnp.zeros(G, jnp.int32)
    gal_ct = jnp.asarray(rng.integers(0, C * TT, G), jnp.int32)
    closed = jnp.zeros((Q, C * TT), bool)
    sv, si = ops.reid_topk_tiles(q, q_seg, closed, g, gal_ct, gal_seg, k)
    assert (np.asarray(si) == -1).all() and (np.asarray(sv) < -1e29).all()
    open_ct = jnp.ones((Q, C * TT), bool)
    unlabeled = jnp.full(G, -1, jnp.int32)
    sv, si = ops.reid_topk_tiles(q, q_seg, open_ct, g, unlabeled, gal_seg, k)
    assert (np.asarray(si) == -1).all() and (np.asarray(sv) < -1e29).all()


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 24), st.integers(0, 70), st.integers(2, 5),
       st.integers(1, 4), st.booleans())
def test_reid_rank_parity_property(Q, G, C, k, ties):
    """Property (ragged Q/G, ties, empty galleries): the Pallas kernel in
    interpret mode, the ref.py oracle, and the engine's match outcome all
    agree.  Tie cases use integer-valued features so float32 scores are
    exact and index tie-breaking is comparable bit-for-bit."""
    from repro.runtime.engine import rank_round

    rng = np.random.default_rng(100_000 + Q * 1000 + G * 10 + C + k)
    D = 8
    draw = (lambda s: rng.integers(0, 2, s).astype(np.float32)) if ties \
        else (lambda s: rng.normal(size=s).astype(np.float32))
    qf, gf = draw((Q, D)), draw((G, D))

    # -- plain kernel vs oracle ------------------------------------------
    sv, si = ops.reid_topk(jnp.asarray(qf), jnp.asarray(gf), k)
    if G == 0:
        assert (np.asarray(si) == -1).all()
        assert (np.asarray(sv) < -1e29).all()
    else:
        kk = min(k, G)
        rv, ri = ref.reid_topk_ref(jnp.asarray(qf), jnp.asarray(gf), kk)
        np.testing.assert_allclose(np.asarray(sv)[:, :kk], rv,
                                   rtol=1e-5, atol=1e-5)
        if ties:
            np.testing.assert_array_equal(np.asarray(si)[:, :kk], ri)
        assert (np.asarray(si)[:, kk:] == -1).all()

    # -- masked kernel vs oracle vs the engine's match path --------------
    q_frame = rng.integers(0, 3, Q).astype(np.int32)
    gal_cam = rng.integers(0, C, G).astype(np.int32)
    gal_frame = rng.integers(0, 3, G).astype(np.int32)
    adm = rng.random((Q, C)) < 0.6
    thresh = 0.6
    if G > 0:
        kk = min(k, G)
        msv, msi = ops.reid_topk_masked(
            jnp.asarray(qf), jnp.asarray(q_frame), jnp.asarray(adm),
            jnp.asarray(gf), jnp.asarray(gal_cam), jnp.asarray(gal_frame), kk)
        rmv, rmi = ref.reid_topk_masked_ref(
            jnp.asarray(qf), jnp.asarray(q_frame), jnp.asarray(adm),
            jnp.asarray(gf), jnp.asarray(gal_cam), jnp.asarray(gal_frame), kk)
        np.testing.assert_allclose(msv, rmv, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(msi, rmi)
        # the segment-ID entry under the round-scoped relabeling is
        # bit-identical to the frame-tag variant (consolidation contract)
        seg_of = {f: s for s, f in enumerate(sorted(set(q_frame) |
                                                    set(gal_frame)))}
        ssv, ssi = ops.reid_topk_segments(
            jnp.asarray(qf),
            jnp.asarray([seg_of[f] for f in q_frame], jnp.int32),
            jnp.asarray(adm), jnp.asarray(gf), jnp.asarray(gal_cam),
            jnp.asarray([seg_of[f] for f in gal_frame], jnp.int32), kk)
        np.testing.assert_array_equal(np.asarray(msv), np.asarray(ssv))
        np.testing.assert_array_equal(np.asarray(msi), np.asarray(ssi))
        # and the tile entry with every tile open degrades to the segment
        # entry bit-for-bit (the sub-frame plane's all-admitted contract)
        TT = 4
        gal_ct = jnp.asarray(gal_cam * TT + rng.integers(0, TT, G), jnp.int32)
        tsv, tsi = ops.reid_topk_tiles(
            jnp.asarray(qf),
            jnp.asarray([seg_of[f] for f in q_frame], jnp.int32),
            jnp.asarray(np.repeat(adm, TT, axis=1)), jnp.asarray(gf),
            gal_ct, jnp.asarray([seg_of[f] for f in gal_frame], jnp.int32),
            kk)
        np.testing.assert_array_equal(np.asarray(msv), np.asarray(tsv))
        np.testing.assert_array_equal(np.asarray(msi), np.asarray(tsi))

    (matched, match_cam, match_emb, topk_val, topk_idx, topk_cam,
     topk_frame) = (
        np.asarray(a) for a in rank_round(
        jnp.asarray(qf), jnp.asarray(q_frame), jnp.asarray(adm),
        jnp.asarray(gf), jnp.asarray(gal_cam), jnp.asarray(gal_frame), thresh))
    best_val, best_idx = topk_val[:, 0], topk_idx[:, 0]
    # numpy mirror of the pre-device host ranking loop
    for i in range(Q):
        valid = adm[i, gal_cam] & (gal_frame == q_frame[i]) if G else \
            np.zeros(0, bool)
        d = np.where(valid, 1.0 - gf.astype(np.float32) @ qf[i], 1e30) if G \
            else np.zeros(0)
        if not valid.any():
            assert not matched[i]
            # fully-masked rows surface the kernels' padding convention
            assert best_idx[i] == -1 and best_val[i] < -1e29
            continue
        j = int(np.argmin(d))
        assert bool(matched[i]) == bool(d[j] < thresh)
        if matched[i]:
            assert int(match_cam[i]) == int(gal_cam[j])
            np.testing.assert_allclose(match_emb[i], gf[j], rtol=1e-6)


@pytest.mark.parametrize("B,L,D,N,chunk,bd", [
    (2, 128, 64, 16, 32, 32),
    (1, 256, 128, 8, 64, 64),
    (2, 64, 32, 4, 64, 16),
])
def test_mamba_scan_sweep(B, L, D, N, chunk, bd):
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (B, L, D)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, D))) * 0.1
    Bm = jax.random.normal(ks[2], (B, L, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, L, N)) * 0.5
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    y = ops.mamba_scan(u, dt, Bm, Cm, A, chunk=chunk, block_d=bd)
    want, _ = ref.mamba_scan_ref(u, dt, Bm, Cm, A, jnp.zeros((B, D, N)))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([64, 128]), st.sampled_from([32, 64]),
       st.booleans())
def test_flash_attention_property(B, S, hd, causal):
    """Property: kernel == oracle across hypothesis-drawn shapes."""
    ks = jax.random.split(jax.random.PRNGKey(B * S + hd), 3)
    H = KV = 2
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    out = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_model_blockwise_matches_kernel_semantics():
    """The pure-JAX model attention and the Pallas kernel agree (same math)."""
    from repro.configs import get_smoke_config
    from repro.models import attention as mattn

    cfg = get_smoke_config("yi_6b")
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, hd = 2, 64, cfg.num_padded_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out_model = mattn.blockwise_attention(q, k, v, cfg, causal=True)
    out_kernel = ops.flash_attention(
        q.transpose(0, 2, 1, 3),
        jnp.take(k, mattn.kv_map(cfg), axis=2).transpose(0, 2, 1, 3),
        jnp.take(v, mattn.kv_map(cfg), axis=2).transpose(0, 2, 1, 3),
        causal=True, block_q=32, block_k=32).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out_model, out_kernel, rtol=2e-5, atol=2e-5)


def test_balanced_causal_schedule_matches_masked():
    from repro.configs import get_smoke_config
    from repro.models import attention as mattn

    cfg = get_smoke_config("deepseek_7b")
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, hd = 2, 64, cfg.num_padded_heads, cfg.num_kv_heads, cfg.head_dim
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    a = mattn.blockwise_attention(q, k, v, cfg, causal=True, causal_skip=False)
    b = mattn.blockwise_attention(q, k, v, cfg, causal=True, causal_skip=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
