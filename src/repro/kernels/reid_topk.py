"""Fused gallery ranking: similarity GEMM + running top-k — Pallas kernel.

The paper's inference-time hot loop (Fig. 2): rank a gallery of detected
objects by feature distance to the query.  TPU adaptation (DESIGN.md §3):
the distance reduces to an inner-product GEMM on the MXU (features are
L2-normalized: d = 2 - 2*s), and the ranking keeps a (block_q, K) running
top-k in VMEM merged tile-by-tile across gallery blocks — the full (Q, G)
score matrix never reaches HBM.

Ragged shapes: real gallery sizes are whatever the admission filter lets
through, so both entry points pad Q/G up to block multiples internally and
mask the padding to NEG_INF inside the kernel (padded indices come back as
-1 in the returned top-k).

``reid_topk_masked`` is the serving-engine variant: one deduplicated
embedding batch per round, where query q may only score gallery row g when
``admit[q, gal_cam[g]]`` is set and ``gal_frame[g] == q_frame[q]`` — the
segment mask is enforced on-device (camera membership via a one-hot GEMM,
MXU-friendly; no (Q, G) mask ever materializes in HBM).

Grid (nq, ng): gallery axis innermost, top-k state carried in VMEM scratch.

The score GEMM asks for ``Precision.HIGHEST``: at the default a v5e
contracts f32 operands in one bf16 pass, which moves scores by ~1e-3 and
reorders near-ties against the f32 oracle in ``kernels/ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# byte budget for one buffer of the tile kernel's (CTp, block_g) f32
# one-hot block: double-buffered, it keeps half of the 16 MiB scoped-VMEM
# default, which leaves room for the (block_q, CTp) admission block
_ONEHOT_BLOCK_BYTES = 4 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_rows(a, n: int, fill):
    pad = n - a.shape[0]
    if pad <= 0:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0)


def _blocks(dim: int, block: int, align: int):
    """Shrink ``block`` to the (aligned) extent of a small axis, then round
    the axis up to a whole number of blocks."""
    block = min(block, _round_up(dim, align))
    return block, _round_up(dim, block)


def _merge_topk(s, cols, val_scr, idx_scr, k: int):
    """Fold one (block_q, block_g) score tile into the running VMEM top-k.

    Exactly a stable top-k over the lane concatenation [scratch, tile]:
    values descend, and equal values go to the scratch entry first (lowest
    slot), then to the lowest tile column.  Scratch entries carry lower
    global columns than the tile, so ties resolve to the lowest global
    column, and a NEG_INF tile never displaces a scratch slot.  Built from
    k static passes of row max + lowest position among the maxima — the
    reductions and selects Mosaic lowers (no ``top_k``, no gathers, no
    unaligned lane concatenation)."""
    sv, si = val_scr[...], idx_scr[...]                   # (block_q, k)
    slot = jax.lax.broadcasted_iota(jnp.int32, sv.shape, 1)
    out_v, out_i = sv, si
    for j in range(k):
        ms = jnp.max(sv, axis=1, keepdims=True)
        mt = jnp.max(s, axis=1, keepdims=True)
        from_scr = ms >= mt                               # ties: scratch first
        ps = jnp.min(jnp.where(sv == ms, slot, k), axis=1, keepdims=True)
        scr_idx = jnp.max(jnp.where(slot == ps, si, jnp.iinfo(jnp.int32).min),
                          axis=1, keepdims=True)
        pt = jnp.min(jnp.where(s == mt, cols, jnp.iinfo(jnp.int32).max),
                     axis=1, keepdims=True)
        out_v = jnp.where(slot == j, jnp.where(from_scr, ms, mt), out_v)
        out_i = jnp.where(slot == j, jnp.where(from_scr, scr_idx, pt), out_i)
        # retire the pick: -inf sits below every score and NEG_INF slot
        sv = jnp.where(from_scr & (slot == ps), -jnp.inf, sv)
        s = jnp.where(~from_scr & (cols == pt), -jnp.inf, s)
    val_scr[...] = out_v
    idx_scr[...] = out_i


def _reid_kernel(q_ref, g_ref, sv_ref, si_ref, val_scr, idx_scr, *,
                 k: int, block_g: int, ng: int, g_real: int):
    gi = pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, NEG_INF)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    q = q_ref[...].astype(jnp.float32)                    # (block_q, D)
    g = g_ref[...].astype(jnp.float32)                    # (block_g, D)
    s = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (block_q, block_g)
    base = gi * block_g
    cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < g_real, s, NEG_INF)              # gallery padding
    _merge_topk(s, cols, val_scr, idx_scr, k)

    @pl.when(gi == ng - 1)
    def _finalize():
        sv_ref[...] = val_scr[...]
        si_ref[...] = idx_scr[...]


def _mask_padded(sv, si):
    """Padded / fully-masked slots surface as idx -1."""
    return sv, jnp.where(sv > NEG_INF / 2, si, -1)


def _empty(Q: int, k: int):
    return (jnp.full((Q, k), NEG_INF, jnp.float32),
            jnp.full((Q, k), -1, jnp.int32))


def reid_topk(queries, gallery, k: int, *, block_q: int = 128,
              block_g: int = 512, interpret: bool = False):
    """queries: (Q, D); gallery: (G, D) -> (scores (Q, k), idx (Q, k)).

    Scores are inner products, descending (for unit features,
    distance = 2 - 2*score).  Q and G may be any size: inputs are padded to
    block multiples internally and padded slots come back as (NEG_INF, -1).
    """
    Q, D = queries.shape
    G = gallery.shape[0]
    if Q == 0 or G == 0:
        return _empty(Q, k)
    block_q, Qp = _blocks(Q, block_q, 8)
    block_g, Gp = _blocks(G, block_g, 128)
    nq, ng = Qp // block_q, Gp // block_g

    kernel = functools.partial(_reid_kernel, k=k, block_g=block_g, ng=ng,
                               g_real=G)
    sv, si = pl.pallas_call(
        kernel,
        grid=(nq, ng),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_g, D), lambda qi, gi: (gi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(_pad_rows(queries, Qp, 0), _pad_rows(gallery, Gp, 0))
    return _mask_padded(sv[:Q], si[:Q])


def _reid_masked_kernel(q_ref, qf_ref, adm_ref, g_ref, gf_ref, oh_ref,
                        sv_ref, si_ref, val_scr, idx_scr, *,
                        k: int, block_g: int, ng: int, g_real: int):
    gi = pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, NEG_INF)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    q = q_ref[...].astype(jnp.float32)                    # (block_q, D)
    g = g_ref[...].astype(jnp.float32)                    # (block_g, D)
    s = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (block_q, block_g)
    # camera admission via one-hot GEMM: (block_q, C) @ (C, block_g) on the
    # MXU — avoids a lane-axis gather of admit[:, gal_cam]
    cam_ok = jax.lax.dot_general(
        adm_ref[...].astype(jnp.float32), oh_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) > 0.5
    frame_ok = qf_ref[...] == gf_ref[...]                 # (block_q, block_g)
    base = gi * block_g
    cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cam_ok & frame_ok & (cols < g_real), s, NEG_INF)
    _merge_topk(s, cols, val_scr, idx_scr, k)

    @pl.when(gi == ng - 1)
    def _finalize():
        sv_ref[...] = val_scr[...]
        si_ref[...] = idx_scr[...]


def _segment_masked_call(queries, q_tag, admit, gallery, gal_cam, gal_tag,
                         k: int, block_q: int, block_g: int, interpret: bool):
    """Shared padded pallas_call behind the frame-masked and segment-ID
    entry points.  Query q scores gallery row g only when
    ``admit[q, gal_cam[g]]`` and ``gal_tag[g] == q_tag[q]`` — the tag is the
    content frame for ``reid_topk_masked`` and the round-scoped segment id
    for ``reid_topk_segments``; int equality is the same kernel either way.
    Padding keeps the tags disjoint (query side -1, gallery side -2) so a
    padded slot can never pair with anything real or padded."""
    Q, D = queries.shape
    G = gallery.shape[0]
    C = admit.shape[1]
    if Q == 0 or G == 0:
        return _empty(Q, k)
    block_q, Qp = _blocks(Q, block_q, 8)
    block_g, Gp = _blocks(G, block_g, 128)
    Cp = _round_up(C, 8)
    nq, ng = Qp // block_q, Gp // block_g

    queries = _pad_rows(queries, Qp, 0)
    q_tag = _pad_rows(jnp.asarray(q_tag, jnp.int32)[:, None], Qp, -1)
    admit = _pad_rows(admit.astype(jnp.float32), Qp, 0.0)
    admit = jnp.pad(admit, ((0, 0), (0, Cp - C)))
    gallery = _pad_rows(gallery, Gp, 0)
    gal_cam = _pad_rows(jnp.asarray(gal_cam, jnp.int32), Gp, -1)
    gal_tag = _pad_rows(jnp.asarray(gal_tag, jnp.int32), Gp, -2)[None, :]
    # (Cp, Gp) camera one-hot; padded rows (cam -1) match no camera
    onehot = (gal_cam[None, :] == jnp.arange(Cp)[:, None]).astype(jnp.float32)

    kernel = functools.partial(_reid_masked_kernel, k=k, block_g=block_g,
                               ng=ng, g_real=G)
    sv, si = pl.pallas_call(
        kernel,
        grid=(nq, ng),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, Cp), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_g, D), lambda qi, gi: (gi, 0)),
            pl.BlockSpec((1, block_g), lambda qi, gi: (0, gi)),
            pl.BlockSpec((Cp, block_g), lambda qi, gi: (0, gi)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, q_tag, admit, gallery, gal_tag, onehot)
    return _mask_padded(sv[:Q], si[:Q])


def reid_topk_masked(queries, q_frame, admit, gallery, gal_cam, gal_frame,
                     k: int, *, block_q: int = 128, block_g: int = 512,
                     interpret: bool = False):
    """Segment-masked gallery ranking over one deduplicated embedding batch.

    queries (Q, D); q_frame (Q,) int32 — the content frame each query's
    cursor is on; admit (Q, C) bool — the admission mask; gallery (G, D);
    gal_cam / gal_frame (G,) int32 — which (camera, frame) each gallery row
    came from.  Query q scores row g only when ``admit[q, gal_cam[g]]`` and
    ``gal_frame[g] == q_frame[q]``; everything else is NEG_INF.  Returns
    (scores (Q, k), idx (Q, k)) with fully-masked slots as (NEG_INF, -1).
    """
    return _segment_masked_call(queries, q_frame, admit, gallery, gal_cam,
                                gal_frame, k, block_q, block_g, interpret)


def reid_topk_segments(queries, q_seg, admit, gallery, gal_cam, gal_seg,
                       k: int, *, block_q: int = 128, block_g: int = 512,
                       interpret: bool = False):
    """Consolidated-round ranking: frame tags replaced by round-scoped
    segment ids.

    The engine's consolidation plane relabels each round's distinct content
    frames to compact segment ids (an injective per-round map), tags every
    query (``q_seg``, (Q,) int32) and gallery row (``gal_seg``, (G,) int32)
    with its segment, and ranks ALL live queries in one call.  Because the
    relabeling is injective, ``gal_seg[g] == q_seg[q]`` holds exactly when
    the underlying frames agree — the masked score matrix, and therefore
    every flat-argmin tie-break, is bit-identical to per-frame
    ``reid_topk_masked``.  Returns (scores (Q, k), idx (Q, k)) with
    fully-masked slots as (NEG_INF, -1).
    """
    return _segment_masked_call(queries, q_seg, admit, gallery, gal_cam,
                                gal_seg, k, block_q, block_g, interpret)


def _reid_tiles_kernel(q_ref, qt_ref, adm_ref, g_ref, gt_ref, oh_ref,
                       live_ref, sv_ref, si_ref, val_scr, idx_scr, *,
                       k: int, block_g: int, ng: int, g_real: int):
    """The segment-masked kernel body over the fused (camera x tile) axis,
    with a per-(q-block, g-block) liveness predicate: when no query row of
    this block admits any (camera, tile) cell present in this gallery block,
    the GEMM + merge are skipped entirely.  Skipping is provably free: every
    score the skipped block would contribute is NEG_INF, and ``_merge_topk``
    resolves NEG_INF ties in favor of the existing scratch entries — the
    scratch is bit-identical either way."""
    qi, gi = pl.program_id(0), pl.program_id(1)

    @pl.when(gi == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, NEG_INF)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    @pl.when(live_ref[qi * ng + gi] > 0)
    def _score():
        q = q_ref[...].astype(jnp.float32)                # (block_q, D)
        g = g_ref[...].astype(jnp.float32)                # (block_g, D)
        s = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        # (cam, tile) admission via one-hot GEMM over the fused axis —
        # same MXU shape as camera admission, just C*T*T columns
        ct_ok = jax.lax.dot_general(
            adm_ref[...].astype(jnp.float32), oh_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) > 0.5
        tag_ok = qt_ref[...] == gt_ref[...]               # (block_q, block_g)
        base = gi * block_g
        cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(ct_ok & tag_ok & (cols < g_real), s, NEG_INF)
        _merge_topk(s, cols, val_scr, idx_scr, k)

    @pl.when(gi == ng - 1)
    def _finalize():
        sv_ref[...] = val_scr[...]
        si_ref[...] = idx_scr[...]


def reid_topk_tiles(queries, q_tag, admit_ct, gallery, gal_ct, gal_tag,
                    k: int, *, block_q: int = 128, block_g: int = 512,
                    interpret: bool = False):
    """Tile-granular gallery ranking: camera admission refined to sub-frame
    (camera, tile) cells, structurally the segment-masked kernel over a
    bigger "camera" axis.

    queries (Q, D); q_tag (Q,) int32 round-scoped segment ids; admit_ct
    (Q, C*T*T) bool — ``admit_ct[q, c*T*T + t]`` fuses camera admission AND
    the learned tile-admit mask; gallery (G, D); gal_ct (G,) int32 — each
    row's fused cell id ``gal_cam*T*T + gal_tile`` (rows with no tile label
    may carry -1: they match nothing); gal_tag (G,) int32 segment ids.
    Eligibility = ``admit_ct[q, gal_ct[g]]`` AND ``gal_tag[g] == q_tag[q]``.

    With every tile admitted, ``admit_ct[q, gal_ct[g]] == admit[q, gal_cam[g]]``
    for all rows, so the masked score matrix — and therefore every
    flat-argmin tie-break and (NEG_INF, -1) sentinel — is bit-identical to
    ``reid_topk_segments``: the camera-granular path is this kernel's
    differential oracle.

    The grid additionally skips dead (q-block, g-block) pairs: a block
    liveness table (any admitted (cam, tile) cell of the q-block present in
    the g-block) gates the GEMM + top-k merge per block, so compute scales
    with the admitted tile area, not the gallery.  Returns
    (scores (Q, k), idx (Q, k)) with fully-masked slots as (NEG_INF, -1).
    """
    Q, D = queries.shape
    G = gallery.shape[0]
    CT = admit_ct.shape[1]
    if Q == 0 or G == 0:
        return _empty(Q, k)
    CTp = _round_up(CT, 8)
    # the (CTp, block_g) one-hot block is the kernel's largest VMEM buffer:
    # narrow block_g as the fused-cell axis grows (130 cameras at T=8 ->
    # 8,320 cells -> 128 lanes, the floor)
    block_g = min(block_g, max(128, _ONEHOT_BLOCK_BYTES // (4 * CTp)
                               // 128 * 128))
    block_q, Qp = _blocks(Q, block_q, 8)
    block_g, Gp = _blocks(G, block_g, 128)
    nq, ng = Qp // block_q, Gp // block_g

    queries = _pad_rows(queries, Qp, 0)
    q_tag = _pad_rows(jnp.asarray(q_tag, jnp.int32)[:, None], Qp, -1)
    admit_ct = _pad_rows(admit_ct.astype(jnp.float32), Qp, 0.0)
    admit_ct = jnp.pad(admit_ct, ((0, 0), (0, CTp - CT)))
    gallery = _pad_rows(gallery, Gp, 0)
    gal_ct = _pad_rows(jnp.asarray(gal_ct, jnp.int32), Gp, -1)
    gal_tag = _pad_rows(jnp.asarray(gal_tag, jnp.int32), Gp, -2)[None, :]
    # (CTp, Gp) fused-cell one-hot; unlabeled/padded rows (cell -1) match
    # no admission column
    onehot = (gal_ct[None, :] == jnp.arange(CTp)[:, None]).astype(jnp.float32)

    # block liveness: does ANY query row of q-block qi admit ANY fused cell
    # present in g-block gi?  (Q-block any) x (cell-in-g-block any) — a tiny
    # (nq, CTp) @ (CTp, ng) product computed once per call, outside the grid.
    q_any = (admit_ct.reshape(nq, block_q, CTp).max(axis=1) > 0.0)
    g_has = (onehot.reshape(CTp, ng, block_g).max(axis=2) > 0.0)
    block_live = jax.lax.dot_general(
        q_any.astype(jnp.float32), g_has.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) > 0.0
    block_live = block_live.astype(jnp.int32).reshape(nq * ng)

    kernel = functools.partial(_reid_tiles_kernel, k=k, block_g=block_g,
                               ng=ng, g_real=G)
    sv, si = pl.pallas_call(
        kernel,
        grid=(nq, ng),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, CTp), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_g, D), lambda qi, gi: (gi, 0)),
            pl.BlockSpec((1, block_g), lambda qi, gi: (0, gi)),
            pl.BlockSpec((CTp, block_g), lambda qi, gi: (0, gi)),
            # whole (nq * ng,) table in SMEM: scalar reads per grid step
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, gi: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, q_tag, admit_ct, gallery, gal_tag, onehot, block_live)
    return _mask_padded(sv[:Q], si[:Q])
