"""The serving engine: ReXCam admission control over the inference plane.

Per tick (one wall step over all live camera streams):

  1. ALL active queries are gathered into one batched
     ``repro.core.policy.PhaseState`` and a single vectorized
     ``policy.admit`` call (jit, policy static) produces the (Q, C)
     admission mask — the same function, windows and phase machine the
     batched offline tracker runs, so the two planes cannot drift,
  2. admitted (camera, frame) pairs are deduplicated across queries (a
     frame is detected / embedded once no matter how many queries want it —
     the fleet-scale batching win), with replay re-reads served from the
     ``FrameStore`` embedding cache so a still-retained frame is never
     embedded twice,
  3. the deduplicated embedding batch is ranked ON DEVICE: one
     ``kernels.reid_topk_masked`` pass scores every query against exactly
     its admitted galleries (camera-major order, so tie-breaking is
     bit-identical to the tracker's flat argmin) and returns
     matched / match_cam / match_emb for the whole round,
  4. match outcomes feed ``policy.advance``: matches re-anchor to phase 1;
     a query whose phase-1 windows exhaust REWINDS its cursor to f_q + 1
     and replays retained frames out of the ``FrameStore`` ring buffer with
     relaxed thresholds (§5.3) — frames evicted past the retention window
     surface as ``replay_misses`` (the cold-storage fallback the paper
     mentions).

Replay pacing follows §5.3: a lagging query consumes
``policy.replay_speed * policy.replay_skip`` content steps per wall tick
(skip mode samples 1-in-k of them inside ``admit``).  Sampled-out replay
rounds are short-circuited on the host — the content step is still charged,
but no admission/inference work is dispatched for a round whose mask is
all-False by construction.

Cost accounting reports BOTH conventions: ``admitted_steps`` counts
per-query camera-steps (comparable with the tracker's / ``policy_sweep``'s
cost), while ``unique_frames`` counts deduplicated (camera, frame) pairs
(the serving plane's actual inference load).

The engine is deliberately backbone-agnostic: ``embed_fn(frames) ->
(n, D)`` may be a smoke-scale transformer from ``repro.models`` or the
simulator's feature oracle (tests).

The device-side step bodies (``rank_advance_round``, ``advance_round`` and
``policy.admit``) are pure over the (Q,)-batched state, with batch-row
assignment indirected through ``_layout``/``self._slots`` — that is what
lets ``runtime.fleet.ShardedServingEngine`` run the SAME round code with
the query axis shard_map-partitioned over a device mesh, trace-identically
(padding rows are ``done`` and rank to (NEG_INF, -1) like the kernels'
padded slots).
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.correlation import SpatioTemporalModel
from repro.core.policy import (PhaseState, SearchPolicy, admit, admit_tiles,
                               advance, phase_windows, replay_sampled_out,
                               tile_counts)
from repro.kernels import ops as kernel_ops
from repro.kernels.reid_topk import NEG_INF
from repro.runtime.gallery import (GalleryStore, LocalGalleryStore,
                                   RoundStaging, assemble_round_gallery,
                                   l2_normalize, pow2)
from repro.runtime.stream_store import FrameStore
from repro.runtime.transport import PrefetchPipeline

# effectively "never": the live engine terminates queries via exit_t /
# window exhaustion, not a simulation horizon
_NO_HORIZON = 2 ** 30


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-plane settings.  All *search* semantics live in ``policy`` —
    the same ``SearchPolicy`` the offline tracker takes."""

    policy: SearchPolicy = SearchPolicy()
    max_batch: int = 256
    retention: int = 600
    embed_cache: bool = True          # gallery-plane embedding cache (§5.3)
    short_circuit_skips: bool = True  # host fast path for sampled-out rounds
    # which GalleryStore backs the embedding plane: "auto" (local for the
    # single engine, the fleet-shared sharded store for the fleet),
    # "local" (replicated per-engine) or "sharded" (fleet only)
    gallery: str = "auto"
    # top-k candidate bands surfaced per query round in the trace records
    # (§5.2 confidence bands / re-ranking); the argmax match path is always
    # band 0, so topk=1 is exactly the classic engine
    topk: int = 1
    # the gallery fetch plane (runtime.transport): None keeps today's
    # direct zero-copy reads; a Transport instance routes every fetch of an
    # owner-resident block through it (fleet + sharded gallery only)
    transport: Any = None
    # double-buffered speculative fetch: at the end of round N the engine
    # issues async fetches for round N+1's predicted admitted blocks, so
    # transport latency hides behind the rank pass (misspeculation falls
    # back to the blocking fetch, exactly accounted)
    prefetch: bool = False
    # cross-query object-level consolidation: rank the whole round through
    # the segment-ID kernel (one ``reid_topk_segments`` call over the
    # fleet-global ``RoundPlan``, content frames relabeled to compact
    # per-round segment ids).  False keeps the per-frame reference path —
    # the two are trace-identical (the relabeling is injective), which the
    # consolidation differential pins
    consolidate: bool = True
    # sub-frame spatial admission (CrossRoI-style): T > 0 refines camera
    # admission to a T x T tile grid — the round ranks through the
    # tile-masked ``reid_topk_tiles`` kernel over the fused (camera, tile)
    # admission ``policy.admit_tiles`` builds from the model's learned
    # ``tile_admit`` tensor.  0 (default) keeps camera-granular admission;
    # a model without tile data gets an all-tiles-admitted tensor, which is
    # trace-identical to the camera path (the tile differential's oracle)
    tile_grid: int = 0
    # §5.2 top-k confidence re-ranking: the k best candidate bands vote by
    # summed passing score per camera and the match re-anchors to the
    # winning camera's best band.  Bit-identical to the argmax path at
    # topk=1 (pinned by the k=1 equivalence regression)
    topk_rerank: bool = False


@dataclasses.dataclass
class QueryState:
    qid: int
    feat: np.ndarray
    c_q: int
    f_q: int
    f_curr: int            # content frame the search cursor is on
    phase: int = 1
    done: bool = False
    matches: list = dataclasses.field(default_factory=list)
    rescued: int = 0       # matches made during replay (phase >= 2)
    replay_credit: float = 0.0  # fractional replay-round carry (ff pacing)
    submit_t: int = 0      # engine wall tick the query was submitted at
    first_match_t: int = -1  # wall tick of the first confirmed match (delay)
    # tile mode only: the fused-cell tile of the last confirmed match (-1
    # before the first match — the anchor detection carries no tile).  A
    # LEARNED tile model narrows the self-camera follow window to this
    # tile's 3x3 neighborhood (policy.tile_follow_mask)
    tile_q: int = -1


@partial(jax.jit, static_argnames=("policy",))
def _admit_jit(model, policy: SearchPolicy, state: PhaseState, geo_adj=None):
    return admit(model, policy, state, geo_adj)


@partial(jax.jit, static_argnames=("policy",))
def _admit_tiles_jit(model, policy: SearchPolicy, state: PhaseState,
                     geo_adj, tile_q, q_seg):
    """The tile round's admit program: both masks, plus the round's
    (admitted, unique) tile counts as one (2,) int32 array, so the host
    reads two ints instead of the (N, C*T*T) mask."""
    mask, mask_ct = admit_tiles(model, policy, state, geo_adj, tile_q)
    return mask, mask_ct, jnp.stack(tile_counts(mask_ct, q_seg))


def _rank_outcome(sv, si, gallery, gal_cam, gal_frame, match_thresh,
                  n_cams: int = 0, topk_rerank: bool = False):
    """Shared post-kernel half of every ranking path: convert the (Q, k)
    score/index bands into the control plane's match outcome.  The best
    (band-0) score converts back to the cosine distance the threshold is
    applied to; unmatched rows carry cam 0 and an arbitrary embedding row;
    padded / fully-masked slots come back as (NEG_INF, -1, -1, -1) in the
    bands, exactly like the kernels.

    ``topk_rerank`` (§5.2): instead of committing to band 0's camera, the
    bands that pass the match threshold vote by summed score per camera and
    the match re-anchors to the winning camera's best band.  ``matched`` is
    unchanged (the bands are score-sorted, so "any band passes" == "band 0
    passes"), and at k=1 only band 0 can vote — the whole path is
    bit-identical to the argmax path, which the k=1 equivalence regression
    pins.

    An empty gallery (G == 0) has nothing to gather from: every row comes
    back unmatched with the (NEG_INF, -1) sentinel bands and a zero
    embedding."""
    if gallery.shape[0] == 0:
        Q = sv.shape[0]
        none = jnp.full(si.shape, -1, jnp.int32)
        return (jnp.zeros(Q, bool), jnp.zeros(Q, jnp.int32),
                jnp.zeros((Q, gallery.shape[1]), gallery.dtype), sv, si,
                none, none)
    valid = si >= 0
    idx = jnp.maximum(si, 0)
    topk_cam = jnp.where(valid, gal_cam[idx], -1).astype(jnp.int32)
    topk_frame = jnp.where(valid, gal_frame[idx], -1).astype(jnp.int32)
    if topk_rerank:
        passing = valid & ((1.0 - sv) < match_thresh)
        matched = passing.any(axis=1)
        # per-camera summed passing score; one_hot(-1) is all-zero, so
        # invalid bands contribute nothing
        oh = jax.nn.one_hot(topk_cam, n_cams, dtype=jnp.float32)
        votes = jnp.einsum("qk,qkc->qc", jnp.where(passing, sv, 0.0), oh)
        rerank_cam = jnp.argmax(votes, axis=1).astype(jnp.int32)
        # the winning camera's best (lowest) passing band supplies the
        # matched embedding
        j = jnp.argmax(passing & (topk_cam == rerank_cam[:, None]), axis=1)
        best_idx = jnp.take_along_axis(si, j[:, None], axis=1)[:, 0]
        match_cam = jnp.where(matched, rerank_cam, 0).astype(jnp.int32)
    else:
        best_val, best_idx = sv[:, 0], si[:, 0]
        matched = (1.0 - best_val) < match_thresh
        match_cam = jnp.where(matched, gal_cam[jnp.maximum(best_idx, 0)],
                              0).astype(jnp.int32)
    idx0 = jnp.maximum(best_idx, 0)
    return matched, match_cam, gallery[idx0], sv, si, topk_cam, topk_frame


def match_rows(matched: np.ndarray, match_cam: np.ndarray,
               topk_idx: np.ndarray, topk_cam: np.ndarray,
               topk_rerank: bool) -> np.ndarray:
    """(N,) round-gallery row each matched query matched, -1 elsewhere: the
    row ``_rank_outcome`` gathers as ``match_emb``.  That is band 0 on the
    argmax path; under ``topk_rerank`` it is the winning camera's best band,
    which is its first band, since the bands are score-sorted and the
    passing ones lead."""
    rows = np.where(matched, topk_idx[:, 0], -1)
    if topk_rerank:
        for j in np.flatnonzero(matched):
            for b in range(topk_cam.shape[1]):
                if topk_cam[j, b] == match_cam[j]:
                    rows[j] = topk_idx[j, b]
                    break
    return rows


@partial(jax.jit, static_argnames=("match_thresh", "k", "topk_rerank"))
def rank_round(q_feat, q_frame, mask, gallery, gal_cam, gal_frame,
               match_thresh: float, k: int = 1, topk_rerank: bool = False):
    """One device pass over the round's deduplicated embedding batch.

    ``reid_topk_masked`` scores each query against exactly its admitted
    galleries; the argmax match path is unchanged by k > 1, the extra bands
    only surface candidates (unless ``topk_rerank`` turns on the §5.2
    confidence vote).  Returns (matched (Q,), match_cam (Q,),
    match_emb (Q, D), topk_val (Q, k), topk_idx (Q, k), topk_cam (Q, k),
    topk_frame (Q, k)).
    """
    sv, si = kernel_ops.reid_topk_masked(q_feat, q_frame, mask, gallery,
                                         gal_cam, gal_frame, k)
    return _rank_outcome(sv, si, gallery, gal_cam, gal_frame, match_thresh,
                         mask.shape[1], topk_rerank)


@partial(jax.jit, static_argnames=("match_thresh", "k", "topk_rerank"))
def rank_round_seg(q_feat, q_seg, mask, gallery, gal_cam, gal_frame, gal_seg,
                   match_thresh: float, k: int = 1,
                   topk_rerank: bool = False):
    """Consolidated variant of ``rank_round``: frame tags replaced by the
    ``RoundPlan``'s compact per-round segment ids (``q_seg`` (Q,) /
    ``gal_seg`` (G,)).  The relabeling is injective over the round's
    distinct content frames, so the masked score matrix — and every
    flat-argmin tie-break behind the (Q, k) bands — is bit-identical to the
    per-frame path; ``gal_frame`` still rides along so the trace records'
    top-k bands surface REAL frame ids, not segment ids.
    """
    sv, si = kernel_ops.reid_topk_segments(q_feat, q_seg, mask, gallery,
                                           gal_cam, gal_seg, k)
    return _rank_outcome(sv, si, gallery, gal_cam, gal_frame, match_thresh,
                         mask.shape[1], topk_rerank)


@partial(jax.jit, static_argnames=("match_thresh", "k", "n_cams",
                                   "topk_rerank"))
def rank_round_tiles(q_feat, q_seg, mask_ct, gallery, gal_ct, gal_cam,
                     gal_frame, gal_seg, match_thresh: float, k: int = 1,
                     n_cams: int = 0, topk_rerank: bool = False):
    """Tile-granular variant of ``rank_round_seg``: camera admission refined
    to the fused (camera, tile) mask ``mask_ct`` (Q, C*T*T) and per-row
    fused cell tags ``gal_ct`` (G,), ranked through ``reid_topk_tiles``.
    With every tile admitted the kernel's masked score matrix is
    bit-identical to ``reid_topk_segments`` — the camera-granular path is
    the differential oracle.  ``gal_cam``/``gal_frame`` ride along for the
    match outcome and trace bands exactly as in the segment path.
    """
    sv, si = kernel_ops.reid_topk_tiles(q_feat, q_seg, mask_ct, gallery,
                                        gal_ct, gal_seg, k)
    return _rank_outcome(sv, si, gallery, gal_cam, gal_frame, match_thresh,
                         n_cams, topk_rerank)


def rank_advance_round(policy: SearchPolicy, windows, state: PhaseState,
                       q_feat, mask, gallery, gal_cam, gal_frame, k: int = 1,
                       topk_rerank: bool = False):
    """The ONE serving step body both the single-process engine and the
    sharded fleet dispatch: rank the round's deduplicated gallery, then run
    the shared phase machine.  Pure over (Q,)-batched inputs, so the fleet
    can shard_map it over the query axis with the gallery replicated.

    The query cursor frames come from ``state.f_curr``; padding rows (done,
    all-False mask) therefore match nothing and surface (NEG_INF, -1) in
    the top-k bands — the same convention the kernels use for their own
    padded slots.
    """
    (matched, match_cam, match_emb, topk_val, topk_idx, topk_cam,
     topk_frame) = rank_round(q_feat, state.f_curr, mask, gallery, gal_cam,
                              gal_frame, policy.match_thresh, k, topk_rerank)
    nxt = advance(policy, windows, state, matched, match_cam, _NO_HORIZON)
    return (nxt, matched, match_cam, match_emb, topk_val, topk_idx,
            topk_cam, topk_frame)


def advance_round(policy: SearchPolicy, windows, state: PhaseState):
    """The no-gallery variant of the step body (nothing admitted anywhere
    this round): the phase machine alone, matched=False for every query."""
    Q = state.f_q.shape[0]
    return advance(policy, windows, state, jnp.zeros(Q, bool),
                   jnp.zeros(Q, jnp.int32), _NO_HORIZON)


def rank_advance_round_seg(policy: SearchPolicy, windows, state: PhaseState,
                           q_feat, q_seg, mask, gallery, gal_cam, gal_frame,
                           gal_seg, k: int = 1, topk_rerank: bool = False):
    """Consolidated step body: the whole round ranks in ONE segment-ID
    kernel call (``rank_round_seg``), then the same shared phase machine
    advances.  Pure over (Q,)-batched inputs like ``rank_advance_round`` —
    the fleet shard_maps it over the query axis with the gallery (and its
    cam/frame/segment tags) replicated."""
    (matched, match_cam, match_emb, topk_val, topk_idx, topk_cam,
     topk_frame) = rank_round_seg(q_feat, q_seg, mask, gallery, gal_cam,
                                  gal_frame, gal_seg, policy.match_thresh, k,
                                  topk_rerank)
    nxt = advance(policy, windows, state, matched, match_cam, _NO_HORIZON)
    return (nxt, matched, match_cam, match_emb, topk_val, topk_idx,
            topk_cam, topk_frame)


def rank_advance_round_tiles(policy: SearchPolicy, windows,
                             state: PhaseState, q_feat, q_seg, mask_ct,
                             gallery, gal_ct, gal_cam, gal_frame, gal_seg,
                             k: int = 1, n_cams: int = 0,
                             topk_rerank: bool = False):
    """Tile-granular step body: the whole round ranks in ONE tile-masked
    segment-ID kernel call (``rank_round_tiles``), then the same shared
    phase machine advances.  ``mask_ct`` (Q, C*T*T) is the fused
    (camera, tile) admission from ``policy.admit_tiles``; with every tile
    admitted this body is bit-identical to ``rank_advance_round_seg`` (the
    tile differential's oracle).  Pure over (Q,)-batched inputs — the fleet
    shard_maps it over the query axis with the gallery (and its
    cam/frame/segment/cell tags) replicated."""
    (matched, match_cam, match_emb, topk_val, topk_idx, topk_cam,
     topk_frame) = rank_round_tiles(q_feat, q_seg, mask_ct, gallery, gal_ct,
                                    gal_cam, gal_frame, gal_seg,
                                    policy.match_thresh, k, n_cams,
                                    topk_rerank)
    nxt = advance(policy, windows, state, matched, match_cam, _NO_HORIZON)
    return (nxt, matched, match_cam, match_emb, topk_val, topk_idx,
            topk_cam, topk_frame)


@partial(jax.jit, static_argnames=("policy", "k", "topk_rerank"))
def _rank_advance_jit(policy: SearchPolicy, windows, state: PhaseState,
                      q_feat, mask, gallery, gal_cam, gal_frame, k=1,
                      topk_rerank=False):
    return rank_advance_round(policy, windows, state, q_feat, mask,
                              gallery, gal_cam, gal_frame, k, topk_rerank)


@partial(jax.jit, static_argnames=("policy", "k", "topk_rerank"))
def _rank_advance_seg_jit(policy: SearchPolicy, windows, state: PhaseState,
                          q_feat, q_seg, mask, gallery, gal_cam, gal_frame,
                          gal_seg, k=1, topk_rerank=False):
    return rank_advance_round_seg(policy, windows, state, q_feat, q_seg,
                                  mask, gallery, gal_cam, gal_frame,
                                  gal_seg, k, topk_rerank)


@partial(jax.jit, static_argnames=("policy", "k", "n_cams", "topk_rerank"))
def _rank_advance_tiles_jit(policy: SearchPolicy, windows, state: PhaseState,
                            q_feat, q_seg, mask_ct, gallery, gal_ct, gal_cam,
                            gal_frame, gal_seg, k=1, n_cams=0,
                            topk_rerank=False):
    return rank_advance_round_tiles(policy, windows, state, q_feat, q_seg,
                                    mask_ct, gallery, gal_ct, gal_cam,
                                    gal_frame, gal_seg, k, n_cams,
                                    topk_rerank)


@partial(jax.jit, static_argnames=("policy",))
def _advance_round_jit(policy: SearchPolicy, windows, state: PhaseState):
    return advance_round(policy, windows, state)


_pow2 = pow2   # shared with runtime.gallery: one padding rule everywhere


@dataclasses.dataclass
class RoundPlan:
    """One round's fleet-global work queue, keyed by unique admitted
    (camera, frame).

    Built ONCE per round by ``_plan_round`` on the controller — the fleet's
    shards all consume the same plan, so no shard re-embeds or re-fetches a
    frame another shard's query already put in flight.  ``work`` is the
    camera-major sorted unique (cam, frame) demand (the order that keeps
    the kernels' flat-argmin tie-breaks bit-identical to the tracker);
    ``want_count`` records how many (query, camera) admission steps each
    key serves (the per-step miss convention — ``replay_miss_steps`` —
    reads it on eviction); ``seg_of_frame``/``q_seg`` carry the round's
    injective content-frame -> compact-segment relabeling for the
    consolidated ``reid_topk_segments`` ranking pass.
    """

    qs: list
    ps: PhaseState
    slots: np.ndarray
    mask: np.ndarray                        # (N, C) admission, host copy
    mask_dev: Any                           # the same mask, on the device
    admitted: int                           # per-(query, camera) steps
    cams_by_q: list
    work: list                              # sorted unique (cam, frame)
    want_count: dict                        # key -> wanting (q, cam) pairs
    seg_of_frame: dict                      # content frame -> segment id
    q_seg: np.ndarray                       # (N,) int32, -1 on padding rows
    # tile mode only: the fused (camera, tile) admission (N, C*T*T) and
    # ``q_seg`` on the device, for the tile-masked ranking pass, and the
    # round's tile counts the admit program computed from them
    mask_ct_dev: Any = None
    q_seg_dev: Any = None
    admitted_tiles: int = 0
    unique_tiles: int = 0

    def gallery_segments(self, batch_keys: list, key_emb: dict,
                         gal_seg: np.ndarray) -> np.ndarray:
        """Write the per-row segment tags of the assembled round gallery
        into ``gal_seg``: each key's embedding block (in ``batch_keys``
        order, exactly how ``assemble_round_gallery`` laid the rows out)
        gets its frame's segment id; the padding rows past them keep the -1
        the staging holds there."""
        pos = 0
        for key in batch_keys:
            cnt = len(key_emb[key])
            gal_seg[pos:pos + cnt] = self.seg_of_frame[key[1]]
            pos += cnt
        return gal_seg


class ServingEngine:
    def __init__(self, model: SpatioTemporalModel, embed_fn: Callable,
                 cfg: EngineConfig, geo_adj=None):
        if cfg.topk < 1:
            raise ValueError(f"topk={cfg.topk} must be >= 1 (band 0 is the "
                             f"argmax match path)")
        self.tile_grid = int(cfg.tile_grid)
        if self.tile_grid > 0:
            model = self._resolve_tiles(model)
        self.model = model
        self.embed_fn = embed_fn
        self.cfg = cfg
        self.policy = cfg.policy
        self.C = model.n_cams
        self.model_epoch = int(model.epoch)  # host mirror for trace records
        self.model_swaps: list[tuple[int, int]] = []  # (tick, new epoch)
        # the geo baseline's proximity mask; all-ones when not provided
        # (same default as the tracker)
        self._geo_adj = jnp.asarray(
            geo_adj if geo_adj is not None else np.ones((self.C, self.C), bool))
        self.gallery = self._make_gallery()
        self.store = FrameStore(self.C, cfg.retention, gallery=self.gallery)
        # the double buffer over the gallery fetch plane (issue round N+1's
        # fetches while round N consumes) — harmless but pointless without a
        # transport, since the local path delivers immediately
        self._prefetch = PrefetchPipeline(self.store) if cfg.prefetch else None
        self.queries: dict[int, QueryState] = {}
        self.t = 0
        self.frames_processed = 0    # (cam, frame) batches actually embedded
        self.cache_hits = 0          # embed calls avoided by the cache
        self.replay_embeds = 0       # replay re-reads the cache missed
        self.admitted_steps = 0      # per-query camera-steps (tracker scale)
        self.unique_frames = 0       # deduplicated (cam, frame) pairs
        # tile mode only: per-(query, camera, tile) admission steps, and the
        # per-key unions of admitted tiles (the sub-frame pixel-load proxy —
        # camera-granular serving loads T*T tiles per admitted step / key)
        self.admitted_tiles = 0
        self.unique_tiles = 0
        # bytes the rounds' blocking device reads copied to the host
        self.readback_bytes = 0
        self.content_steps = 0       # per-query content rounds charged
        self.replay_steps = 0        # content rounds behind the frontier
        self.skipped_steps = 0       # short-circuited sampled-out rounds
        self.replay_misses = 0       # replay reads past the retention window
        # the same misses in admitted_steps' per-(query, camera) convention:
        # an evicted key wanted by k queries is k rescue failures, not 1
        self.replay_miss_steps = 0
        self.ticks = 0
        # (C, C) replay-rescue attribution (phase >= 2 matches, keyed by the
        # anchor camera at match time) — the tracker's rescue_pairs, live:
        # the §6 drift-detection signal profiler.drift_score consumes
        self.rescue_pairs = np.zeros((self.C, self.C), np.int64)
        # (qid, cam, frame) confirmed-sighting log: the query's submit anchor
        # plus every match — the engine's own trajectory record, which
        # runtime.recal.match_log_source can re-profile from (§6).  A deque
        # pruned each tick past the largest window anyone can replay into
        # (frame retention, or the recal window when a controller is
        # attached), so a long-running engine's memory stays bounded.
        self.sightings: collections.deque[tuple[int, int, int]] = \
            collections.deque()
        self.recal = None            # attached RecalibrationController
        self._in_round = False       # swap_model atomicity guard
        self._slots = np.zeros(0, np.int64)  # qs-index -> batch-row mapping
        # high-water marks freezing steady-state jit signatures: the padded
        # batch and round gallery never shrink below a size already compiled.
        # Growth-only padding is trace-neutral — padding rows are done/masked
        # and rank to (NEG_INF, -1) — so a shrinking cohort or gallery reuses
        # the compiled shape instead of minting a smaller signature every
        # time it dips (what RecompileGuard would trip on).
        self._batch_hwm = 1
        self._gal_rows_hwm = 1
        # the round's host staging (gallery + query features), sized from
        # those marks and reused across rounds
        self._staging = RoundStaging()
        self._windows = phase_windows(model, cfg.policy)
        # host copies of the exhaustion windows for the skip fast path
        self._w1 = np.asarray(self._windows.w_end1)
        self._w2 = np.asarray(self._windows.w_end2)

    # -- the correlation model (the control plane's only persistent state) --
    def _resolve_tiles(self, model: SpatioTemporalModel) -> SpatioTemporalModel:
        """Reconcile a model with the engine's ``cfg.tile_grid``: a model
        profiled WITHOUT tile data gets the all-tiles-admitted tensor
        (trace-identical to camera-granular serving — the tile
        differential's oracle); a model profiled at a different grid is a
        config error, not something to resample silently."""
        if model.tile_grid not in (0, self.tile_grid):
            raise ValueError(
                f"tile_grid mismatch: engine serves T={self.tile_grid} but "
                f"the model was profiled at T={model.tile_grid} — re-profile "
                f"with profile(..., tile_grid={self.tile_grid})")
        if model.tile_admit is None or model.tile_grid == 0:
            C, TT = model.n_cams, self.tile_grid * self.tile_grid
            model = dataclasses.replace(
                model, tile_admit=jnp.ones((C, C, TT), bool),
                tile_grid=self.tile_grid, tile_learned=False)
        return model

    def swap_model(self, model: SpatioTemporalModel) -> int:
        """Hot-swap the spatio-temporal model M without dropping in-flight
        queries (§6 recalibration): the next round admits/ranks under the new
        model while every query keeps its anchor, cursor and phase.  The
        phase-exhaustion windows (device + host skip-path copies) are rebuilt
        so both step paths switch together, and the model epoch bumps — trace
        records carry it, which is how the differential harness pins the
        fleet's swap to the same round as the single engine's.

        M's arrays must keep their shapes ((C, C[, NB])), so the jitted step
        bodies never recompile on a swap; swaps land BETWEEN rounds (calling
        mid-round raises — the atomicity contract the fleet relies on, since
        one round's admit and rank must see the same M on every shard).
        Returns the new epoch."""
        if self._in_round:
            raise RuntimeError(
                "swap_model called mid-round: the model must stay constant "
                "within a round (admit and rank see one M) — swap between "
                "ticks, e.g. from RecalibrationController.on_tick")
        if model.n_cams != self.C or model.n_bins != self.model.n_bins \
                or model.bin_width != self.model.bin_width:
            raise ValueError(
                f"swap_model shape mismatch: engine serves C={self.C}, "
                f"NB={self.model.n_bins}, bin_width={self.model.bin_width}; "
                f"got C={model.n_cams}, NB={model.n_bins}, "
                f"bin_width={model.bin_width} (re-profile with the same "
                f"n_bins/bin_width — bin_width is jit-static, so a mismatch "
                f"would recompile every step body)")
        if self.tile_grid > 0:
            # epoch-versioned tile carry: a recalibration that re-profiled
            # WITHOUT tile data keeps serving the incumbent learned masks
            # (they ride the swap forward); a re-profile WITH tile data at
            # the serving grid hot-swaps them like every other model array
            if model.tile_admit is None or model.tile_grid == 0:
                model = dataclasses.replace(
                    model, tile_admit=self.model.tile_admit,
                    tile_grid=self.tile_grid,
                    tile_learned=self.model.tile_learned)
            else:
                model = self._resolve_tiles(model)
        self.model_epoch += 1
        if int(model.epoch) != self.model_epoch:
            model = dataclasses.replace(model, epoch=self.model_epoch)
        self.model = model
        self._windows = phase_windows(model, self.cfg.policy)
        self._w1 = np.asarray(self._windows.w_end1)
        self._w2 = np.asarray(self._windows.w_end2)
        self.model_swaps.append((self.t, self.model_epoch))
        return self.model_epoch

    # -- the gallery plane -------------------------------------------------
    def _make_gallery(self) -> GalleryStore:
        """Which GalleryStore backs the embedding plane.  The fleet
        overrides this to inject the shared ``ShardedGalleryStore``."""
        if self.cfg.transport is not None:
            raise ValueError(
                "transport= requires the sharded fleet gallery "
                "(serve(..., shards=k)); the single engine's local store "
                "has no remote owners to fetch from")
        if self.cfg.gallery in ("auto", "local"):
            return LocalGalleryStore(self.C, self.cfg.retention)
        if self.cfg.gallery == "sharded":
            raise ValueError(
                "gallery='sharded' requires the sharded fleet "
                "(serve(..., shards=k)); the single engine is local-only")
        raise ValueError(f"unknown gallery mode {self.cfg.gallery!r} "
                         f"(expected 'auto', 'local' or 'sharded')")

    def gallery_report(self) -> dict:
        """The embedding plane's own accounting: backend kind plus
        hit/miss/eviction/put counters and resident memory.  Rescue-failure
        cost rides along in BOTH conventions: ``replay_misses`` per unique
        evicted key, ``replay_miss_steps`` per wanting (query, camera)
        step (comparable with ``admitted_steps``)."""
        return dict(kind=self.gallery.kind,
                    replay_misses=self.replay_misses,
                    replay_miss_steps=self.replay_miss_steps,
                    **self.gallery.counters())

    # -- query lifecycle --------------------------------------------------
    def submit_query(self, qid: int, feat: np.ndarray, cam: int, frame: int):
        self.queries[qid] = QueryState(
            qid, l2_normalize(feat), cam, frame, f_curr=frame + 1,
            submit_t=self.t)
        self.sightings.append((qid, cam, frame))

    def _on_query_done(self, q: QueryState) -> None:
        """Fired exactly once per query, on its not-done -> done transition
        (both the device round and the host skip fast path).  The fleet
        keeps its O(1) per-worker live-load counters here."""

    # -- batched state marshalling ---------------------------------------
    def _layout(self, qs: list[QueryState]) -> tuple[int, np.ndarray]:
        """(batch size N, slots): which padded-batch row each query in ``qs``
        occupies.  The single-process engine packs queries densely and pads
        to the next power of two (O(log Q) jit shapes); the sharded fleet
        overrides this to group rows by worker placement, each shard block
        padded to a shard-uniform power of two.  Both hold the batch at its
        high-water mark so a shrinking cohort keeps the compiled shape."""
        n = len(qs)
        self._batch_hwm = max(self._batch_hwm, _pow2(n))
        return self._batch_hwm, np.arange(n)

    def prime_batch(self, n_queries: int) -> None:
        """Pre-size the padded batch for an expected peak of ``n_queries``
        live queries.  Round cohorts grow lazily (a 3-query cohort may
        first form hundreds of ticks in), and each pow2 growth mints a new
        jit signature — pre-sizing moves all of them into warmup, so a
        RecompileGuard-ed steady state compiles nothing.  Trace-neutral by
        the hwm layout rule: padding rows are done/masked and rank to
        (NEG_INF, -1)."""
        self._batch_hwm = max(self._batch_hwm, _pow2(max(int(n_queries), 1)))

    def prime_gallery(self, rows: int) -> None:
        """Pre-size the padded round gallery for an expected peak of
        ``rows`` embedding rows.  The gallery side of the rank signature
        has the same lazy-growth problem as the batch side: a phase-2
        rescue hundreds of ticks in can admit the largest round gallery
        yet, and each pow2 growth of ``_gal_rows_hwm`` mints a new rank
        signature.  Trace-neutral: padded rows carry cam/frame -1 and rank
        to (NEG_INF, -1) inside the kernels."""
        self._gal_rows_hwm = max(self._gal_rows_hwm,
                                 _pow2(max(int(rows), 1)))

    @property
    def padded_gallery_rows(self) -> int:
        """Current round-gallery row high-water mark (pow2-padded) — feed
        it back through ``prime_gallery`` on a fresh engine to replay the
        same workload without mid-run shape growth."""
        return self._gal_rows_hwm

    @property
    def staging_allocs(self) -> int:
        """Allocations or growths of the round's host staging buffers so
        far.  The buffers follow the primed high-water marks, so after
        ``prime_batch``/``prime_gallery`` and the first round it holds
        still through steady serving."""
        return self._staging.allocs

    def _read(self, *arrays):
        """Copy device arrays to the host, counting the bytes in
        ``readback_bytes``.  Several arrays go in one ``jax.device_get``:
        their copies overlap and the host waits once."""
        if len(arrays) == 1:
            out = np.asarray(arrays[0])
            self.readback_bytes += out.nbytes
            return out
        out = jax.device_get(arrays)
        self.readback_bytes += sum(a.nbytes for a in out)
        return out

    def _gather(self, qs: list[QueryState]) -> PhaseState:
        """Engine QueryStates -> one batched PhaseState.  The live frontier
        is the engine wall clock: frames through ``self.t`` are ingested.

        Row assignment comes from ``_layout`` (stored in ``self._slots`` for
        the rest of the round); every non-query row is padding — ``done``,
        so it admits nothing, never advances, and ranks to (NEG_INF, -1)
        exactly like the kernels' own padded slots.
        """
        N, slots = self._layout(qs)
        self._slots = slots

        def col(vals, fill, dtype):
            a = np.full(N, fill, dtype)
            a[slots] = vals
            return jnp.asarray(a)

        return PhaseState(
            f_q=col([q.f_q for q in qs], 0, np.int32),
            c_q=col([q.c_q for q in qs], 0, np.int32),
            f_curr=col([q.f_curr for q in qs], 0, np.int32),
            phase=col([q.phase for q in qs], 1, np.int32),
            live_f=col([float(self.t)] * len(qs), 0.0, np.float32),
            done=col([False] * len(qs), True, np.bool_),
        )

    def _scatter(self, qs: list[QueryState], ps: PhaseState,
                 matched: np.ndarray, match_cam: np.ndarray,
                 emb_rows: np.ndarray | None, gallery: np.ndarray | None):
        """Write the advanced PhaseState back into the QueryState objects.
        A matched query's embedding is row ``emb_rows[j]`` of the host
        round ``gallery`` the rank step ranked, the row it matched."""
        a = self.policy.feat_alpha
        sl = self._slots
        f_q = self._read(ps.f_q)
        c_q = self._read(ps.c_q)
        f_curr = self._read(ps.f_curr)
        phase = self._read(ps.phase)
        done = self._read(ps.done)
        for i, q in enumerate(qs):
            j = sl[i]
            if matched[j]:
                emb = gallery[emb_rows[j]]
                q.feat = l2_normalize((1 - a) * q.feat + a * emb)
                if q.first_match_t < 0:   # detection delay (Fig. 15 metric)
                    q.first_match_t = self.t
                if q.phase >= 2:
                    q.rescued += 1
                    self.rescue_pairs[q.c_q, int(match_cam[j])] += 1
                q.matches.append((int(match_cam[j]), int(q.f_curr)))
                self.sightings.append((q.qid, int(match_cam[j]),
                                       int(q.f_curr)))
            q.f_q, q.c_q = int(f_q[j]), int(c_q[j])
            q.f_curr, q.phase = int(f_curr[j]), int(phase[j])
            q.done = bool(done[j])
            if q.done:          # qs never contains done queries: a transition
                self._on_query_done(q)

    # -- device dispatch ---------------------------------------------------
    # The fleet overrides these three to run the SAME step bodies under
    # shard_map over the query axis (model/windows/gallery replicated).
    def _dispatch_admit(self, ps: PhaseState):
        return _admit_jit(self.model, self.policy, ps, self._geo_adj)

    def _dispatch_admit_tiles(self, ps: PhaseState, tile_q, q_seg):
        return _admit_tiles_jit(self.model, self.policy, ps, self._geo_adj,
                                tile_q, q_seg)

    def _dispatch_rank_advance(self, ps: PhaseState, q_feat, mask, gallery,
                               gal_cam, gal_frame):
        return _rank_advance_jit(self.policy, self._windows, ps, q_feat,
                                 mask, gallery, gal_cam, gal_frame,
                                 k=self.cfg.topk,
                                 topk_rerank=self.cfg.topk_rerank)

    def _dispatch_rank_advance_seg(self, ps: PhaseState, q_feat, q_seg,
                                   mask, gallery, gal_cam, gal_frame,
                                   gal_seg):
        return _rank_advance_seg_jit(self.policy, self._windows, ps, q_feat,
                                     q_seg, mask, gallery, gal_cam,
                                     gal_frame, gal_seg, k=self.cfg.topk,
                                     topk_rerank=self.cfg.topk_rerank)

    def _dispatch_rank_advance_tiles(self, ps: PhaseState, q_feat, q_seg,
                                     mask_ct, gallery, gal_ct, gal_cam,
                                     gal_frame, gal_seg):
        return _rank_advance_tiles_jit(self.policy, self._windows, ps,
                                       q_feat, q_seg, mask_ct, gallery,
                                       gal_ct, gal_cam, gal_frame, gal_seg,
                                       k=self.cfg.topk, n_cams=self.C,
                                       topk_rerank=self.cfg.topk_rerank)

    def _dispatch_advance(self, ps: PhaseState):
        return _advance_round_jit(self.policy, self._windows, ps)

    def _plan_round(self, qs: list[QueryState]) -> RoundPlan:
        """Gather + admit, then build the round's fleet-global work queue:
        the deduplicated (cam, frame) demand with per-key want counts, and
        the injective content-frame -> segment relabeling the consolidated
        ranking pass tags queries and gallery rows with."""
        ps = self._gather(qs)
        sl = self._slots
        N = ps.f_q.shape[0]
        seg_of_frame = {f: s for s, f in
                        enumerate(sorted({q.f_curr for q in qs}))}
        q_seg = np.full(N, -1, np.int32)
        for i, q in enumerate(qs):
            q_seg[sl[i]] = seg_of_frame[q.f_curr]
        m_ct = q_seg_dev = None
        counts = (0, 0)
        if self.tile_grid > 0:
            # one fused admit pass: the (N, C) camera mask (identical to
            # _dispatch_admit by construction — mask_ct reduces to it over
            # the tile axis), the (N, C*T*T) tile-refined admission, which
            # stays on the device for the rank step, and the round's tile
            # counts, read back with the camera mask.  tile_q rides along
            # padded like every batch column (-1 = unknown, which admits
            # every self tile)
            tq = np.full(N, -1, np.int32)
            tq[sl] = [q.tile_q for q in qs]
            q_seg_dev = jnp.asarray(q_seg)
            m, m_ct, counts = self._dispatch_admit_tiles(
                ps, jnp.asarray(tq), q_seg_dev)
            mask, counts = self._read(m, counts)
        else:
            m = self._dispatch_admit(ps)
            mask = self._read(m)                                     # (N, C)
        cams_by_q = [np.flatnonzero(mask[sl[i]]) for i in range(len(qs))]
        want_count: dict[tuple[int, int], int] = {}
        for i, q in enumerate(qs):
            for cam in cams_by_q[i]:
                key = (int(cam), q.f_curr)
                want_count[key] = want_count.get(key, 0) + 1
        return RoundPlan(qs=qs, ps=ps, slots=sl, mask=mask, mask_dev=m,
                         admitted=int(mask[sl].sum()), cams_by_q=cams_by_q,
                         work=sorted(want_count), want_count=want_count,
                         seg_of_frame=seg_of_frame, q_seg=q_seg,
                         mask_ct_dev=m_ct, q_seg_dev=q_seg_dev,
                         admitted_tiles=int(counts[0]),
                         unique_tiles=int(counts[1]))

    def _account_round(self, plan: RoundPlan) -> None:
        """Per-round accounting hook over the shared ``RoundPlan`` —
        ``plan.cams_by_q[i]`` is the camera set query i admitted,
        ``plan.work`` the round's globally-deduplicated (cam, frame) demand
        (the fleet adds per-shard cost here)."""

    # -- per-tick ----------------------------------------------------------
    def ingest(self, frames_by_cam: dict[int, Any],
               tiles_by_cam: dict[int, Any] | None = None):
        """New live frames at the current step (frame = detector crops).

        Tile mode (``cfg.tile_grid > 0``) additionally requires per-camera
        flat tile ids, one per detection crop (``tiles_by_cam[cam][i]`` =
        ``ty * T + tx`` for crop i — ``core.simulate.tile_index`` maps
        normalized positions to them).  Labels are MANDATORY: a gallery row
        without a tile cell would either silently match nothing or need a
        wildcard that breaks the all-admitted <-> camera-path equivalence,
        so a missing/mismatched label set raises instead."""
        for cam, frame in frames_by_cam.items():
            tile = None
            if self.tile_grid > 0:
                tile = None if tiles_by_cam is None else tiles_by_cam.get(cam)
                if tile is None:
                    raise ValueError(
                        f"tile_grid={self.tile_grid} serving requires per-"
                        f"detection tile labels: ingest(frames_by_cam, "
                        f"tiles_by_cam) got none for camera {cam}")
                if len(tile) != len(frame):
                    raise ValueError(
                        f"camera {cam}: {len(tile)} tile labels for "
                        f"{len(frame)} detections at t={self.t}")
                tile = np.asarray(tile, np.int32)
            self.store.append(cam, self.t, frame, tile=tile)

    def tick(self, record_trace: list | None = None) -> dict:
        """One admission+inference round over all live queries at once.

        A caught-up query consumes one content step; a replaying query
        consumes up to ``policy.replay_rate`` content steps (extra rounds),
        which is how fast-forward mode catches up.  Returns stats; pass a
        list as ``record_trace`` to collect (qid, f_curr, phase, mask) per
        processed round (the parity-test hook).
        """
        stats = {"t": self.t, "admitted_steps": 0, "unique_frames": 0,
                 "batched": 0, "embedded": 0, "cache_hits": 0,
                 "replay_embeds": 0, "matches": 0, "replay_misses": 0,
                 "replay_miss_steps": 0, "content_steps": 0,
                 "replay_steps": 0, "skipped_rounds": 0,
                 "admitted_tiles": 0, "unique_tiles": 0}
        # Replay pacing: a lagging query earns policy.replay_rate content
        # rounds per wall tick, with the fractional remainder carried across
        # ticks so e.g. replay_speed=1.5 really averages 1.5x, matching the
        # tracker's continuous live_f model.  Caught-up queries get 1 round.
        # drop prefetch handles whose blocks got evicted since they were
        # issued (ingest ran between ticks) — exact waste accounting and a
        # buffer bounded by the cache size
        if self._prefetch is not None:
            self._prefetch.sweep()
        budget = {}
        for q in self.queries.values():
            if q.done:
                continue
            if q.f_curr >= self.t:
                q.replay_credit = 0.0
                budget[q.qid] = 1
            else:
                q.replay_credit += self.policy.replay_rate
                rounds = int(q.replay_credit)
                q.replay_credit -= rounds
                budget[q.qid] = rounds
        while True:
            qs = [q for q in self.queries.values()
                  if not q.done and budget.get(q.qid, 0) > 0
                  and q.f_curr <= self.t]
            if not qs:
                break
            for q in qs:
                if q.f_curr < self.t:
                    budget[q.qid] -= 1
                else:
                    # live queries only get 1 content step per wall tick; a
                    # replayer that caught up mid-tick banks its unspent
                    # budget back into replay_credit (the credit was already
                    # decremented at tick start — forfeiting it here would
                    # undershoot policy.replay_rate long-run)
                    q.replay_credit += budget[q.qid] - 1
                    budget[q.qid] = 0
            self._round(qs, stats, record_trace)
        self.t += 1
        self.ticks += 1
        # drift-aware recalibration (§6): the attached controller polls the
        # live rescue matrix and may hot-swap M — strictly between rounds,
        # so the swap is atomic across the whole fleet's next round
        if self.recal is not None:
            self.recal.on_tick()
        # bound the sighting log: drop entries no recalibration window can
        # still reach (sightings arrive near-sorted by frame — submit
        # anchors and replay matches lag at most a window behind — so
        # stopping at the first young head is amortized O(1) per tick)
        keep = max(self.cfg.retention,
                   self.recal.policy.window if self.recal is not None else 0)
        cutoff = self.t - 2 * keep
        while self.sightings and self.sightings[0][2] < cutoff:
            self.sightings.popleft()
        return stats

    def _round(self, qs: list[QueryState], stats: dict,
               trace: list | None) -> None:
        self._in_round = True
        try:
            self._round_body(qs, stats, trace)
        finally:
            self._in_round = False

    def _round_body(self, qs: list[QueryState], stats: dict,
                    trace: list | None) -> None:
        stats["content_steps"] += len(qs)
        self.content_steps += len(qs)
        replaying = sum(q.f_curr < self.t for q in qs)
        stats["replay_steps"] += replaying
        self.replay_steps += replaying

        # §5.3 skip mode: a sampled-out replay cursor admits nothing by
        # construction — advance it on the host instead of paying a full
        # gather/admit/rank dispatch (the content step is already charged).
        # Trace records are buffered per qid and emitted in the original
        # round order so the fast path stays trace-identical to the slow one.
        all_qs = qs
        records: dict[int, dict] = {}
        if self.cfg.short_circuit_skips and self.policy.replay_skip > 1:
            gated = [q for q in qs
                     if replay_sampled_out(self.policy, q.f_q, q.f_curr,
                                           q.f_curr < self.t)]
            if gated:
                self._skip_round(gated, stats,
                                 records if trace is not None else None)
                gated_ids = {q.qid for q in gated}
                qs = [q for q in qs if q.qid not in gated_ids]
                if not qs:
                    if trace is not None:
                        trace.extend(records[q.qid] for q in all_qs)
                    if self._prefetch is not None:
                        self._issue_prefetch(all_qs)
                    return

        # the round's fleet-global work queue: one plan, every shard's
        # queries — each admitted (cam, frame) pair embeds/fetches once no
        # matter how many queries (on whichever shard) want it
        plan = self._plan_round(qs)
        ps, sl, mask = plan.ps, plan.slots, plan.mask
        stats["admitted_steps"] += plan.admitted
        self.admitted_steps += plan.admitted
        self._account_round(plan)
        stats["unique_frames"] += len(plan.work)
        self.unique_frames += len(plan.work)
        if self.tile_grid > 0:
            # both cost conventions, tile-refined: admitted_tiles is
            # per-(query, camera, tile) steps (camera-granular serving
            # would charge T*T per admitted step); unique_tiles is the
            # per-key UNION of admitted tiles (the deduplicated sub-frame
            # pixel-load proxy — camera-granular loads T*T per unique key)
            stats["admitted_tiles"] += plan.admitted_tiles
            self.admitted_tiles += plan.admitted_tiles
            stats["unique_tiles"] += plan.unique_tiles
            self.unique_tiles += plan.unique_tiles

        # camera-major key order (plan.work is sorted): ascending gallery
        # index reproduces the tracker's flat-argmin tie-break within every
        # query's admitted set
        batch_keys: list[tuple[int, int]] = []
        frames: dict[tuple[int, int], Any] = {}
        key_emb: dict[tuple[int, int], np.ndarray] = {}
        for key in plan.work:
            if self.cfg.embed_cache:
                # prefetched blocks first (round N-1 speculated this key);
                # any misspeculation falls back to the blocking fetch below
                emb = None
                if self._prefetch is not None:
                    emb = self._prefetch.consume(*key)
                if emb is None:
                    emb = self.store.get_emb(*key)
                if emb is not None:     # replay re-read: skip re-embedding
                    key_emb[key] = emb
                    batch_keys.append(key)
                    stats["cache_hits"] += 1
                    self.cache_hits += 1
                    continue
            try:
                frame = self.store.get(*key)
            except KeyError:            # evicted: cold-storage miss (§5.3)
                # both conventions: one per unique key, plus one per wanting
                # (query, camera) step — a key shared by k queries is k
                # failed rescues at admitted_steps scale
                self.replay_misses += 1
                stats["replay_misses"] += 1
                self.replay_miss_steps += plan.want_count[key]
                stats["replay_miss_steps"] += plan.want_count[key]
                continue
            if frame is not None and len(frame):
                batch_keys.append(key)
                frames[key] = frame
        stats["batched"] += len(batch_keys)

        to_embed = [k for k in batch_keys if k not in key_emb]
        for start in range(0, len(to_embed), self.cfg.max_batch):
            keys = to_embed[start:start + self.cfg.max_batch]
            counts = [len(frames[key]) for key in keys]
            crops = [c for key in keys for c in frames[key]]
            emb = l2_normalize(self.embed_fn(np.stack(crops)))  # (n, D)
            self.frames_processed += len(keys)
            stats["embedded"] += len(keys)
            # keys behind the wall clock are replay re-reads the cache missed
            replay_embeds = sum(key[1] < self.t for key in keys)
            stats["replay_embeds"] += replay_embeds
            self.replay_embeds += replay_embeds
            pos = 0
            for key, cnt in zip(keys, counts):
                key_emb[key] = emb[pos:pos + cnt]
                if self.cfg.embed_cache:
                    # the frame was just read out of the store, so it IS
                    # retained — a refused write here is a bookkeeping bug
                    # (raise, not assert: must survive python -O)
                    if not self.store.put_emb(*key, key_emb[key]):
                        raise RuntimeError(
                            f"engine tried to cache un-retained frame {key}")
                pos += cnt

        # one rank+advance pass over the whole round, through the step body
        # both engines share: every query scores exactly its admitted
        # galleries via the segment-masked reid kernel, then the phase
        # machine advances — matched plus the (N, k) top-k bands come back
        # per row with padding rows as (False, NEG_INF, -1)
        N = mask.shape[0]
        K = self.cfg.topk
        matched = np.zeros(N, bool)
        match_cam = np.zeros(N, np.int32)
        topk_val = np.full((N, K), NEG_INF, np.float32)
        topk_idx = np.full((N, K), -1, np.int32)
        topk_cam = np.full((N, K), -1, np.int32)
        topk_frame = np.full((N, K), -1, np.int32)
        emb_rows = gal = None
        if batch_keys:
            # camera-major key order was fixed above; assembly + pow2 pad
            # live in the gallery plane so both engines share one rule.
            # The rows land in the engine's own staging, refilled in place
            # each round (the previous round's results are back on the host)
            st = self._staging
            gal, gal_cam, gal_frame = assemble_round_gallery(
                batch_keys, key_emb, min_rows=self._gal_rows_hwm, out=st)
            self._gal_rows_hwm = max(self._gal_rows_hwm, gal.shape[0])
            Gp = gal.shape[0]
            q_feat = st.stage_queries(N, sl, [q.feat for q in qs])
            if self.tile_grid > 0:
                # tile path: ONE tile-masked segment-ID kernel call ranks
                # the whole round regardless of cfg.consolidate (the
                # relabeling is injective, so consolidation cannot change
                # the outcome — pinned by the tile differential).  Every
                # gallery row carries its fused (camera, tile) cell from
                # the ingest-time labels.
                TT = self.tile_grid * self.tile_grid
                gal_ct = st.ct[:Gp]
                pos = 0
                for key in batch_keys:
                    cnt = len(key_emb[key])
                    tiles_k = self.store.get_tile(*key)
                    if tiles_k is None or len(tiles_k) != cnt:
                        # ingest enforces labels, so this is a bookkeeping
                        # bug (eviction raced a replay read), not user error
                        raise RuntimeError(
                            f"tile labels missing/mismatched for {key}: "
                            f"got {None if tiles_k is None else len(tiles_k)}"
                            f" for {cnt} gallery rows")
                    gal_ct[pos:pos + cnt] = key[0] * TT + \
                        np.asarray(tiles_k, np.int32)
                    pos += cnt
                gal_seg = plan.gallery_segments(batch_keys, key_emb,
                                                st.seg[:Gp])
                (ps_next, m, mc, _, tv, ti, tc,
                 tf) = self._dispatch_rank_advance_tiles(
                    ps, jnp.asarray(q_feat), plan.q_seg_dev,
                    plan.mask_ct_dev, jnp.asarray(gal),
                    jnp.asarray(gal_ct), jnp.asarray(gal_cam),
                    jnp.asarray(gal_frame), jnp.asarray(gal_seg))
            elif self.cfg.consolidate:
                # consolidated path: ONE segment-ID kernel call ranks the
                # whole round — frames relabeled to the plan's compact
                # segment ids, gal_frame riding along for the trace bands
                gal_seg = plan.gallery_segments(batch_keys, key_emb,
                                                st.seg[:Gp])
                (ps_next, m, mc, _, tv, ti, tc,
                 tf) = self._dispatch_rank_advance_seg(
                    ps, jnp.asarray(q_feat), jnp.asarray(plan.q_seg),
                    plan.mask_dev, jnp.asarray(gal),
                    jnp.asarray(gal_cam), jnp.asarray(gal_frame),
                    jnp.asarray(gal_seg))
            else:
                (ps_next, m, mc, _, tv, ti, tc,
                 tf) = self._dispatch_rank_advance(
                    ps, jnp.asarray(q_feat), plan.mask_dev,
                    jnp.asarray(gal), jnp.asarray(gal_cam),
                    jnp.asarray(gal_frame))
            # the step's match_emb stays on the device: each matched row
            # is already in the host gallery, at the index the step chose
            matched = self._read(m)
            match_cam = self._read(mc)
            topk_val = self._read(tv)
            topk_idx = self._read(ti)
            topk_cam = self._read(tc)
            topk_frame = self._read(tf)
            emb_rows = match_rows(matched, match_cam, topk_idx, topk_cam,
                                  self.cfg.topk_rerank)
            stats["matches"] += int(matched[sl].sum())
            if self.tile_grid > 0:
                # follow-window state: a confirmed match pins the query to
                # the matched gallery row's tile (gal_ct carries the fused
                # cell; % T*T recovers the tile) — the next round's learned
                # self-camera admission narrows around it
                for i, q in enumerate(qs):
                    mi = emb_rows[sl[i]]
                    if mi >= 0 and gal_ct[mi] >= 0:
                        q.tile_q = int(gal_ct[mi]) % TT
        else:
            ps_next = self._dispatch_advance(ps)

        if trace is not None:
            for i, q in enumerate(qs):
                j = sl[i]
                records[q.qid] = dict(
                    qid=q.qid, f_curr=q.f_curr, phase=q.phase,
                    epoch=self.model_epoch,
                    mask=mask[j].copy(), matched=bool(matched[j]),
                    match_cam=int(match_cam[j]),
                    match_val=float(topk_val[j, 0]),
                    match_idx=int(topk_idx[j, 0]),
                    topk=tuple((float(topk_val[j, b]), int(topk_cam[j, b]),
                                int(topk_frame[j, b])) for b in range(K)))
            trace.extend(records[q.qid] for q in all_qs)

        self._scatter(qs, ps_next, matched, match_cam, emb_rows, gal)

        # double-buffer: with the round's outcomes scattered, the cohort's
        # NEXT cursors are known — speculate round N+1's admission and start
        # its cached fetches now, so they deliver while other work runs
        if self._prefetch is not None:
            self._issue_prefetch(all_qs)

    def _issue_prefetch(self, qs: list[QueryState]) -> None:
        """Speculatively issue async fetches for the cohort's next round.

        ``policy.advance`` already produced the next cursors/phases, so the
        next admission mask is re-evaluated on the REAL advanced state; the
        only guesses are the live frontier (``self.t`` — next tick moves it)
        and anything that mutates between rounds (a model swap, eviction, a
        resubmitted query).  Guesses only cost accuracy, never correctness:
        ``PrefetchPipeline.consume`` validates at use time and the round
        falls back to the blocking fetch — the trace cannot change.
        """
        # only replay cursors (f_curr behind the live frontier) can read a
        # cache-RESIDENT block — a live-frontier block was ingested this tick
        # and is not embedded yet, so issuing its key either declines or,
        # worse, strands a handle that counts as prefetch_wasted when a
        # concurrent replayer happened to embed the frame.  Filtering to
        # replay cursors (not just skipping when NOBODY replays) keeps the
        # waste metric honest in mixed cohorts and keeps the speculative
        # admit dispatch proportional to the replay rounds.
        live = [q for q in qs if not q.done and q.f_curr < self.t]
        if not live:
            return
        ps = self._gather(live)
        sl = self._slots
        mask = self._read(self._dispatch_admit(ps))
        keys: set[tuple[int, int]] = set()
        for i, q in enumerate(live):
            for cam in np.flatnonzero(mask[sl[i]]):
                keys.add((int(cam), q.f_curr))
        self._prefetch.issue(keys)

    def _skip_round(self, qs: list[QueryState], stats: dict,
                    records: dict | None) -> None:
        """Host mirror of one no-match ``policy.advance`` step for
        sampled-out replay rounds (their admission mask is all-False, so no
        inference can happen; only the cursor/phase machine moves).  Must
        stay transition-identical to ``advance`` with matched=False —
        pinned by the fast-path equivalence regression test.
        """
        stats["skipped_rounds"] += len(qs)
        self.skipped_steps += len(qs)
        if records is not None:
            empty_topk = ((float(NEG_INF), -1, -1),) * self.cfg.topk
            for q in qs:
                records[q.qid] = dict(qid=q.qid, f_curr=q.f_curr,
                                      phase=q.phase, epoch=self.model_epoch,
                                      mask=np.zeros(self.C, bool),
                                      matched=False, match_cam=0,
                                      match_val=float(NEG_INF), match_idx=-1,
                                      topk=empty_topk)
        p = self.policy
        for q in qs:
            f_next = q.f_curr + 1
            el_next = f_next - q.f_q
            if p.scheme in ("all", "geo") or not p.use_replay:
                done = el_next > p.exit_t or f_next >= _NO_HORIZON
                f_new, phase_new = f_next, q.phase
            else:
                nothing_relaxed = self._w2[q.c_q] <= p.self_window
                exh1 = q.phase == 1 and el_next > self._w1[q.c_q]
                exh2 = q.phase == 2 and el_next > self._w2[q.c_q]
                exh3 = q.phase >= 3 and el_next > p.exit_t
                if p.exhaustive_final:
                    esc = exh1 or exh2
                    done = exh3 or f_next >= _NO_HORIZON
                else:
                    esc = exh1 and not nothing_relaxed
                    done = ((exh1 and nothing_relaxed) or exh2 or exh3
                            or f_next >= _NO_HORIZON)
                phase_new = q.phase + 1 if esc else q.phase
                f_new = q.f_q + 1 if esc else f_next
            q.f_curr, q.phase, q.done = f_new, phase_new, bool(done)
            if q.done:
                self._on_query_done(q)


def trace_key(trace: list) -> list[tuple]:
    """Canonical per-round tuple stream of ``tick(record_trace=...)``
    records: admissions (mask), the match decision, tie-break (gallery row
    index), raw kernel score, the top-k (value, cam, frame) candidate bands
    and the model epoch the round ran under.  Two runs are trace-identical
    when their keys are equal."""
    return [(r["qid"], r["f_curr"], r["phase"], r["epoch"],
             tuple(bool(x) for x in r["mask"]), bool(r["matched"]),
             int(r["match_cam"]), float(r["match_val"]), int(r["match_idx"]),
             tuple(r["topk"]))
            for r in trace]
