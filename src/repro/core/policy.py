"""The admission control plane: one `SearchPolicy`, one `admit`, one `advance`.

This module is the single home of ReXCam's spatio-temporal admission
semantics (paper §5.1-§5.3, Algorithm 1).  Every consumer — the batched
offline tracker (``repro.core.tracker``), the live serving engine
(``repro.runtime.engine``), benchmarks and examples via ``repro.api`` —
drives the same three primitives:

  ``SearchPolicy``   frozen, hashable search configuration (scheme,
                     thresholds, relax/replay settings).  Static under jit.
  ``PhaseState``     batched (Q,) pytree of per-query search state: the
                     last-seen anchor (c_q, f_q), the content cursor f_curr,
                     the live frontier, the Alg.-1 phase, and done flags.
  ``admit``          pure, vectorized (Q, C) admission-mask construction —
                     the ONLY place a correlation threshold is compared.
  ``advance``        pure phase-machine step: match resets, window
                     exhaustion, the phase-2 rewind to f_q + 1, the optional
                     phase-3 exhaustive pass, and exit-threshold termination.

Phase semantics (§5.2-5.3, Alg. 1 line 21): phase 1 searches the normal
spatio-temporal windows; when those are *exhausted* the tracker rewinds to
f_q + 1 and replays with thresholds relaxed x ``relax_factor`` (phase 2).
When the relaxed windows are exhausted too, the model's prediction is that
the query has exited; ``exhaustive_final=True`` additionally runs the
paper's literal all-camera terminal sweep (phase 3) — off by default since
the paper's reported ~3 s delays show it cannot run per query (DESIGN.md
§7).  ``exit_t`` is the baseline's "maximum duration" (§3.2) and an upper
bound on every phase.

Replay lag follows §5.3: a cursor behind the live frontier processes
*historical* frames; skip mode (process 1-in-k) and fast-forward mode
(k x throughput) trade cost, accuracy and delay differently.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # avoid a runtime import cycle with correlation.py
    from repro.core.correlation import SpatioTemporalModel


# ---------------------------------------------------------------------------
# The model query interface: thresholds vs the model's raw arrays.
# ``SpatioTemporalModel`` methods delegate here so admission-mask
# construction lives in exactly one module.
# ---------------------------------------------------------------------------

def spatial_mask(model: "SpatioTemporalModel", c_s, s_thresh) -> jnp.ndarray:
    """Destinations spatially correlated with c_s.

    Scalar c_s -> (C,); batched c_s (Q,) with per-query thresholds -> (Q, C).
    """
    th = jnp.asarray(s_thresh)
    if jnp.ndim(c_s) > 0 and th.ndim > 0:
        th = th[:, None]
    return model.S[c_s] >= th


def temporal_mask(model: "SpatioTemporalModel", c_s, elapsed, t_thresh) -> jnp.ndarray:
    """Destinations temporally correlated at ``elapsed`` steps since c_s.

    The fraction already arrived at time t is the CDF *before* t's bin — the
    exclusive form keeps the arrival bin itself searchable even for
    degenerate (zero-variance) travel-time distributions.  Scalar args ->
    (C,); batched (Q,) args -> (Q, C).
    """
    batched = jnp.ndim(c_s) > 0 or jnp.ndim(elapsed) > 0
    c, e = jnp.broadcast_arrays(jnp.atleast_1d(jnp.asarray(c_s)),
                                jnp.atleast_1d(jnp.asarray(elapsed)))
    th = jnp.broadcast_to(jnp.asarray(t_thresh), c.shape)
    b = jnp.clip(e // model.bin_width, 0, model.n_bins - 1)
    arrived = jnp.where((b > 0)[:, None],
                        model.cdf[c, :, jnp.maximum(b - 1, 0)], 0.0)
    started = e[:, None] >= model.f0[c]
    out = started & (arrived <= 1.0 - th[:, None])
    return out if batched else out[0]


def correlated(model: "SpatioTemporalModel", c_s, elapsed, s_thresh, t_thresh) -> jnp.ndarray:
    """M(c_s, ·, elapsed): bool mask over destination cameras."""
    return spatial_mask(model, c_s, s_thresh) & \
        temporal_mask(model, c_s, elapsed, t_thresh)


def window_end(model: "SpatioTemporalModel", s_thresh: float, t_thresh: float) -> jnp.ndarray:
    """(C,) — per source camera, the elapsed time beyond which NO admitted
    destination's temporal window is still open (Alg. 1 line 21's exhaustion
    test, vectorized).  t_thresh=0 never exhausts within the histogram
    range.  +1 bin for the exclusive-CDF convention of ``temporal_mask``."""
    open_bins = ((model.cdf <= 1.0 - t_thresh).sum(-1) + 1) * model.bin_width
    open_bins = jnp.minimum(open_bins, model.n_bins * model.bin_width)  # (C,C)
    admitted = model.S >= s_thresh
    ends = jnp.where(admitted, open_bins, 0)
    return ends.max(axis=1)


def potential_savings(model: "SpatioTemporalModel", s_thresh: float,
                      t_thresh: float, weight_by_traffic: bool = True) -> float:
    """Analytic potential (paper §3.2): ratio of camera-steps searched by a
    correlation-agnostic baseline (all C cameras for the max window) to the
    camera-steps M admits, averaged over source cameras (optionally
    traffic-weighted).  Spatial-only: t_thresh=0.  Temporal-only: s_thresh=0."""
    C = model.n_cams
    sp = np.asarray(model.S) >= s_thresh                # (C, C) searched pairs
    cdf = np.asarray(model.cdf)
    f0 = np.asarray(model.f0)
    NB = cdf.shape[-1]
    b = np.arange(NB)[None, None, :] * model.bin_width  # (1,1,NB) bin start times
    active = (b >= f0[..., None]) & (cdf <= 1.0 - t_thresh)   # (C,C,NB)
    steps = (active.sum(-1) * model.bin_width) * sp     # (C,C) searched steps
    per_src = steps.sum(1).astype(np.float64)           # camera-steps per source
    baseline = C * NB * model.bin_width
    if weight_by_traffic:
        w = np.asarray(model.counts).sum(1).astype(np.float64)
        w = w / max(w.sum(), 1.0)
        filt = float((per_src * w).sum())
    else:
        filt = float(per_src.mean())
    return baseline / max(filt, 1e-9)


# ---------------------------------------------------------------------------
# SearchPolicy — the one search configuration every consumer shares.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SearchPolicy:
    """Algorithm-1 parameters (supersedes the old TrackerParams and the
    overlapping EngineConfig fields).  Frozen and hashable: pass as a static
    argument under jit."""

    scheme: str = "rexcam"          # rexcam | all | geo | spatial_only
    s_thresh: float = 0.05
    t_thresh: float = 0.02
    exit_t: int = 240               # max steps without a match (baseline window)
    match_thresh: float = 0.28      # cosine-distance acceptance
    feat_alpha: float = 0.25        # query-representation EMA rate
    relax_factor: float = 10.0      # replay threshold relaxation (paper: x10)
    replay_speed: float = 1.0       # >1 = parallelism ("ff") mode
    replay_skip: int = 1            # >1 = frame-skip mode
    use_replay: bool = True
    exhaustive_final: bool = False  # paper-literal terminal all-camera pass
    self_window: int = 6            # steps the last-seen camera stays admitted

    @property
    def use_spatial(self) -> bool:
        return self.scheme in ("rexcam", "spatial_only")

    @property
    def use_temporal(self) -> bool:
        return self.scheme == "rexcam" and self.t_thresh > 0.0

    @property
    def replay_rate(self) -> float:
        """Content steps consumed per wall step while replaying."""
        return self.replay_speed * self.replay_skip


# ---------------------------------------------------------------------------
# PhaseState + precomputed exhaustion windows.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PhaseState:
    """Batched (Q,) per-query search state — the Alg.-1 state machine."""

    f_q: jnp.ndarray     # (Q,) int32   frame of the last confirmed sighting
    c_q: jnp.ndarray     # (Q,) int32   camera of the last confirmed sighting
    f_curr: jnp.ndarray  # (Q,) int32   content frame the search cursor is on
    phase: jnp.ndarray   # (Q,) int32   1 = normal, 2 = relaxed replay, >=3 = exhaustive
    live_f: jnp.ndarray  # (Q,) float32 live frontier (content time of "now")
    done: jnp.ndarray    # (Q,) bool    search concluded

    @classmethod
    def init(cls, c_q, f_q) -> "PhaseState":
        """Fresh phase-1 state anchored at the (c_q, f_q) sightings."""
        f_q = jnp.asarray(f_q, jnp.int32)
        c_q = jnp.asarray(c_q, jnp.int32)
        return cls(f_q=f_q, c_q=c_q, f_curr=f_q + 1,
                   phase=jnp.ones_like(f_q),
                   live_f=(f_q + 1).astype(jnp.float32),
                   done=jnp.zeros(f_q.shape, jnp.bool_))

    @property
    def elapsed(self) -> jnp.ndarray:
        return self.f_curr - self.f_q

    @property
    def behind(self) -> jnp.ndarray:
        """Replaying: the cursor is strictly behind the live frontier."""
        return self.f_curr.astype(jnp.float32) < self.live_f - 0.5


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PhaseWindows:
    """Per-source-camera exhaustion horizons for phases 1 and 2."""

    w_end1: jnp.ndarray  # (C,) phase-1 window end
    w_end2: jnp.ndarray  # (C,) relaxed (phase-2) window end


def phase_windows(model: "SpatioTemporalModel", policy: SearchPolicy) -> PhaseWindows:
    t_th = policy.t_thresh if policy.use_temporal else 0.0
    w1 = window_end(model, policy.s_thresh, t_th)
    w2 = window_end(model, policy.s_thresh / policy.relax_factor,
                    t_th / policy.relax_factor)
    clamp = lambda w: jnp.minimum(jnp.maximum(w, policy.self_window), policy.exit_t)  # noqa: E731
    return PhaseWindows(w_end1=clamp(w1), w_end2=clamp(w2))


# ---------------------------------------------------------------------------
# admit — the one admission-mask construction.
# ---------------------------------------------------------------------------

def replay_sampled_out(policy: SearchPolicy, f_q, f_curr, behind):
    """§5.3 skip mode: True where a replaying cursor's content frame is
    sampled out by the 1-in-k gate (its admission mask is all-False by
    construction).  Works batched (jnp arrays, inside ``admit``) and scalar
    (python ints/bools, the engine's host-side short-circuit of sampled-out
    replay rounds) — so the gate lives in exactly one place."""
    if policy.replay_skip <= 1:
        return behind & False          # shape/type-preserving all-False
    return behind & ((f_curr - f_q) % policy.replay_skip != 0)

def admit(model: "SpatioTemporalModel", policy: SearchPolicy, state: PhaseState,
          geo_adj=None) -> jnp.ndarray:
    """(Q, C) bool: which cameras each live query searches at its cursor.

    Pure and jit-compatible (``policy`` static).  Combines the scheme's
    correlation mask, the self-camera follow window, the phase-2 threshold
    relaxation, the phase-3 exhaustive pass, and §5.3 skip-mode sampling of
    historical frames.  Done queries admit nothing.
    """
    Q = state.f_q.shape[0]
    C = model.S.shape[0]
    elapsed = state.elapsed

    # last-seen camera stays admitted briefly (single-camera follow)
    self_mask = jax.nn.one_hot(state.c_q, C, dtype=jnp.bool_) & \
        (elapsed <= policy.self_window)[:, None]

    if policy.scheme == "all":
        mask = jnp.ones((Q, C), bool)
    elif policy.scheme == "geo":
        if geo_adj is None:                 # no proximity data: degrade to all
            geo_adj = jnp.ones((C, C), bool)
        mask = geo_adj[state.c_q] | self_mask
    else:
        relax = jnp.where(state.phase >= 2, 1.0 / policy.relax_factor, 1.0)
        sp = spatial_mask(model, state.c_q, policy.s_thresh * relax) \
            if policy.use_spatial else jnp.ones((Q, C), bool)
        tp = temporal_mask(model, state.c_q, elapsed, policy.t_thresh * relax) \
            if policy.use_temporal else jnp.ones((Q, C), bool)
        mask = (sp & tp) | self_mask
        mask = jnp.where(state.phase[:, None] >= 3, True, mask)  # exhaustive pass

    # lag-aware processing: behind the live frontier -> historical frames,
    # optionally sampled 1-in-k (skip mode)
    process = ~replay_sampled_out(policy, state.f_q, state.f_curr, state.behind)
    return mask & process[:, None] & (~state.done)[:, None]


def tile_follow_mask(tile_q: jnp.ndarray, T: int) -> jnp.ndarray:
    """(Q, T*T) bool: the 3x3 neighborhood of each query's last-matched
    tile on the T x T grid (clipped at frame edges) — the same 1-tile halo
    the profiler dilates its entry-region masks by, covering per-frame
    jitter and slow in-FOV motion.  ``tile_q < 0`` (no match yet: the
    anchor detection carries no tile) admits every tile."""
    cells = jnp.arange(T * T, dtype=jnp.int32)
    cy, cx = cells // T, cells % T
    qy, qx = (tile_q[:, None] // T), (tile_q[:, None] % T)
    near = (jnp.abs(cy[None, :] - qy) <= 1) & (jnp.abs(cx[None, :] - qx) <= 1)
    return near | (tile_q < 0)[:, None]


def tile_admission(model: "SpatioTemporalModel", policy: SearchPolicy,
                   state: PhaseState,
                   tile_q: jnp.ndarray | None = None) -> jnp.ndarray:
    """(Q, C, T*T) bool: which sub-frame tiles of each destination camera a
    query searches, from the profiled entry-region masks
    ``model.tile_admit[c_q]``.

    Recall-preserving relaxations mirror the camera plane's: the relaxed
    replay and exhaustive phases (phase >= 2) admit every tile — a rescue
    pass must not re-apply the spatial prior whose miss it is rescuing —
    and pairs the profiler never observed are already all-True in the
    tensor itself.

    The self camera (the follow window) is where entry-region priors say
    nothing: an entity mid-FOV is wherever it was last seen, not at a
    portal.  With a LEARNED model (``model.tile_learned``) and per-query
    last-matched tiles ``tile_q``, the self column narrows to
    ``tile_follow_mask`` — the last tile +- a 1-tile halo, all tiles until
    the first match.  A synthesized (tile-less) model keeps the whole
    frame, preserving the bit-identity with camera-granular serving the
    tile differential pins."""
    C = model.S.shape[0]
    tiles = model.tile_admit[state.c_q]                  # (Q, C, TT)
    self_cam = jax.nn.one_hot(state.c_q, C, dtype=jnp.bool_)
    if model.tile_learned and tile_q is not None:
        # inside the follow window the self column narrows to the follow
        # mask (a missed novel re-entry is phase 2's to rescue, all tiles);
        # outside it, self admission only comes from observed self-transit
        # correlation, so the learned diagonal (re-entry portals) applies
        follow = tile_follow_mask(tile_q, model.tile_grid)   # (Q, TT)
        diag = model.tile_admit[state.c_q, state.c_q]        # (Q, TT)
        self_col = jnp.where((state.elapsed <= policy.self_window)[:, None],
                             follow, diag)
        tiles = jnp.where(self_cam[:, :, None], self_col[:, None, :], tiles)
    else:
        self_mask = self_cam & (state.elapsed <= policy.self_window)[:, None]
        tiles = tiles | self_mask[:, :, None]
    return tiles | (state.phase >= 2)[:, None, None]


def admit_tiles(model: "SpatioTemporalModel", policy: SearchPolicy,
                state: PhaseState, geo_adj=None, tile_q=None):
    """Tile-granular admission: the (Q, C) camera mask (identical to
    ``admit`` — the tile plane refines, never changes, WHICH cameras are
    searched) plus the fused (Q, C*T*T) per-(camera, tile) admission the
    tile kernel consumes: ``mask_ct[q, c*T*T + t] = mask[q, c] AND
    tile_admission[q, c, t]``.  ``tile_q`` (Q,) int32 is each query's
    last-matched tile (-1 before the first match) — only consulted for a
    learned model's self-camera follow column."""
    mask = admit(model, policy, state, geo_adj)
    tiles = tile_admission(model, policy, state, tile_q)
    Q = mask.shape[0]
    mask_ct = (mask[:, :, None] & tiles).reshape(Q, -1)
    return mask, mask_ct


def tile_counts(mask_ct: jnp.ndarray, q_seg: jnp.ndarray):
    """The round's tile cost in both conventions, as int32 scalars:
    ``admitted`` counts the (query, camera, tile) steps of ``mask_ct``
    (Q, C*T*T); ``unique`` counts, per content-frame segment ``q_seg`` (Q,),
    the union of its queries' admitted cells — the deduplicated sub-frame
    pixel load, one (camera, frame) key's tiles at a time.  Padding rows
    (``q_seg < 0``) are done and admit nothing, and belong to no segment.

    The union is a segment one-hot (Q, Q) times the mask: every cell of the
    product counts its segment's admitting rows, exactly — 0/1 inputs are
    exact in bfloat16 and Q <= 2**24 rows sum exactly in float32."""
    Q = mask_ct.shape[0]
    onehot = q_seg[None, :] == jnp.arange(Q, dtype=q_seg.dtype)[:, None]
    per_seg = jnp.dot(onehot.astype(jnp.bfloat16),
                      mask_ct.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return (jnp.sum(mask_ct, dtype=jnp.int32),
            jnp.sum(per_seg > 0, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# advance — the one phase-machine transition.
# ---------------------------------------------------------------------------

def advance(policy: SearchPolicy, windows: PhaseWindows, state: PhaseState,
            matched: jnp.ndarray, match_cam: jnp.ndarray,
            horizon: int) -> PhaseState:
    """One Alg.-1 transition for every query at once.

    ``matched`` (Q,) bool and ``match_cam`` (Q,) int32 come from the
    consumer's re-id step.  On a match: re-anchor at (match_cam, f_curr) and
    reset to phase 1.  Otherwise advance the cursor; on window exhaustion
    escalate — phase 1 rewinds to f_q + 1 with relaxed thresholds (phase 2),
    phase 2 either concludes exit or (``exhaustive_final``) enters the
    all-camera phase 3, which runs to the exit threshold.
    """
    matched = matched & ~state.done
    f_q = jnp.where(matched, state.f_curr, state.f_q)
    c_q = jnp.where(matched, match_cam, state.c_q)
    phase = jnp.where(matched, 1, state.phase)

    f_next = state.f_curr + 1
    # behind the frontier: content advances (speed*skip) x realtime, so the
    # live frontier only moves 1/(speed*skip) wall-steps per content step;
    # caught up: the frontier IS the content time.
    rate = 1.0 / policy.replay_rate
    live_next = jnp.where(state.behind, state.live_f + rate,
                          f_next.astype(jnp.float32))
    live_next = jnp.maximum(live_next, f_next.astype(jnp.float32))

    el_next = f_next - f_q
    if policy.scheme in ("all", "geo") or not policy.use_replay:
        done_new = state.done | (el_next > policy.exit_t) | (f_next >= horizon)
        phase_new = phase
        f_new = f_next
    else:
        # phase 1 exhausts its windows -> rewind + relax (phase 2);
        # phase 2 exhausts -> exhaustive pass (phase 3) or conclude exit;
        # phase 3 runs to the exit threshold.  If even the relaxed model
        # admits nothing beyond the self-window, the model's prediction is
        # "exited" — conclude directly, no pointless rewind.
        nothing_relaxed = windows.w_end2[c_q] <= policy.self_window
        exh1 = (phase == 1) & (el_next > windows.w_end1[c_q])
        exh2 = (phase == 2) & (el_next > windows.w_end2[c_q])
        exh3 = (phase >= 3) & (el_next > policy.exit_t)
        if policy.exhaustive_final:
            esc = exh1 | exh2
            done_new = state.done | exh3 | (f_next >= horizon)
        else:
            esc = exh1 & ~nothing_relaxed
            done_new = (state.done | (exh1 & nothing_relaxed) | exh2 | exh3
                        | (f_next >= horizon))
        phase_new = jnp.where(esc, phase + 1, phase)
        f_new = jnp.where(esc, f_q + 1, f_next)

    return PhaseState(
        f_q=f_q,
        c_q=c_q,
        f_curr=jnp.where(state.done, state.f_curr, f_new),
        phase=jnp.where(state.done, state.phase, phase_new),
        live_f=jnp.where(state.done, state.live_f, live_next),
        done=done_new,
    )
