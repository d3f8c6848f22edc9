"""Plain reference of ReXCam serving, and the comparison that decides
``correct``.

Written from the paper's Algorithm 1 and the semantics the program
documents, with nothing imported from the program and nothing taken from
what it made: the reference profiles the history itself, follows each
sampled query on its own through the same stream, and scores candidates in
float64.

The model's arrays are float32 by definition (``S``, the travel-time CDF)
and the thresholds are compared in float32, in the order the configuration
states them: phase 1 at ``s_thresh``/``t_thresh``, the relaxed replay phase
at ``threshold * (1 / relax_factor)`` in float32 for admission, and the
exhaustion windows at ``threshold / relax_factor`` rounded once.  So the
reference and the program decide every admission alike; a mismatch there
is a fault, not rounding.

The comparison is teacher-forced per query: each served round is checked
against the reference's own decision from the same state, and then the
reference continues from the program's match decision (the detection the
program matched, identified by its score), so one near-tie between two
detections cannot make every later round disagree.  Two numbers come out:

  score_gap   the widest gap, over every compared round, between the
              served match score and the reference's best score (or the
              reference's score of the detection the program matched),
              and between the served and reference query features at the
              end.  Float32 scoring reads about 1e-6; bfloat16 about 1e-3.
  mismatches  rounds whose discrete outcome differs: rounds per tick,
              cursor, phase, camera admission, match decision away from
              the threshold, matched camera, a candidate set seen empty
              on one side only, the final query state.  Must be 0.
"""
from __future__ import annotations

import dataclasses

import numpy as np

INF_TIME = 2 ** 30
NEG_SENTINEL = -1e29        # the program's "no candidate" score is -1e30
THRESH_EPS = 1e-5           # a match decision this close to the threshold
#                             may go either way in float32


# ---------------------------------------------------------------------------
# profiling: transitions -> S, travel-time CDF, first arrivals, tile masks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    S: np.ndarray          # (C, C) float32 share of c_s's outbound traffic
    cdf: np.ndarray        # (C, C, NB) float32 fraction arrived by bin b
    f0: np.ndarray         # (C, C) int64 earliest travel time
    tiles: np.ndarray | None   # (C, C, T*T) bool entry-region masks
    bin_width: int


def _halo(core: np.ndarray, T: int) -> np.ndarray:
    """One-tile dilation of a (T*T,) mask on the T x T grid."""
    g = core.reshape(T, T)
    p = np.pad(g, 1)
    out = np.zeros_like(g)
    for dy in range(3):
        for dx in range(3):
            out |= p[dy:dy + T, dx:dx + T]
    return out.reshape(T * T)


def profile(visits, n_cams: int, *, n_bins: int, bin_width: int,
            until: int | None, tile_grid: int) -> Model:
    """Consecutive visits of one entity are a transition c_s -> c_d taking
    dt = t_in(next) - t_out(prev) (at least 0); an entity's last visit is
    an exit.  ``until`` keeps visits that start before it."""
    ent, cam = np.asarray(visits.ent), np.asarray(visits.cam)
    t_in, t_out = np.asarray(visits.t_in), np.asarray(visits.t_out)
    xy = np.asarray(visits.tile_xy)
    if until is not None:
        keep = t_in < until
        ent, cam, t_in, t_out, xy = (a[keep] for a in
                                     (ent, cam, t_in, t_out, xy))
    order = np.lexsort((t_in, ent))
    ent, cam, t_in, t_out, xy = (a[order] for a in (ent, cam, t_in, t_out, xy))
    C, NB = n_cams, n_bins
    counts = np.zeros((C, C))
    hist = np.zeros((C, C, NB))
    f0 = np.full((C, C), INF_TIME, np.int64)
    exits = np.zeros(C)
    T = tile_grid
    seen = np.zeros((C, C, T * T), bool) if T else None
    for i in range(len(ent)):
        last = i + 1 == len(ent) or ent[i + 1] != ent[i]
        if last:
            exits[cam[i]] += 1
            continue
        s, d = cam[i], cam[i + 1]
        dt = max(int(t_in[i + 1] - t_out[i]), 0)
        counts[s, d] += 1
        hist[s, d, min(dt // bin_width, NB - 1)] += 1
        f0[s, d] = min(f0[s, d], dt)
        if T:
            x, y = (min(max(float(v), 0.0), np.nextafter(1.0, 0.0))
                    for v in xy[i + 1])
            seen[s, d, int(y * T) * T + int(x * T)] = True
    S = counts / np.maximum(counts.sum(1) + exits, 1.0)[:, None]
    cdf = np.cumsum(hist, axis=-1)
    cdf = cdf / np.maximum(cdf[..., -1:], 1.0)
    tiles = None
    if T:
        # observed entry tiles plus a one-tile halo; a pair never observed
        # admits every tile
        tiles = np.ones((C, C, T * T), bool)
        for s, d in zip(*np.nonzero(counts)):
            tiles[s, d] = _halo(seen[s, d], T)
    return Model(S.astype(np.float32), cdf.astype(np.float32), f0, tiles,
                 bin_width)


# ---------------------------------------------------------------------------
# Algorithm 1 for one query
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Policy:
    s_thresh: float
    t_thresh: float
    exit_t: int
    match_thresh: float
    feat_alpha: float
    relax_factor: float
    replay_speed: float
    self_window: int
    retention: int


class Rules:
    """Admission, exhaustion windows and the phase machine of one model
    under one policy."""

    def __init__(self, m: Model, p: Policy):
        self.m, self.p = m, p
        NB = m.cdf.shape[-1]
        f32 = np.float32
        relax = f32(1.0 / p.relax_factor)
        # admission thresholds per phase, as float32 products
        self.s_th = {1: f32(p.s_thresh), 2: f32(f32(p.s_thresh) * relax)}
        t_th = {1: f32(p.t_thresh), 2: f32(f32(p.t_thresh) * relax)}
        self.t_open = {k: f32(f32(1.0) - v) for k, v in t_th.items()}

        def window_end(s, t):
            open_bins = ((m.cdf <= f32(1.0 - t)).sum(-1) + 1) * m.bin_width
            open_bins = np.minimum(open_bins, NB * m.bin_width)
            return np.where(m.S >= f32(s), open_bins, 0).max(axis=1)

        def clamp(w):
            return np.minimum(np.maximum(w, p.self_window), p.exit_t)

        self.w1 = clamp(window_end(p.s_thresh, p.t_thresh))
        self.w2 = clamp(window_end(p.s_thresh / p.relax_factor,
                                   p.t_thresh / p.relax_factor))

    def cameras(self, c_q: int, f_q: int, f_curr: int, phase: int):
        m, p = self.m, self.p
        e = f_curr - f_q
        NB = m.cdf.shape[-1]
        b = min(max(e // m.bin_width, 0), NB - 1)
        arrived = m.cdf[c_q, :, b - 1] if b > 0 else np.zeros(len(m.S),
                                                              np.float32)
        ph = min(phase, 2)
        mask = ((m.S[c_q] >= self.s_th[ph]) & (e >= m.f0[c_q])
                & (arrived <= self.t_open[ph]))
        if e <= p.self_window:
            mask[c_q] = True
        return mask

    def tiles(self, c_q: int, f_q: int, f_curr: int, phase: int,
              tile_q: int, T: int) -> np.ndarray:
        """(C, T*T) tiles admitted per camera."""
        tiles = self.m.tiles[c_q].copy()
        if phase >= 2:
            tiles[:] = True
            return tiles
        if f_curr - f_q <= self.p.self_window:
            if tile_q < 0:
                tiles[c_q] = True
            else:
                cy, cx = np.divmod(np.arange(T * T), T)
                qy, qx = divmod(tile_q, T)
                tiles[c_q] = (abs(cy - qy) <= 1) & (abs(cx - qx) <= 1)
        return tiles

    def advance(self, st: "QState", matched: bool, match_cam: int) -> None:
        p = self.p
        if matched:
            st.f_q, st.c_q, st.phase = st.f_curr, match_cam, 1
        f_next = st.f_curr + 1
        el = f_next - st.f_q
        nothing_relaxed = self.w2[st.c_q] <= p.self_window
        exh1 = st.phase == 1 and el > self.w1[st.c_q]
        exh2 = st.phase == 2 and el > self.w2[st.c_q]
        exh3 = st.phase >= 3 and el > p.exit_t
        esc = exh1 and not nothing_relaxed
        st.done = bool((exh1 and nothing_relaxed) or exh2 or exh3)
        if esc:
            st.phase += 1
            st.f_curr = st.f_q + 1
        else:
            st.f_curr = f_next


@dataclasses.dataclass
class QState:
    feat: np.ndarray        # float64 unit
    c_q: int
    f_q: int
    f_curr: int
    phase: int = 1
    done: bool = False
    credit: float = 0.0
    tile_q: int = -1
    matches: int = 0


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

class Comparison:
    """Follows sampled queries through the stream and compares each served
    round.  ``world`` is the benchmark's world, ``records`` maps qid -> tick
    -> the program's round records of that tick, ``final`` maps qid -> the
    program's query state after the last tick (f_curr, phase, done, number
    of matches, feature, last matched tile)."""

    def __init__(self, world: dict, cfg: dict, policy: Policy,
                 control: bool = False):
        serve = cfg["serve"]
        self.T = int(serve.get("tile_grid", 0))
        self.rules = Rules(profile(world["history"], world["net"].n_cams,
                                   n_bins=int(serve["n_bins"]),
                                   bin_width=int(serve["bin_width"]),
                                   until=world["profile_until"],
                                   tile_grid=self.T), policy)
        self.p = policy
        self.gal, self.tile_of = world["gal"], world["tiles"]
        f = world["feats"].astype(np.float64)
        self.rows = f / np.linalg.norm(f, axis=1, keepdims=True)
        self.t0 = world["t0"]
        # FrameStore retention: a camera's horizon trails the last step it
        # ingested any detection at
        nonempty = (self.gal >= 0).any(-1)                       # (C, H)
        steps = np.where(nonempty, np.arange(nonempty.shape[1]), -1)
        steps[:, :self.t0] = -1
        self.latest = np.maximum.accumulate(steps, axis=1)
        self.control = control
        self.ctrl_pairs: list = []        # (feat, vids, best) per round
        self.score_gap = 0.0
        self.mismatches = 0
        self.notes: list[str] = []
        self.rounds = 0
        self.queries = 0

    def _fault(self, qid, tick, what):
        self.mismatches += 1
        if len(self.notes) < 8:
            self.notes.append(f"q{qid} t{tick}: {what}")

    def _candidates(self, st: QState, mask: np.ndarray, t: int):
        f = st.f_curr
        vids, cams = [], []
        if f < self.t0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        tiles = (self.rules.tiles(st.c_q, st.f_q, f, st.phase, st.tile_q,
                                  self.T) if self.T else None)
        for c in np.flatnonzero(mask):
            if f < self.latest[c, t] - self.p.retention:
                continue                                  # evicted
            v = self.gal[c, f]
            v = v[v >= 0]
            if tiles is not None:
                v = v[tiles[c, self.tile_of[v]]]
            vids.append(v)
            cams.append(np.full(len(v), c))
        if not vids:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return np.concatenate(vids), np.concatenate(cams)

    def query(self, qid: int, feat: np.ndarray, cam: int, frame: int,
              t_submit: int, t_last: int, records: dict, final) -> None:
        f = np.asarray(feat, np.float64)
        st = QState(feat=f / np.linalg.norm(f), c_q=cam, f_q=frame,
                    f_curr=frame + 1)
        self.queries += 1
        p, rules = self.p, self.rules
        rate = p.replay_speed
        for t in range(t_submit, t_last + 1):
            recs = list(records.get(t, ()))
            if st.done:
                if recs:
                    return self._fault(qid, t, "rounds after the search ended")
                continue
            if st.f_curr >= t:
                st.credit, budget = 0.0, 1
            else:
                st.credit += rate
                budget = int(st.credit)
                st.credit -= budget
            while budget > 0 and st.f_curr <= t and not st.done:
                if st.f_curr < t:
                    budget -= 1
                else:
                    st.credit += budget - 1
                    budget = 0
                if not recs:
                    return self._fault(qid, t, f"round at f={st.f_curr} "
                                               f"not served")
                if not self._round(qid, t, st, recs.pop(0)):
                    return
            if recs:
                return self._fault(qid, t, f"{len(recs)} extra rounds")
        if final is None:
            return self._fault(qid, t_last, "query missing at the end")
        f_curr, phase, done, n_matches, feat_end, tile_q = final
        if (f_curr, phase, bool(done), n_matches) != (
                st.f_curr, st.phase, st.done, st.matches) or (
                self.T and tile_q != st.tile_q):
            return self._fault(qid, t_last, "final state differs")
        e = np.asarray(feat_end, np.float64)
        self.score_gap = max(self.score_gap, float(np.abs(
            e / max(np.linalg.norm(e), 1e-30) - st.feat).max()))

    def _round(self, qid, t, st: QState, rec: dict) -> bool:
        self.rounds += 1
        if (rec["f_curr"], rec["phase"]) != (st.f_curr, st.phase):
            self._fault(qid, t, f"cursor/phase {rec['f_curr']}/"
                                f"{rec['phase']} vs {st.f_curr}/{st.phase}")
            return False
        mask = self.rules.cameras(st.c_q, st.f_q, st.f_curr, st.phase)
        if not np.array_equal(np.asarray(rec["mask"], bool), mask):
            self._fault(qid, t, "camera admission differs")
            return False
        vids, cams = self._candidates(st, mask, t)
        val = float(rec["match_val"])
        if len(vids) == 0:
            if val > NEG_SENTINEL or rec["matched"]:
                self._fault(qid, t, "scored a candidate the reference "
                                    "does not have")
                return False
            self.rules.advance(st, False, 0)
            return True
        if val <= NEG_SENTINEL:
            self._fault(qid, t, "no candidate scored")
            return False
        scores = self.rows[vids] @ st.feat
        best = float(scores.max())
        self.score_gap = max(self.score_gap, abs(best - val))
        if self.control:
            self.ctrl_pairs.append((st.feat.astype(np.float32), vids, best))
        dist = 1.0 - best
        if bool(rec["matched"]) != (dist < self.p.match_thresh) and \
                abs(dist - self.p.match_thresh) > THRESH_EPS:
            self._fault(qid, t, "match decision differs")
            return False
        matched, mc = bool(rec["matched"]), int(rec["match_cam"])
        if matched:
            at = np.flatnonzero(cams == mc)
            if len(at) == 0:
                self._fault(qid, t, f"matched camera {mc} has no candidate")
                return False
            j = at[np.argmin(np.abs(scores[at] - val))]
            self.score_gap = max(self.score_gap, abs(float(scores[j]) - val))
            a = self.p.feat_alpha
            nf = (1 - a) * st.feat + a * self.rows[vids[j]]
            st.feat = nf / np.linalg.norm(nf)
            st.matches += 1
            if self.T:
                st.tile_q = int(self.tile_of[vids[j]])
        self.rules.advance(st, matched, mc)
        return True

    def control_gap(self) -> float | None:
        """The same rounds scored in bfloat16 on the default device: the
        query and gallery rows rounded to bfloat16 on the host (so no
        compiler can keep the excess precision), products summed in
        float32.  The widest gap to the float64 best; None when no round
        had a candidate."""
        if not self.ctrl_pairs:
            return None
        import jax
        import jax.numpy as jnp
        import ml_dtypes

        bf = ml_dtypes.bfloat16
        rows = jnp.asarray(self.rows.astype(np.float32).astype(bf))
        feats = jnp.asarray(np.stack([f for f, _, _ in self.ctrl_pairs])
                            .astype(np.float32).astype(bf))
        vids = np.concatenate([v for _, v, _ in self.ctrl_pairs])
        lens = [len(v) for _, v, _ in self.ctrl_pairs]
        rid = np.repeat(np.arange(len(lens)), lens)
        score = jax.jit(lambda F, R, r, v: jnp.sum(
            F[r].astype(jnp.float32) * R[v].astype(jnp.float32), axis=-1))
        chunk = 1 << 15                 # one program, bounded memory
        n = len(vids)
        pad = -n % chunk
        vids = np.concatenate([vids, np.zeros(pad, vids.dtype)])
        rid = np.concatenate([rid, np.zeros(pad, rid.dtype)])
        s = np.concatenate([
            np.asarray(score(feats, rows, rid[i:i + chunk],
                             vids[i:i + chunk]), np.float64)
            for i in range(0, len(vids), chunk)])[:n]
        gap, pos = 0.0, 0
        for (_, _, best), k in zip(self.ctrl_pairs, lens):
            gap = max(gap, abs(float(s[pos:pos + k].max()) - best))
            pos += k
        return gap
