"""The benchmark's own data generators: camera networks, trajectories,
per-step detections, re-id features.

A copy of the simulator the program ships (``repro.core.simulate``; the
features follow ``repro.core.features``'s construction in float32), kept
here so that no change to the program can move the yardstick.  The draws
follow the same order as the program's simulator; ``rng.choice(n, p=...)``
is replaced by the search it performs internally (cumulative sum, one
uniform, ``searchsorted(side="right")``), which draws the same numbers
without its per-call overhead, and the dense detection table is filled in
one vectorized pass in the same slot order.

One simulation step is one second of video.  A deployment is one recording:
its trajectories, detections, re-id features and the sightings that become
queries are drawn once, from ``RECORDING`` and the configuration, so every
run serves the same amount of work.  The run's seed draws how that
recording is presented to the program: a relabeling of the cameras and a
signed permutation of the feature coordinates.  Both leave every distance,
every admission and every match of the recording as it is (a signed
permutation is an orthogonal map), while the camera ids, the gallery's
camera-major order, the kernels' summation order and every feature value
the program reads change with the seed.  A world is everything a run
serves:

  net       the deployment's camera network, cameras relabeled
  history   the visits the model is profiled on
  stream    the live visits the engine is fed, tick by tick
  gal       (C, H, K) visit ids detected per camera and step, -1 empty
  feats     (V, D) float32 unit re-id features, one per stream visit
  tiles     (V,) int32 sub-frame tile of each stream visit (tile configs)
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

# entry portals belong to a directed camera pair, not to a run: every world
# over one network shares them (the program's simulator uses the same salt)
_PORTAL_SALT = 0x7E11E5
# the seed every configuration's recording is drawn from
RECORDING = 0x5EC0D
_PORTAL_JITTER = 0.03
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class CameraNetwork:
    n_cams: int
    trans: np.ndarray         # (C, C+1) next-camera probabilities, last = exit
    travel_mean: np.ndarray   # (C, C) seconds
    travel_std: np.ndarray    # (C, C) seconds
    entry: np.ndarray         # (C,) entry-camera distribution
    dwell_mean: float         # mean seconds an entity stays in one view
    geo_adjacent: np.ndarray  # (C, C) bool


@dataclasses.dataclass
class Visits:
    """One row per (entity, camera) visit; the attribute names are those
    ``repro.api.profile`` reads."""
    ent: np.ndarray
    cam: np.ndarray
    t_in: np.ndarray
    t_out: np.ndarray         # last visible step, inclusive
    horizon: int
    n_cams: int
    tile_xy: np.ndarray       # (V, 2) float32 normalized position

    def __len__(self):
        return len(self.ent)


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator keyed by the run's seed and a purpose: any whole number
    (negative or past 64 bits) maps to one 64-bit word."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *salt])


def tile_index(tile_xy: np.ndarray, tile_grid: int) -> np.ndarray:
    """Flat tile id ``floor(y*T)*T + floor(x*T)`` of normalized positions."""
    xy = np.clip(np.asarray(tile_xy, np.float64), 0.0, np.nextafter(1.0, 0.0))
    tx = np.floor(xy[..., 0] * tile_grid).astype(np.int32)
    ty = np.floor(xy[..., 1] * tile_grid).astype(np.int32)
    return ty * np.int32(tile_grid) + tx


def _portal_center(src: int, dst: int) -> np.ndarray:
    return np.random.default_rng([src, dst, _PORTAL_SALT]).uniform(0.1, 0.9, 2)


def _draw(cdf: np.ndarray, u: float) -> int:
    return int(cdf.searchsorted(u, side="right"))


def simulate(net: CameraNetwork, n_entities: int, horizon: int,
             rng: np.random.Generator,
             rng_xy: np.random.Generator) -> Visits:
    """Entity trajectories through ``net``: enter uniformly over the first
    95% of the horizon, dwell ~Exp(dwell_mean) (at least 2 s) in a view,
    hop by the transition matrix after a normal travel time, until they
    exit.  Entries appear anywhere in the frame, hand-offs near the
    directed pair's portal."""
    C = net.n_cams
    entry_cdf = np.cumsum(net.entry)
    entry_cdf /= entry_cdf[-1]
    trans_cdf = np.cumsum(net.trans, axis=1)
    trans_cdf /= trans_cdf[:, -1:]
    portals = {}
    ents, cams, tins, touts, xys = [], [], [], [], []
    enter_times = rng.uniform(0, horizon * 0.95, n_entities).astype(np.int64)
    for e in range(n_entities):
        t = int(enter_times[e])
        c = _draw(entry_cdf, rng.random())
        xy = rng_xy.uniform(0.0, 1.0, 2)
        while t < horizon:
            dwell = max(2, int(rng.exponential(net.dwell_mean)))
            t_out = min(t + dwell, horizon - 1)
            ents.append(e)
            cams.append(c)
            tins.append(t)
            touts.append(t_out)
            xys.append(xy)
            if t_out >= horizon - 1:
                break
            nxt = _draw(trans_cdf[c], rng.random())
            if nxt == C:
                break
            travel = max(1, int(rng.normal(net.travel_mean[c, nxt],
                                           net.travel_std[c, nxt])))
            if (c, nxt) not in portals:
                portals[c, nxt] = _portal_center(c, nxt)
            xy = np.clip(portals[c, nxt]
                         + rng_xy.normal(0.0, _PORTAL_JITTER, 2),
                         0.0, np.nextafter(1.0, 0.0))
            t = t_out + travel
            c = nxt
    return Visits(np.asarray(ents, np.int64), np.asarray(cams, np.int64),
                  np.asarray(tins, np.int64), np.asarray(touts, np.int64),
                  horizon, C,
                  np.asarray(xys, np.float32).reshape(len(ents), 2))


def detections(visits: Visits, max_slots: int) -> np.ndarray:
    """(C, H, K) int32 visit ids visible per camera and step, -1 empty:
    slots fill in visit order, and a frame holds at most ``max_slots``."""
    C, H, K = visits.n_cams, visits.horizon, max_slots
    lens = (visits.t_out - visits.t_in + 1).astype(np.int64)
    vid = np.repeat(np.arange(len(visits)), lens)
    start = np.repeat(np.cumsum(lens) - lens, lens)
    t = np.repeat(visits.t_in, lens) + (np.arange(len(vid)) - start)
    cam = visits.cam[vid]
    key = cam * H + t
    order = np.argsort(key, kind="stable")     # visit order within a frame
    key_s = key[order]
    first = np.r_[0, np.flatnonzero(np.diff(key_s)) + 1]
    run_start = np.repeat(first, np.diff(np.r_[first, len(key_s)]))
    slot = np.arange(len(key_s)) - run_start
    keep = slot < K
    gal = np.full((C, H, K), -1, np.int32)
    o = order[keep]
    gal[cam[o], t[o], slot[keep]] = vid[o]
    return gal


def features(visits: Visits, n_entities: int, dim: int, n_clusters: int,
             cluster_delta: float, noise_sigma: float,
             rng: np.random.Generator) -> np.ndarray:
    """Lookalike-clustered entity embeddings with a fixed perturbation per
    visit (viewpoint, lighting); (V, dim) float32, unit rows.  The program's
    construction, drawn in float32 and normalized in place: at a re-id
    embedding's width the per-visit noise is most of a run's set-up."""
    def unit(x):
        x /= np.linalg.norm(x, axis=-1, keepdims=True)
        return x

    f32 = np.float32
    centers = unit(rng.standard_normal((n_clusters, dim), dtype=f32))
    assign = rng.integers(0, n_clusters, n_entities)
    indiv = unit(rng.standard_normal((n_entities, dim), dtype=f32))
    emb = unit(centers[assign] + f32(cluster_delta) * indiv)
    out = unit(rng.standard_normal((len(visits), dim), dtype=f32))
    out *= f32(noise_sigma)
    out += emb[visits.ent]
    return unit(out)


def network(spec: dict) -> CameraNetwork:
    """The network a configuration names: ``bench/networks/<builder>.py``."""
    from harness.layers import load_file

    path = os.path.join(BENCH, "networks", f"{spec['builder']}.py")
    return load_file(path, f"bench_network_{spec['builder']}").build(
        **spec.get("params", {}))


def relabel(net: CameraNetwork, perm: np.ndarray) -> CameraNetwork:
    """``net`` with camera c renamed ``perm[c]``."""
    inv = np.argsort(perm)
    C = net.n_cams
    return dataclasses.replace(
        net, trans=net.trans[inv][:, np.r_[inv, C]],
        travel_mean=net.travel_mean[np.ix_(inv, inv)],
        travel_std=net.travel_std[np.ix_(inv, inv)],
        entry=net.entry[inv], geo_adjacent=net.geo_adjacent[np.ix_(inv, inv)])


def build(cfg: dict, seed: int) -> dict:
    """The world of configuration ``cfg`` as run ``seed`` presents it."""
    if int(cfg.get("frame_rate_fps", 1)) != 1:
        raise ValueError("the simulator steps one second of video at a time")
    net = network(cfg["network"])
    st, pr, ft = cfg["stream"], cfg["profile"], cfg["features"]
    H = int(st["horizon_s"])
    n_ent = int(round(st["identities_per_s"] * H))
    stream = simulate(net, n_ent, H, seed_rng(RECORDING, 1),
                      seed_rng(RECORDING, 2))
    if "history" in pr:
        h = pr["history"]
        history = simulate(net, int(h["identities"]), int(h["horizon_s"]),
                           seed_rng(RECORDING, 3), seed_rng(RECORDING, 4))
    else:
        history = stream
    D = int(ft["dim"])
    recorded = features(stream, n_ent, D, int(ft["n_clusters"]),
                        float(ft["cluster_delta"]), float(ft["noise_sigma"]),
                        seed_rng(RECORDING, 5))
    gal = detections(stream, int(st["detections_per_step"]))
    T = int(cfg["serve"].get("tile_grid", 0))

    # the run's presentation of the recording
    cams = seed_rng(seed, 6).permutation(net.n_cams)
    rng = seed_rng(seed, 7)
    coords = rng.permutation(D)
    signs = np.where(rng.random(D) < 0.5, -1.0, 1.0).astype(np.float32)
    feats = recorded[:, coords]
    del recorded
    feats *= signs
    renamed = {id(v): dataclasses.replace(v, cam=cams[v.cam])
               for v in (stream, history)}
    return dict(
        net=relabel(net, cams), stream=renamed[id(stream)],
        history=renamed[id(history)],
        profile_until=pr.get("until_s"),
        gal=gal[np.argsort(cams)],
        feats=feats,
        tiles=tile_index(stream.tile_xy, T) if T else None,
        t0=int(st["serve_from_s"]), horizon=H)
