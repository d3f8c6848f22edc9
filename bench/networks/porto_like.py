"""The 130-camera simulated city of ReXCam §8.1 (Porto taxi trajectories).

Cameras sit at the intersections of a 13 x 10 road grid; from each one,
taxis continue to a grid neighbour with skewed main-road weights, leak to a
node two blocks away, or end their trip.  Hops take 30-55 s per block.  The
topology is the deployment: it is fixed (its own seed, 3), and a run's
seed only relabels its cameras.
"""
from __future__ import annotations

import numpy as np

from harness.world import CameraNetwork


def build(n_cams: int = 130, rows: int = 13, cols: int = 10,
          seed: int = 3) -> CameraNetwork:
    rng = np.random.default_rng(seed)
    coords = np.array([(r, c) for r in range(rows)
                       for c in range(cols)][:n_cams])
    C = n_cams
    T = np.zeros((C, C + 1))
    dist = np.abs(coords[:, None] - coords[None]).sum(-1)
    for i in range(C):
        nbrs = np.where(dist[i] == 1)[0]
        if len(nbrs) == 0:
            T[i, C] = 1.0
            continue
        w = rng.dirichlet(np.full(len(nbrs), 0.6)) * 0.75
        far = np.where(dist[i] == 2)[0]
        fw = np.zeros(0)
        if len(far):
            fw = rng.dirichlet(np.full(len(far), 0.4)) * 0.10
        T[i, nbrs] = w
        if len(far):
            T[i, far] = fw
        T[i, C] = 1.0 - w.sum() - fw.sum()
    base = rng.uniform(30.0, 55.0, (C, C))
    mean = base * np.maximum(dist, 1)
    std = np.clip(mean * 0.18, 2.0, 25.0)
    entry = rng.dirichlet(np.full(C, 2.0))
    geo = dist <= 4
    np.fill_diagonal(geo, False)
    return CameraNetwork(C, T, mean, std, entry, dwell_mean=6.0,
                         geo_adjacent=geo)
