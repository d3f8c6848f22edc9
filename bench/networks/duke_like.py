"""The 8-camera Duke-like campus (DukeMTMC as set up in ReXCam §8.1).

Transition structure calibrated to the paper's Fig. 4 (about 1.9 of 7 peers
receive >= 5% of a camera's outbound traffic; c7 -> c6 above 50% but the
reverse below 25%; c5 correlated with c2/c6 but not the nearer c7/c8),
travel times around 44.2 s pooled, a modest per-hop exit probability and
entries concentrated at the campus gates.  The topology is the deployment:
it is fixed, and a run's seed only relabels its cameras.
"""
from __future__ import annotations

import numpy as np

from harness.world import CameraNetwork


def build() -> CameraNetwork:
    C = 8
    T = np.array([
        #  c1     c2     c3     c4     c5     c6     c7     c8    exit
        [0.000, 0.510, 0.010, 0.005, 0.005, 0.005, 0.005, 0.160, 0.300],
        [0.350, 0.000, 0.330, 0.010, 0.010, 0.005, 0.005, 0.005, 0.285],
        [0.010, 0.360, 0.000, 0.280, 0.010, 0.005, 0.005, 0.005, 0.325],
        [0.005, 0.010, 0.330, 0.000, 0.300, 0.010, 0.005, 0.005, 0.335],
        [0.005, 0.300, 0.010, 0.015, 0.000, 0.330, 0.005, 0.005, 0.330],
        [0.005, 0.010, 0.005, 0.010, 0.270, 0.000, 0.210, 0.015, 0.475],
        [0.005, 0.005, 0.010, 0.005, 0.010, 0.560, 0.000, 0.085, 0.320],
        [0.270, 0.010, 0.010, 0.005, 0.010, 0.015, 0.160, 0.000, 0.520],
    ])
    exit_p = 0.12
    T[:, :C] *= (1.0 - exit_p) / T[:, :C].sum(1, keepdims=True)
    T[:, C] = exit_p
    rng = np.random.default_rng(7)
    mean = np.clip(rng.normal(44.2, 8.0, (C, C)), 20.0, 75.0)
    std = np.clip(rng.normal(6.5, 1.5, (C, C)), 3.0, 10.0)
    entry = np.array([0.42, 0.06, 0.04, 0.03, 0.05, 0.08, 0.06, 0.26])
    entry = entry / entry.sum()
    geo = np.zeros((C, C), bool)
    for a, b in [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (5, 6), (6, 7),
                 (7, 0), (1, 4), (4, 6), (4, 7), (1, 7), (5, 7)]:
        geo[a, b] = geo[b, a] = True
    return CameraNetwork(C, T, mean, std, entry, dwell_mean=12.0,
                         geo_adjacent=geo)
