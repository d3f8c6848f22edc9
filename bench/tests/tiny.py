"""A small world for the benchmark's CPU tests: the duke-like campus over
a short stream, few queries, interpret-mode kernels.  ``run`` drives one
whole run of the harness except the look for a chip."""
from __future__ import annotations

import copy
import io
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny",
    "network": {"builder": "duke_like"},
    "stream": {"identities_per_s": 0.53, "horizon_s": 3000,
               "detections_per_step": 24, "serve_from_s": 400},
    "profile": {"until_s": 400},
    "features": {"dim": 64, "n_clusters": 150, "cluster_delta": 0.55,
                 "noise_sigma": 0.45},
    "serve": {"scheme": "rexcam", "s_thresh": 0.05, "t_thresh": 0.02,
              "exit_t": 240, "match_thresh": 0.28, "feat_alpha": 0.25,
              "relax_factor": 10.0, "self_window": 6, "retention": 600,
              "n_bins": 256, "bin_width": 1, "tile_grid": 0,
              "max_batch": 256, "topk": 1},
    "queries": {"target": 6, "arrivals_per_tick": 0.05, "batch_cap": 16,
                "gallery_rows_cap": 512},
    "check": {"query_share": 0.5, "score_gap_limit": 1e-5},
}

LIVE = {"rate_scale": 1.0, "ramp_factor": 2.0,
        "anchor_lag_s": [1, 1], "replay_speed": 1.0,
        "warmup_ticks": [20, 60]}
REPLAY = {"rate_scale": 1.0, "ramp_factor": 2.0,
          "anchor_lag_s": [20, 60], "replay_speed": 4.0,
          "warmup_ticks": [20, 60]}


def config(tile_grid: int = 0) -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg["serve"]["tile_grid"] = tile_grid
    return cfg


def run(seed: int = 3, seconds: float = 1.5, cfg=None, mix=None,
        trace=False, control=False, chips=1):
    """One harness run on the CPU; returns (result, what it printed)."""
    from harness import drive

    out = io.StringIO()
    cell = drive.Cell("tiny.live", "tiny", "live", chips)
    res = drive.run(cell, seed, seconds, trace, control=control,
                    require_tpu=False, out=out,
                    cfg=config() if cfg is None else cfg,
                    mix=LIVE if mix is None else mix)
    return res, out.getvalue()
