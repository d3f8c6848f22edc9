"""The one query-traffic generator.  A mix is a data file,
``bench/traffic/<mix>.json``, with these keys:

  rate_scale          the configuration's arrival rate
                      (``queries.arrivals_per_tick``: the rate Little's law
                      gives for its target population, target / mean
                      lifetime of a live query in ticks) times this; queries
                      that start behind the live edge and catch up live
                      shorter, so a replay mix scales the rate up to hold
                      the same population
  ramp_factor         the rate is this many times higher until the live
                      population first reaches its target (the warm-up)
  anchor_lag_s        [lo, hi]: a query arriving at tick t is anchored on a
                      sighting whose last visible step is t - lag, lag drawn
                      uniformly from [lo, hi]; [1, 1] is a sighting that has
                      just left the frame at the live edge
  replay_speed        catch-up content steps per tick for a query behind
                      the live edge (1 = real time, 4 = the "ff" mode)
  warmup_ticks        [min, max] ticks served from the first arrival before
                      the measured window: at least min, then until the
                      live population reaches the configuration's target,
                      at most max

Arrivals are open-loop in video time once the target is reached: a query
is due whenever the accumulated rate passes a whole query, whatever the
engine does.  The lags and the sightings they anchor on belong to the
deployment's recording (``world.RECORDING``), so every run asks the same
queries; the run's seed only relabels the cameras they are seen on and
reorders their feature coordinates (``world.build``).
"""
from __future__ import annotations

import json
import os

import numpy as np

from harness.world import RECORDING, seed_rng

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(mix: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{mix}.json")) as f:
        return json.load(f)


class Arrivals:
    """The queries due at each tick, in order; a query's id is its index."""

    def __init__(self, world: dict, cfg: dict, mix: dict):
        self.rate = (float(cfg["queries"]["arrivals_per_tick"])
                     * float(mix.get("rate_scale", 1.0)))
        self.ramp = float(mix.get("ramp_factor", 1.0))
        self.lo, self.hi = (int(x) for x in mix["anchor_lag_s"])
        self.first = world["t0"] + self.hi
        self.rng = seed_rng(RECORDING, 10)
        t_out = world["stream"].t_out
        self.order = np.argsort(t_out, kind="stable")
        self.t_sorted = t_out[self.order]
        self.acc = 0.0

    def anchor(self, tick: int) -> int:
        """A sighting that left the frame ``lag`` steps before ``tick`` (or
        the latest earlier step that has one)."""
        lag = int(self.rng.integers(self.lo, self.hi + 1))
        hi_i = int(np.searchsorted(self.t_sorted, tick - lag, side="right"))
        if hi_i == 0:
            raise ValueError(f"no sighting ends at or before {tick - lag}")
        lo_i = int(np.searchsorted(self.t_sorted, self.t_sorted[hi_i - 1],
                                   side="left"))
        return int(self.order[lo_i + self.rng.integers(0, hi_i - lo_i)])

    def due(self, tick: int, ramping: bool) -> list[int]:
        """Anchor visit ids of the queries arriving at ``tick``."""
        if tick < self.first:
            return []
        self.acc += self.rate * (self.ramp if ramping else 1.0)
        out = []
        while self.acc >= 1.0:
            self.acc -= 1.0
            out.append(self.anchor(tick))
        return out
