"""The tile round's counts come from the device.

``policy.tile_counts`` computes ``admitted_tiles`` and ``unique_tiles``
inside the tile admit program, so the host reads two ints instead of the
(N, C*T*T) fused mask.  Here the counts are held to the host loop the
engine used to run over the mask read back (kept below as the oracle), on
the single engine and on the fleet over 2 and 4 virtual CPU devices; and
the host reads are held to what the round still needs: the fleet's
``sharded_readback_bytes`` and the engines' ``readback_bytes`` against the
camera path over the same rounds."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import tile_counts

TESTS = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.abspath(os.path.join(TESTS, "..", "src"))
DEVICES = 4


def host_tile_counts(mask_ct, q_seg):
    """Per segment, the union of its rows' admitted cells (numpy)."""
    unique = sum(int(np.any(mask_ct[q_seg == s], axis=0).sum())
                 for s in np.unique(q_seg[q_seg >= 0]))
    return int(mask_ct.sum()), unique


@pytest.mark.parametrize("n_seg", [1, 3, 40])
@pytest.mark.parametrize("seed", [0, 1])
def test_tile_counts_equal_numpy(seed, n_seg):
    """Random masks over 64 rows, the last 16 padding (segment -1, no
    admission), segment ids drawn from ``n_seg``, some of them unused."""
    rng = np.random.default_rng(seed)
    Q, cells = 64, 5 * 16
    q_seg = np.full(Q, -1, np.int32)
    q_seg[:48] = rng.integers(0, n_seg, 48)
    mask_ct = rng.random((Q, cells)) < 0.3
    mask_ct[48:] = False
    got = tuple(int(v) for v in tile_counts(jnp.asarray(mask_ct),
                                            jnp.asarray(q_seg)))
    assert got == host_tile_counts(mask_ct, q_seg)


def test_tile_counts_of_nothing_admitted():
    mask_ct = np.zeros((8, 32), bool)
    q_seg = np.array([0, 0, 1, 2, -1, -1, -1, -1], np.int32)
    got = tile_counts(jnp.asarray(mask_ct), jnp.asarray(q_seg))
    assert [int(v) for v in got] == [0, 0]


CHILD = r'''
import json, sys
import jax, numpy as np
import conftest
from repro import api as rexcam
from repro.core.policy import SearchPolicy
from repro.runtime.engine import ServingEngine

T = 4
TT = T * T
assert len(jax.devices()) == int(sys.argv[1]), jax.devices()
policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02, exit_t=60)
world = conftest.make_serving_world(seed=0, n_queries=8)
learned = rexcam.profile(world["vis"], time_limit=252, tile_grid=T)


def engine_loop(plan, mask_ct):
    """The round's tile counts as the engine counted them on the host:
    the whole mask's sum, and the per-(camera, frame) key union of the
    admitted tiles of every query that admitted the camera."""
    adm = int(mask_ct.sum())
    tiles_by_key = {}
    for i, q in enumerate(plan.qs):
        row = mask_ct[plan.slots[i]]
        for cam in plan.cams_by_q[i]:
            key = (int(cam), q.f_curr)
            seg = row[key[0] * TT:(key[0] + 1) * TT]
            if key in tiles_by_key:
                tiles_by_key[key] |= seg
            else:
                tiles_by_key[key] = seg.copy()
    return adm, sum(int(v.sum()) for v in tiles_by_key.values())


def drive(shards, tile_grid, model=None):
    rounds = []
    orig_plan, orig_body = ServingEngine._plan_round, ServingEngine._round_body

    def plan_round(self, qs):
        plan = orig_plan(self, qs)
        row = dict(segs=len({q.f_curr for q in qs}), n=plan.mask.shape[0])
        if plan.mask_ct_dev is not None:
            mask_ct = np.asarray(plan.mask_ct_dev)
            row.update(cells=mask_ct.shape[1],
                       device=[plan.admitted_tiles, plan.unique_tiles],
                       host=list(engine_loop(plan, mask_ct)))
        rounds.append(row)
        return plan

    def round_body(self, qs, stats, trace):
        before = self.readback_bytes
        orig_body(self, qs, stats, trace)
        rounds[-1]["read"] = self.readback_bytes - before

    ServingEngine._plan_round, ServingEngine._round_body = \
        plan_round, round_body
    try:
        eng, _, _ = conftest.drive_serving_trace(
            world, policy, shards=shards, tile_grid=tile_grid, model=model,
            churn_wave=30)
    finally:
        ServingEngine._plan_round, ServingEngine._round_body = \
            orig_plan, orig_body
    return dict(rounds=rounds, admitted_tiles=eng.admitted_tiles,
                unique_tiles=eng.unique_tiles,
                readback_bytes=eng.readback_bytes,
                sharded_readback_bytes=getattr(eng, "sharded_readback_bytes",
                                               None))


out = {}
for name, shards in (("single", None), ("fleet2", 2), ("fleet4", 4)):
    out[name] = dict(learned=drive(shards, T, learned))
for name, shards in (("single", None), ("fleet4", 4)):
    # the tile path over a tile-less model ranks the camera path's rounds
    out[name].update(all_tiles=drive(shards, T), camera=drive(shards, 0))
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}")
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, TESTS] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, "-c", CHILD, str(DEVICES)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["single", "fleet2", "fleet4"])
def test_device_tile_counts_equal_host_loop(served, engine):
    """Every tile round's device counts equal the host loop over the same
    round's mask, with learned masks and a late wave of replaying queries,
    so rounds hold several segments and queries share frames."""
    run = served[engine]["learned"]
    rounds = run["rounds"]
    for r in rounds:
        assert r["device"] == r["host"], r
    assert any(r["segs"] >= 2 and r["host"][1] < r["host"][0]
               for r in rounds), "no round held several segments and dedup"
    assert run["admitted_tiles"] == sum(r["host"][0] for r in rounds)
    assert run["unique_tiles"] == sum(r["host"][1] for r in rounds)


@pytest.mark.parametrize("engine", ["single", "fleet4"])
def test_tile_round_reads_no_fused_mask(served, engine):
    """A tile round copies less to the host than the (N, C*T*T) mask alone,
    and exactly the camera path's reads of the same rounds plus the two
    int32 counts."""
    runs = served[engine]
    for r in runs["learned"]["rounds"]:
        assert r["read"] < r["n"] * r["cells"], r
    tiles, camera = runs["all_tiles"], runs["camera"]
    assert len(tiles["rounds"]) == len(camera["rounds"])
    assert tiles["readback_bytes"] == \
        camera["readback_bytes"] + 8 * len(tiles["rounds"])


def test_fleet_sharded_readback_leaves_out_the_fused_mask(served):
    """The fleet charges a tile round's sharded read-back as the camera
    path's: the fused mask block is gone, and the replicated counts are
    the only other bytes the host reads."""
    tiles, camera = served["fleet4"]["all_tiles"], served["fleet4"]["camera"]
    assert tiles["sharded_readback_bytes"] == \
        camera["sharded_readback_bytes"] > 0
    assert camera["readback_bytes"] == camera["sharded_readback_bytes"]
    assert tiles["readback_bytes"] == \
        tiles["sharded_readback_bytes"] + 8 * len(tiles["rounds"])
