"""The four-chip cell's path on four virtual CPU devices: the fleet served
through the harness is correct, and leaving out the exchange between
chips (every shard but the first never returns its rows' results) makes
``correct`` false.  Runs in a child process, which can fake the devices."""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

CHILD = r'''
import json, sys
sys.path.insert(0, sys.argv[1])
import tiny
import jax, jax.numpy as jnp
assert len(jax.devices()) == 4, jax.devices()
fault = sys.argv[2] == "fault"
if fault:
    from repro.runtime import fleet
    real = fleet.make_sharded_step_fns

    def without_exchange(mesh, *a, **k):
        fns = list(real(mesh, *a, **k))
        n = mesh.devices.size

        def first_shard_only(rank):
            def call(windows, state, *args):
                nxt, matched, mc, me, tv, ti, tc, tf = rank(windows, state,
                                                            *args)
                rows = matched.shape[0]
                keep = jnp.arange(rows) < rows // n
                nxt = jax.tree.map(lambda x, y: jnp.where(keep, x, y), nxt,
                                   state)
                drop = lambda x, v: jnp.where(keep.reshape(
                    (-1,) + (1,) * (x.ndim - 1)), x, v)
                return (nxt, drop(matched, False), drop(mc, 0), drop(me, 0),
                        drop(tv, -1e30), drop(ti, -1), drop(tc, -1),
                        drop(tf, -1))
            return call

        fns[2] = first_shard_only(fns[2])
        fns[5] = first_shard_only(fns[5])
        return tuple(fns)

    fleet.make_sharded_step_fns = without_exchange
res, printed = tiny.run(seed=21, seconds=1.0,
                        cfg=tiny.config(tile_grid=8), chips=4)
print(json.dumps(dict(correct=res["correct"], checks=res["checks"])))
'''


def _child(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD,
                        os.path.dirname(os.path.abspath(__file__)), mode],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=tiny.CHECKOUT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_fleet_is_correct_on_four_devices():
    got = _child("sound")
    assert got["correct"], got


def test_fleet_without_exchange_is_not_correct():
    got = _child("fault")
    assert not got["correct"], got
    assert got["checks"]["mismatches"]["value"] > 0
