"""Compile the serving path's Mosaic kernels for a described TPU v5e.

Nothing here runs on a chip: each case lowers and compiles against the
v5e:2x2 topology that the installed TPU compiler can describe without one
attached, and asserts the compiled program holds the Pallas kernel
(``tpu_custom_call``).  That catches what interpret mode cannot: primitives
Mosaic does not lower, blocks that break the (8, 128) tiling rule, and
kernels that overflow scoped VMEM — at the widths the deployments serve:
the 8-camera Duke-like campus and the 130-camera city, each at Q=256 live
queries over a 4,096-row round gallery of 64-d embeddings.

The topology is described inside a module fixture (never at import), so
every pytest worker collects the same cases and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

Q, G, D = 256, 4096, 64

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for this file
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the engine's kernel wrappers onto the compiled Mosaic path:
    here ``jax.default_backend()`` is the CPU, which would pick interpret
    mode.  Traces cached under interpret mode are dropped first."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not compiled in"
    return text


@pytest.mark.parametrize("C,k", [(8, 1), (130, 3)], ids=["duke8", "city130"])
def test_reid_topk_segments_compiles(one_chip, C, k):
    from repro.kernels import ops
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    compiled = ops.reid_topk_segments.lower(
        s((Q, D), jnp.float32), s((Q,), jnp.int32), s((Q, C), jnp.bool_),
        s((G, D), jnp.float32), s((G,), jnp.int32), s((G,), jnp.int32), k,
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("C,T", [(8, 8), (130, 8)], ids=["duke8", "city130"])
def test_reid_topk_tiles_compiles(one_chip, C, T):
    """At 130 cameras and T=8 the fused-cell axis is 8,320 wide: the kernel
    must still fit the 16 MiB scoped-VMEM default."""
    from repro.kernels import ops
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    compiled = ops.reid_topk_tiles.lower(
        s((Q, D), jnp.float32), s((Q,), jnp.int32),
        s((Q, C * T * T), jnp.bool_), s((G, D), jnp.float32),
        s((G,), jnp.int32), s((G,), jnp.int32), 1, interpret=False).compile()
    _assert_kernel(compiled)


def _step_args(C, state_sh, rep_sh):
    """Abstract (windows, state, q_feat, q_seg, mask, gallery, gal_cam,
    gal_frame, gal_seg) for one consolidated Duke-8 round."""
    from repro.analysis.registry import _tiny_model
    from repro.core.policy import PhaseState, SearchPolicy, phase_windows

    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    windows = jax.tree.map(lambda a: _spec(a.shape, a.dtype, rep_sh),
                           phase_windows(_tiny_model(C=C, NB=256), policy))
    i32 = lambda sh, n: _spec((n,), jnp.int32, sh)  # noqa: E731
    state = PhaseState(f_q=i32(state_sh, Q), c_q=i32(state_sh, Q),
                       f_curr=i32(state_sh, Q), phase=i32(state_sh, Q),
                       live_f=_spec((Q,), jnp.float32, state_sh),
                       done=_spec((Q,), jnp.bool_, state_sh))
    return policy, (windows, state, _spec((Q, D), jnp.float32, state_sh),
                    i32(state_sh, Q), _spec((Q, C), jnp.bool_, state_sh),
                    _spec((G, D), jnp.float32, rep_sh), i32(rep_sh, G),
                    i32(rep_sh, G), i32(rep_sh, G))


def test_rank_advance_seg_step_compiles(one_chip, compiled_kernels):
    """The engine's whole consolidated round step (rank + phase machine)
    on one chip, as ``ServingEngine`` dispatches it."""
    from repro.runtime.engine import _rank_advance_seg_jit
    policy, args = _step_args(8, one_chip, one_chip)
    compiled = _rank_advance_seg_jit.lower(policy, *args, k=1).compile()
    _assert_kernel(compiled)


def test_fleet_rank_advance_seg_compiles_on_four_chips(topo,
                                                       compiled_kernels):
    """The fleet's shard_map step over a 4-chip data axis: query rows shard,
    the round gallery replicates, and each chip ranks its own rows — the
    kernel is compiled in and no collective is."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.cluster import ElasticMesh
    from repro.runtime.fleet import make_sharded_step_fns

    mesh = ElasticMesh(model_parallel=1).make_mesh(topo.devices)
    assert mesh.shape["data"] == 4
    policy, args = _step_args(8, NamedSharding(mesh, P("data")),
                              NamedSharding(mesh, P()))
    f_rank_seg = make_sharded_step_fns(mesh, policy, topk=1, n_cams=8)[2]
    text = _assert_kernel(f_rank_seg.lower(*args).compile())
    assert not [c for c in _COLLECTIVES if c in text]


def _admit_tiles_args(state_sh, rep_sh, C=130, T=8, n=512):
    """Abstract (model, state, geo_adj, tile_q, q_seg) for one city130_t8
    tile round: 512 batch rows over 130 cameras' 8 x 8 tiles."""
    import dataclasses
    import numpy as np
    from repro.analysis.registry import _tiny_model
    from repro.core.policy import PhaseState, SearchPolicy

    policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02)
    model = dataclasses.replace(
        _tiny_model(C=C, NB=256), tile_admit=np.ones((C, C, T * T), bool),
        tile_grid=T, tile_learned=True)
    model = jax.tree.map(
        lambda a: _spec(np.shape(a), jnp.asarray(a).dtype, rep_sh), model)
    i32 = lambda sh: _spec((n,), jnp.int32, sh)  # noqa: E731
    state = PhaseState(f_q=i32(state_sh), c_q=i32(state_sh),
                       f_curr=i32(state_sh), phase=i32(state_sh),
                       live_f=_spec((n,), jnp.float32, state_sh),
                       done=_spec((n,), jnp.bool_, state_sh))
    return policy, (model, state, _spec((C, C), jnp.bool_, rep_sh),
                    i32(state_sh), i32(state_sh))


def test_admit_tiles_step_compiles(one_chip):
    """The engine's tile admit program at city size on one chip: both
    masks plus the round's tile counts from the segment one-hot product."""
    from repro.runtime.engine import _admit_tiles_jit
    policy, (model, *args) = _admit_tiles_args(one_chip, one_chip)
    compiled = _admit_tiles_jit.lower(model, policy, *args).compile()
    assert compiled.as_text()


def test_fleet_admit_tiles_compiles_on_four_chips(topo):
    """The fleet's tile admit over a 4-chip data axis: each chip gathers
    the fused mask and segment ids to count the round's tiles whole."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.runtime.cluster import ElasticMesh
    from repro.runtime.fleet import make_sharded_step_fns

    mesh = ElasticMesh(model_parallel=1).make_mesh(topo.devices)
    policy, args = _admit_tiles_args(NamedSharding(mesh, P("data")),
                                     NamedSharding(mesh, P()))
    f_admit_tiles = make_sharded_step_fns(mesh, policy, topk=1,
                                          n_cams=130)[4]
    text = f_admit_tiles.lower(*args).compile().as_text()
    assert "all-gather" in text
