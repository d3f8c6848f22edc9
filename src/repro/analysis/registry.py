"""The registered jit entry points of the serving loop.

One place that answers "what compiles?": every jitted callable the engines
dispatch per round, each paired with a builder for tiny, fully-deterministic
example arguments.  The jaxpr auditor traces each entry through its REAL
jit wrapper (statics and all) and walks the resulting ClosedJaxpr; the
RecompileGuard snapshots the same wrappers' compile caches.

Example args are deliberately minute (C=4 cameras, Q=8 queries, G=24
gallery rows) — tracing is abstract, so sizes only shape the jaxpr, and the
audit must stay cheap enough to run as a blocking CI step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

__all__ = ["JitEntry", "jit_entry_fns", "entries"]


@dataclasses.dataclass(frozen=True)
class JitEntry:
    name: str
    fn: Any                                   # jitted: .trace/._cache_size
    example: Callable[[], tuple[tuple, dict]]  # -> (args, kwargs)


def _tiny_model(C: int = 4, NB: int = 8):
    from repro.core.profiler import build_model
    rng = np.random.default_rng(7)
    E, hops = 6, 5
    ent = np.repeat(np.arange(E), hops)
    cam = rng.integers(0, C, E * hops)
    t_in = np.concatenate([np.sort(rng.integers(0, 64, hops))
                           for _ in range(E)])
    t_out = t_in + rng.integers(1, 4, E * hops)
    return build_model(ent, cam, t_in, t_out, C, n_bins=NB)


def _example_world(Q: int = 8, G: int = 24, D: int = 16, C: int = 4):
    """Deterministic batched example state shared by every entry builder."""
    import jax.numpy as jnp
    from repro.core.policy import PhaseState, SearchPolicy, phase_windows

    model = _tiny_model(C=C)
    policy = SearchPolicy()
    windows = phase_windows(model, policy)
    rng = np.random.default_rng(11)
    state = PhaseState(
        f_q=jnp.asarray(rng.integers(0, 8, Q), jnp.int32),
        c_q=jnp.asarray(rng.integers(0, C, Q), jnp.int32),
        f_curr=jnp.asarray(rng.integers(8, 16, Q), jnp.int32),
        phase=jnp.ones(Q, jnp.int32),
        live_f=jnp.full(Q, 16.0, jnp.float32),
        done=jnp.zeros(Q, bool),
    )
    q_feat = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, (Q, C)), bool)
    gal = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    gal_cam = jnp.asarray(rng.integers(0, C, G), jnp.int32)
    gal_frame = jnp.asarray(np.repeat(state.f_curr, G // Q + 1)[:G], jnp.int32)
    # the consolidation plane's round-scoped relabeling: distinct content
    # frames -> compact segment ids (exactly what RoundPlan builds)
    segs = {f: s for s, f in
            enumerate(sorted({int(x) for x in np.asarray(state.f_curr)}))}
    q_seg = jnp.asarray([segs[int(x)] for x in np.asarray(state.f_curr)],
                        jnp.int32)
    gal_seg = jnp.asarray([segs[int(x)] for x in np.asarray(gal_frame)],
                          jnp.int32)
    # the sub-frame spatial admission plane at a tiny T=2 grid: a tile-
    # carrying model clone, the fused (camera, tile) admission, and
    # per-row fused cell tags (one unlabeled row exercises the -1 path)
    import dataclasses as _dc
    T = 2
    TT = T * T
    model_tiles = _dc.replace(
        model, tile_admit=jnp.asarray(rng.integers(0, 2, (C, C, TT)), bool),
        tile_grid=T, tile_learned=True)
    mask_ct = jnp.asarray(rng.integers(0, 2, (Q, C * TT)), bool)
    # per-query last-matched tiles for the learned self-follow column; one
    # -1 row exercises the no-match-yet (admit-everything) path
    tile_q = jnp.asarray(
        np.where(np.arange(Q) % 3 == 0, -1, rng.integers(0, TT, Q)),
        jnp.int32)
    gal_ct = jnp.asarray(
        np.where(np.arange(G) == G - 1, -1,
                 np.asarray(gal_cam) * TT + rng.integers(0, TT, G)),
        jnp.int32)
    return dict(model=model, policy=policy, windows=windows, state=state,
                q_feat=q_feat, mask=mask, gal=gal, gal_cam=gal_cam,
                gal_frame=gal_frame, q_seg=q_seg, gal_seg=gal_seg,
                model_tiles=model_tiles, mask_ct=mask_ct, gal_ct=gal_ct,
                tile_q=tile_q, n_cams=C)


def jit_entry_fns() -> dict[str, Any]:
    """name -> module-level jitted callable, for RecompileGuard snapshots.
    (The fleet's per-mesh shard_map jits are added per engine — see
    ``RecompileGuard.for_engine``.)"""
    from repro.kernels import ops as kernel_ops
    from repro.runtime import engine as _engine
    return {
        "policy.admit": _engine._admit_jit,
        "policy.admit_tiles": _engine._admit_tiles_jit,
        "policy.advance": _engine._advance_round_jit,
        "rank_round": _engine.rank_round,
        "rank_round_seg": _engine.rank_round_seg,
        "rank_round_tiles": _engine.rank_round_tiles,
        "rank_advance_round": _engine._rank_advance_jit,
        "rank_advance_round_seg": _engine._rank_advance_seg_jit,
        "rank_advance_round_tiles": _engine._rank_advance_tiles_jit,
        "reid_topk": kernel_ops.reid_topk,
        "reid_topk_masked": kernel_ops.reid_topk_masked,
        "reid_topk_segments": kernel_ops.reid_topk_segments,
        "reid_topk_tiles": kernel_ops.reid_topk_tiles,
    }


def entries(include_fleet: bool = True) -> list[JitEntry]:
    """Every registered jit entry with example args, for the jaxpr audit.

    ``include_fleet`` adds the shard_map step bodies on a 1-device mesh
    (tracing needs no fleet, just the mesh the jaxpr closes over)."""
    from repro.kernels import ops as kernel_ops
    from repro.runtime import engine as _engine

    w = _example_world()
    fns = jit_entry_fns()
    out = [
        JitEntry("policy.admit", fns["policy.admit"],
                 lambda: ((w["model"], w["policy"], w["state"], None), {})),
        JitEntry("policy.advance", fns["policy.advance"],
                 lambda: ((w["policy"], w["windows"], w["state"]), {})),
        JitEntry("rank_round", fns["rank_round"],
                 lambda: ((w["q_feat"], w["state"].f_curr, w["mask"],
                           w["gal"], w["gal_cam"], w["gal_frame"],
                           w["policy"].match_thresh, 2), {})),
        JitEntry("rank_round_seg", fns["rank_round_seg"],
                 lambda: ((w["q_feat"], w["q_seg"], w["mask"], w["gal"],
                           w["gal_cam"], w["gal_frame"], w["gal_seg"],
                           w["policy"].match_thresh, 2), {})),
        JitEntry("rank_advance_round", fns["rank_advance_round"],
                 lambda: ((w["policy"], w["windows"], w["state"], w["q_feat"],
                           w["mask"], w["gal"], w["gal_cam"], w["gal_frame"]),
                          dict(k=1))),
        JitEntry("rank_advance_round_seg", fns["rank_advance_round_seg"],
                 lambda: ((w["policy"], w["windows"], w["state"], w["q_feat"],
                           w["q_seg"], w["mask"], w["gal"], w["gal_cam"],
                           w["gal_frame"], w["gal_seg"]), dict(k=1))),
        JitEntry("reid_topk", fns["reid_topk"],
                 lambda: ((w["q_feat"], w["gal"], 2), dict(interpret=True))),
        JitEntry("reid_topk_masked", fns["reid_topk_masked"],
                 lambda: ((w["q_feat"], w["state"].f_curr, w["mask"],
                           w["gal"], w["gal_cam"], w["gal_frame"], 2),
                          dict(interpret=True))),
        JitEntry("reid_topk_segments", fns["reid_topk_segments"],
                 lambda: ((w["q_feat"], w["q_seg"], w["mask"], w["gal"],
                           w["gal_cam"], w["gal_seg"], 2),
                          dict(interpret=True))),
        JitEntry("policy.admit_tiles", fns["policy.admit_tiles"],
                 lambda: ((w["model_tiles"], w["policy"], w["state"], None,
                           w["tile_q"], w["q_seg"]), {})),
        JitEntry("rank_round_tiles", fns["rank_round_tiles"],
                 lambda: ((w["q_feat"], w["q_seg"], w["mask_ct"], w["gal"],
                           w["gal_ct"], w["gal_cam"], w["gal_frame"],
                           w["gal_seg"], w["policy"].match_thresh, 2,
                           w["n_cams"]), {})),
        JitEntry("rank_advance_round_tiles", fns["rank_advance_round_tiles"],
                 lambda: ((w["policy"], w["windows"], w["state"], w["q_feat"],
                           w["q_seg"], w["mask_ct"], w["gal"], w["gal_ct"],
                           w["gal_cam"], w["gal_frame"], w["gal_seg"]),
                          dict(k=1, n_cams=w["n_cams"]))),
        JitEntry("reid_topk_tiles", fns["reid_topk_tiles"],
                 lambda: ((w["q_feat"], w["q_seg"], w["mask_ct"], w["gal"],
                           w["gal_ct"], w["gal_seg"], 2),
                          dict(interpret=True))),
    ]
    if include_fleet:
        import jax
        from repro.runtime.cluster import ElasticMesh
        from repro.runtime.fleet import make_sharded_step_fns
        mesh = ElasticMesh(model_parallel=1).make_mesh([jax.devices()[0]])
        (f_admit, f_rank, f_rank_seg, f_advance, f_admit_tiles,
         f_rank_tiles) = make_sharded_step_fns(mesh, w["policy"], topk=1,
                                               n_cams=w["n_cams"])
        out += [
            JitEntry("fleet.admit@shard_map", f_admit,
                     lambda: ((w["model"], w["state"], None), {})),
            JitEntry("fleet.rank_advance@shard_map", f_rank,
                     lambda: ((w["windows"], w["state"], w["q_feat"],
                               w["mask"], w["gal"], w["gal_cam"],
                               w["gal_frame"]), {})),
            JitEntry("fleet.rank_advance_seg@shard_map", f_rank_seg,
                     lambda: ((w["windows"], w["state"], w["q_feat"],
                               w["q_seg"], w["mask"], w["gal"], w["gal_cam"],
                               w["gal_frame"], w["gal_seg"]), {})),
            JitEntry("fleet.advance@shard_map", f_advance,
                     lambda: ((w["windows"], w["state"]), {})),
            JitEntry("fleet.admit_tiles@shard_map", f_admit_tiles,
                     lambda: ((w["model_tiles"], w["state"], None,
                               w["tile_q"], w["q_seg"]), {})),
            JitEntry("fleet.rank_advance_tiles@shard_map", f_rank_tiles,
                     lambda: ((w["windows"], w["state"], w["q_feat"],
                               w["q_seg"], w["mask_ct"], w["gal"],
                               w["gal_ct"], w["gal_cam"], w["gal_frame"],
                               w["gal_seg"]), {})),
        ]
    return out
