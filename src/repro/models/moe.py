"""Mixture-of-Experts FFN with expert-parallel (EP) dispatch.

Design (TPU-native, not a GShard one-hot-einsum port):
  * token-choice top-k routing (fp32 router),
  * sort-based capacity dispatch — tokens are scatter-packed into fixed
    ``(E, C)`` buffers via an argsort over expert ids (static shapes, no
    (T,E,C) one-hot tensors),
  * under a mesh, a ``shard_map`` over the ``data`` axis all-to-alls the
    packed buffers to the expert-owning devices (EP=|data|), runs the batched
    expert GEMMs with the hidden dim tensor-sharded over ``model`` (psum to
    combine), and all-to-alls results back,
  * without a mesh (smoke tests / examples) the identical dispatch math runs
    locally.

Dispatch is chunked over tokens (``moe_chunk``) so the packed buffers stay a
few hundred MB at the 1M-token production batch instead of multi-GB.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.models.config import ModelConfig
from repro.models.layers import Params, _dtype, _pdtype, dense_init
from repro.parallel.sharding import constrain, get_mesh_context

MOE_CHUNK = 8192          # tokens per dispatch chunk (per device)
MIN_CAPACITY = 4


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = _pdtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "wi": dense_init(ks[1], (e, d, f), d, dt),
        "wg": dense_init(ks[2], (e, d, f), d, dt),
        "wo": dense_init(ks[3], (e, f, d), f, dt),
    }
    ax = {
        "router": ("none", "none"),
        "wi": ("experts", "none", "expert_mlp"),
        "wg": ("experts", "none", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "none"),
    }
    return p, ax


def _route(tokens_f32, router_w, k: int):
    """tokens: (T, D) -> (probs (T,k), ids (T,k), aux_metrics)."""
    logits = tokens_f32 @ router_w                                  # (T, E)
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch): E * sum_e f_e * p_e
    f_e = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)
    zloss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_i, aux, zloss


def _dispatch_indices(ids: jax.Array, E: int, C: int):
    """ids: (T, k) expert assignments -> packed-buffer index per (t, j).

    Returns (dest (T*k,), valid (T*k,)) where dest in [0, E*C) addresses the
    packed (E, C) buffer, computed by a stable argsort over expert ids
    (slot = rank of the token within its expert).  Overflow beyond capacity C
    is dropped (valid=False), matching capacity-factor routing.
    """
    Tk = ids.size
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)                          # (Tk,)
    sorted_e = flat[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")  # (E,)
    slot = jnp.arange(Tk) - start[sorted_e]
    valid_sorted = slot < C
    dest_sorted = jnp.where(valid_sorted, sorted_e * C + jnp.minimum(slot, C - 1), E * C)
    inv = jnp.argsort(order, stable=True)
    return dest_sorted[inv], (dest_sorted != E * C)[inv]


def _expert_ffn(xb: jax.Array, wi, wg, wo, dt):
    """xb: (E_l, M, D); weights (E_l, D, F_l)/(E_l, F_l, D) -> (E_l, M, D)."""
    h = jnp.einsum("emd,edf->emf", xb.astype(dt), wg.astype(dt))
    u = jnp.einsum("emd,edf->emf", xb.astype(dt), wi.astype(dt))
    h = jax.nn.silu(h) * u
    return jnp.einsum("emf,efd->emd", h, wo.astype(dt))


def _moe_chunk_local(tokens, router_w, wi, wg, wo, cfg: ModelConfig, C: int):
    """Single-device dispatch + expert compute for one token chunk."""
    T, D = tokens.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    dt = _dtype(cfg)
    top_p, top_i, aux, zloss = _route(tokens.astype(jnp.float32), router_w, k)
    dest, valid = _dispatch_indices(top_i, E, C)
    src = jnp.repeat(tokens, k, axis=0)                             # (T*k, D)
    buf = jnp.zeros((E * C + 1, D), tokens.dtype).at[jnp.where(valid, dest, E * C)].set(src)
    xb = buf[:E * C].reshape(E, C, D)
    yb = _expert_ffn(xb, wi, wg, wo, dt).reshape(E * C, D)
    y = yb[dest] * valid[:, None]                                   # (T*k, D)
    y = y.reshape(T, k, D) * top_p[..., None].astype(y.dtype)
    return y.sum(1), aux, zloss


def _moe_chunk_ep(tokens, router_w, wi, wg, wo, cfg: ModelConfig, C: int,
                  data_axis: str, model_axis: str | None, n_data: int):
    """shard_map body: tokens (T_l, D) local; wi/wg/wo local expert shards.

    With ``model_axis=None`` the expert weights are full-F (pre-gathered) and
    no TP psum is emitted."""
    T, D = tokens.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    E_l = E // n_data
    dt = _dtype(cfg)
    top_p, top_i, aux, zloss = _route(tokens.astype(jnp.float32), router_w, k)
    dest, valid = _dispatch_indices(top_i, E, C)
    src = jnp.repeat(tokens, k, axis=0)
    buf = jnp.zeros((E * C + 1, D), tokens.dtype).at[jnp.where(valid, dest, E * C)].set(src)
    send = buf[:E * C].reshape(n_data, E_l, C, D)
    # EP all-to-all: expert e = d*E_l + e_l lives on data-device d.
    recv = jax.lax.all_to_all(send, data_axis, split_axis=0, concat_axis=0, tiled=False)
    xb = recv.transpose(1, 0, 2, 3).reshape(E_l, n_data * C, D)
    yb = _expert_ffn(xb, wi, wg, wo, dt)
    if model_axis is not None:
        yb = jax.lax.psum(yb, model_axis)                           # TP combine over F shards
    send_back = yb.reshape(E_l, n_data, C, D).transpose(1, 0, 2, 3)
    got = jax.lax.all_to_all(send_back, data_axis, split_axis=0, concat_axis=0, tiled=False)
    yflat = got.reshape(E * C, D)
    y = yflat[dest] * valid[:, None]
    y = y.reshape(T, k, D) * top_p[..., None].astype(y.dtype)
    return y.sum(1), aux, zloss


def moe_block(p: Params, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss, z_loss). Mesh-aware."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    mesh, rules = get_mesh_context()
    router_w = p["router"].astype(jnp.float32)

    use_ep = False
    data_axes: tuple[str, ...] = ()
    if mesh is not None and rules is not None:
        data_axes = rules.get("experts")
        use_ep = len(data_axes) == 1 and mesh.shape[data_axes[0]] > 1 and \
            E % mesh.shape[data_axes[0]] == 0

    if not use_ep:
        tokens = x.reshape(B * S, D)
        T = tokens.shape[0]
        chunk = min(MOE_CHUNK, T)
        C = max(MIN_CAPACITY, int(np.ceil(chunk * k / E * cfg.capacity_factor)))
        if T % chunk != 0:  # pad to a chunk multiple (decode tails)
            pad = chunk - T % chunk
            tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        nch = tokens.shape[0] // chunk

        def step(_, tc):
            y, aux, zl = _moe_chunk_local(tc, router_w, p["wi"], p["wg"], p["wo"], cfg, C)
            return None, (y, aux, zl)

        _, (ys, auxs, zls) = jax.lax.scan(step, None, tokens.reshape(nch, chunk, D))
        out = ys.reshape(-1, D)[:T].reshape(B, S, D)
        return constrain(out, ("batch", "seq", "embed")), auxs.mean(), zls.mean()

    # ---- EP path under a mesh ----
    data_axis = data_axes[0]
    n_data = mesh.shape[data_axis]
    model_axes = rules.get("expert_mlp")
    model_axis = model_axes[0] if model_axes else None
    batch_axes = rules.get("batch")

    # per-device token count after batch sharding
    n_batch_shards = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    T_local = (B * S) // n_batch_shards
    chunk = min(MOE_CHUNK, T_local)
    C = max(MIN_CAPACITY, int(np.ceil(chunk * k / E * cfg.capacity_factor)))

    from jax.sharding import PartitionSpec as P

    tok_spec = P(tuple(batch_axes) if batch_axes else None, None)
    w_e_spec = P(data_axis, None, model_axis)
    wo_spec = P(data_axis, model_axis, None)

    from repro.perf import get_flags
    flags = get_flags()
    n_model = mesh.shape[model_axis] if model_axis else 1
    tp_dispatch = bool(flags.moe_tp_dispatch and model_axis and n_model > 1
                       and chunk % n_model == 0)

    # TP-sharded dispatch (PerfFlags.moe_tp_dispatch): each model rank routes
    # a distinct 1/TP slice of the chunk, so the EP all-to-all payload and the
    # expert GEMM shrink TP x (they are otherwise duplicated across TP ranks).
    # Expert weights are all-gathered over the model axis once per layer (in
    # bf16) so each rank computes full-F outputs for its tokens — no TP psum.
    C_eff = C if not tp_dispatch else max(
        MIN_CAPACITY, int(np.ceil(chunk / n_model * k / E * cfg.capacity_factor)))

    def body(tokens, rw, wi, wg, wo):
        Tl = tokens.shape[0]
        ch = min(chunk, Tl)
        pad = (-Tl) % ch
        tpad = jnp.pad(tokens, ((0, pad), (0, 0))) if pad else tokens
        nch = tpad.shape[0] // ch
        dt = _dtype(cfg)

        if tp_dispatch:
            wi_f = jax.lax.all_gather(wi.astype(dt), model_axis, axis=2, tiled=True)
            wg_f = jax.lax.all_gather(wg.astype(dt), model_axis, axis=2, tiled=True)
            wo_f = jax.lax.all_gather(wo.astype(dt), model_axis, axis=1, tiled=True)

        def step(_, tc):
            if tp_dispatch:
                my = jax.lax.axis_index(model_axis)
                sl = ch // n_model
                tc_slice = jax.lax.dynamic_slice_in_dim(tc, my * sl, sl, 0)
                y, aux, zl = _moe_chunk_ep(tc_slice, rw, wi_f, wg_f, wo_f, cfg,
                                           C_eff, data_axis, None, n_data)
                y = jax.lax.all_gather(y, model_axis, axis=0, tiled=True)
                return None, (y, aux, zl)
            return None, _moe_chunk_ep(tc, rw, wi, wg, wo, cfg, C_eff,
                                       data_axis, model_axis, n_data)

        _, (ys, auxs, zls) = jax.lax.scan(step, None, tpad.reshape(nch, ch, -1))
        y = ys.reshape(-1, tokens.shape[-1])[:Tl]
        aux = jax.lax.pmean(auxs.mean(), data_axis)
        zl = jax.lax.pmean(zls.mean(), data_axis)
        return y, aux, zl

    tokens = x.reshape(B * S, D)
    y, aux, zl = shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(), w_e_spec, w_e_spec, wo_spec),
        out_specs=(tok_spec, P(), P()),
        check_vma=False,
    )(tokens, router_w, p["wi"], p["wg"], p["wo"])
    out = y.reshape(B, S, D)
    return constrain(out, ("batch", "seq", "embed")), aux, zl
