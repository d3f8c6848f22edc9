"""Static checks over the Pallas kernels.

Two halves:

1. **Grid/BlockSpec bounds proof.**  ``pl.pallas_call`` is intercepted (no
   kernel executes) to capture every call's grid, BlockSpecs and padded
   operand shapes; each index map is then evaluated at EVERY grid point and
   each block offset checked in bounds for the operand it addresses.  The
   wrapper sweep covers ragged shapes (Q/G/C/D far from the block sizes) so
   the pow2/round_up padding arithmetic is what's actually proved.

2. **Sentinel-convention probes.**  The tie-break differentials
   (tracker<->engine, single<->fleet) rely on every masked/padded slot
   ranking to exactly ``(NEG_INF, -1)``.  Tiny interpret-mode probes pin
   that for: bands beyond the gallery size, fully-masked queries,
   frame-mismatched galleries, and the empty-gallery fast path — plus the
   NEG_INF constant itself.
"""
from __future__ import annotations

import contextlib
import itertools

import numpy as np

from repro.analysis.lint import Violation

__all__ = ["audit_kernels", "capture_pallas_calls", "check_record"]

# grid-point enumeration budget per captured call (probes are tiny; a grid
# this large in an audit fixture is itself a bug)
_MAX_GRID_POINTS = 200_000


class _Captured(Exception):
    """Raised by the intercepted pallas_call to abort wrapper execution."""


@contextlib.contextmanager
def capture_pallas_calls(records: list):
    """Monkeypatch ``pl.pallas_call`` to record (kernel, grid, specs,
    operand shapes) and abort before execution.  Call sites must catch
    ``_Captured`` — use ``_capture_call`` below."""
    from jax.experimental import pallas as pl
    real = pl.pallas_call

    def fake(kernel, **kw):
        # a PrefetchScalarGridSpec carries grid + specs itself, and its
        # leading scalar operands are extra arguments of every index map
        gs = kw.get("grid_spec")
        src = kw if gs is None else dict(grid=gs.grid, in_specs=gs.in_specs,
                                         out_specs=gs.out_specs)
        nsp = 0 if gs is None else getattr(gs, "num_scalar_prefetch", 0)

        def runner(*operands):
            records.append(dict(
                kernel=getattr(getattr(kernel, "func", kernel), "__name__",
                               str(kernel)),
                grid=src.get("grid"),
                in_specs=list(src.get("in_specs") or []),
                out_specs=src.get("out_specs"),
                out_shape=kw.get("out_shape"),
                operand_shapes=[tuple(np.shape(o)) for o in operands[nsp:]],
                scalar_prefetch=[np.asarray(o) for o in operands[:nsp]],
            ))
            raise _Captured
        return runner

    pl.pallas_call = fake
    try:
        yield records
    finally:
        pl.pallas_call = real


def _capture_call(fn, *args, **kwargs) -> list[dict]:
    records: list[dict] = []
    with capture_pallas_calls(records):
        try:
            fn(*args, **kwargs)
        except _Captured:
            pass
    return records


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _untiled(spec) -> bool:
    """Specs the (8, 128) VMEM tiling rule does not apply to: scalar memory
    (SMEM) and operands left in HBM for manual DMA (ANY)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    space = getattr(spec, "memory_space", None)
    return space is not None and space in (pltpu.SMEM, pl.ANY)


def _tiling_violation(block, shape) -> str | None:
    """TPU VMEM tiling rule: the last block dim is a multiple of 128 and the
    second-to-last a multiple of 8, unless the dim spans the whole operand
    axis.  Mosaic refuses any other block."""
    for axis, unit in ((-1, 128), (-2, 8)):
        if len(block) < -axis:
            continue
        blk = shape[axis] if block[axis] is None else block[axis]
        if blk % unit and blk != shape[axis]:
            return (f"block {tuple(block)} breaks the TPU (8, 128) tiling "
                    f"rule on operand shape {shape}: dim {axis} is {blk}, "
                    f"neither a multiple of {unit} nor the whole axis")
    return None


def check_record(rec: dict) -> list[Violation]:
    """Prove every BlockSpec index map in bounds over the full grid, and
    every VMEM block shape legal under the TPU tiling rule."""
    out: list[Violation] = []
    where = f"<pallas:{rec['kernel']}>"
    grid = rec["grid"]
    grid = (grid,) if isinstance(grid, int) else tuple(grid or ())
    total = 1
    for g in grid:
        total *= g
    if total > _MAX_GRID_POINTS:
        out.append(Violation("PALLAS", where, 0,
                             f"grid {grid} too large to enumerate "
                             f"({total} points) — shrink the audit shapes"))
        return out

    out_shapes = [tuple(s.shape) for s in _as_list(rec["out_shape"])]
    pairs = list(zip(rec["in_specs"], rec["operand_shapes"])) + \
        list(zip(_as_list(rec["out_specs"]), out_shapes))
    prefetch = rec.get("scalar_prefetch", [])
    for argno, (spec, shape) in enumerate(pairs):
        block = getattr(spec, "block_shape", None)
        imap = getattr(spec, "index_map", None)
        if block is None or imap is None:
            continue              # whole-array spec: trivially in bounds
        if not _untiled(spec):
            msg = _tiling_violation(block, shape)
            if msg:
                out.append(Violation("PALLAS", where, 0,
                                     f"arg {argno}: {msg}"))
        bad = 0
        for point in itertools.product(*map(range, grid)):
            idx = imap(*point, *prefetch)
            idx = tuple(idx) if isinstance(idx, (tuple, list)) else (idx,)
            if len(idx) != len(block) or len(block) != len(shape):
                out.append(Violation(
                    "PALLAS", where, 0,
                    f"arg {argno}: index map rank {len(idx)} vs block rank "
                    f"{len(block)} vs operand rank {len(shape)}"))
                bad += 1
                break
            for off, blk, dim in zip(idx, block, shape):
                blk = dim if blk is None else blk
                if off < 0 or (int(off) + 1) * blk > dim:
                    out.append(Violation(
                        "PALLAS", where, 0,
                        f"arg {argno}: block offset {idx} x block {block} "
                        f"out of bounds for operand shape {shape} at grid "
                        f"point {point}"))
                    bad += 1
                    break
            if bad:
                break   # one finding per (call, arg) is enough
    return out


# ---------------------------------------------------------------------------
# Shape sweeps: ragged (Q, G, C, D) far from the block sizes, so the
# pow2/round_up padding paths are what gets proved.
# ---------------------------------------------------------------------------

def _bounds_findings() -> list[Violation]:
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mamba_scan import mamba_scan
    from repro.kernels.reid_topk import (reid_topk, reid_topk_masked,
                                         reid_topk_segments, reid_topk_tiles)

    rng = np.random.default_rng(3)
    records: list[dict] = []

    for Q, G, D, k in [(1, 1, 8, 1), (5, 120, 16, 3), (100, 700, 32, 2),
                       (130, 1024, 64, 1), (8, 129, 8, 4)]:
        q = rng.normal(size=(Q, D)).astype(np.float32)
        g = rng.normal(size=(G, D)).astype(np.float32)
        records += _capture_call(reid_topk, q, g, k)

    for Q, C, G, k in [(1, 4, 1, 1), (5, 30, 120, 3), (100, 30, 700, 2),
                       (16, 130, 257, 1)]:
        q = rng.normal(size=(Q, 16)).astype(np.float32)
        qf = rng.integers(0, 9, Q).astype(np.int32)
        adm = rng.integers(0, 2, (Q, C)).astype(bool)
        g = rng.normal(size=(G, 16)).astype(np.float32)
        gc = rng.integers(0, C, G).astype(np.int32)
        gf = rng.integers(0, 9, G).astype(np.int32)
        records += _capture_call(reid_topk_masked, q, qf, adm, g, gc, gf, k)
        # the segment-ID entry shares the padded call; sweep it over the
        # same ragged shapes so a divergence in its padding arithmetic
        # cannot hide behind the frame-tag variant
        qs = rng.integers(0, 5, Q).astype(np.int32)
        gs = rng.integers(0, 5, G).astype(np.int32)
        records += _capture_call(reid_topk_segments, q, qs, adm, g, gc,
                                 gs, k)
        # the tile-granular entry widens the admission axis to C*T*T fused
        # cells; sweep it over the same ragged shapes (plus unlabeled -1
        # rows) so its CT-axis round_up padding is proved alongside
        TT = 4
        adm_ct = rng.integers(0, 2, (Q, C * TT)).astype(bool)
        g_ct = np.where(rng.random(G) < 0.1, -1,
                        gc * TT + rng.integers(0, TT, G)).astype(np.int32)
        records += _capture_call(reid_topk_tiles, q, qs, adm_ct, g, g_ct,
                                 gs, k)

    for B, H, S, hd, KV, T in [(2, 4, 256, 64, 2, 512), (1, 2, 512, 32, 2, 256)]:
        q = rng.normal(size=(B, H, S, hd)).astype(np.float32)
        kv = rng.normal(size=(B, KV, T, hd)).astype(np.float32)
        records += _capture_call(flash_attention, q, kv, kv)

    B, H, hd, KV, T = 2, 4, 64, 2, 1024
    import jax.numpy as jnp
    q = rng.normal(size=(B, H, hd)).astype(np.float32)
    kv = rng.normal(size=(B, KV, T, hd)).astype(np.float32)
    length = jnp.asarray(rng.integers(1, T, B), jnp.int32)
    records += _capture_call(decode_attention, q, kv, kv, length)

    B, L, D, N = 2, 256, 256, 16
    u = rng.normal(size=(B, L, D)).astype(np.float32)
    bm = rng.normal(size=(B, L, N)).astype(np.float32)
    A = rng.normal(size=(D, N)).astype(np.float32)
    records += _capture_call(mamba_scan, u, u, bm, bm, A)

    out: list[Violation] = []
    if not records:
        out.append(Violation("PALLAS", "<pallas:capture>", 0,
                             "no pallas_call captured — did the kernel "
                             "wrappers stop calling pl.pallas_call?"))
    for rec in records:
        out.extend(check_record(rec))
    return out


# ---------------------------------------------------------------------------
# Sentinel-convention probes (interpret mode, tiny shapes)
# ---------------------------------------------------------------------------

def _sentinel_findings() -> list[Violation]:
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.reid_topk import NEG_INF

    out: list[Violation] = []

    def expect(cond: bool, msg: str):
        if not cond:
            out.append(Violation("PALLAS", "<pallas:sentinel>", 0, msg))

    expect(float(NEG_INF) == -1e30,
           f"NEG_INF is {NEG_INF!r}, expected -1e30 — the sentinel the "
           "tie-break differentials encode")

    rng = np.random.default_rng(5)
    D = 8
    q = jnp.asarray(rng.normal(size=(3, D)), jnp.float32)

    # bands beyond the gallery size come back (NEG_INF, -1)
    g = jnp.asarray(rng.normal(size=(2, D)), jnp.float32)
    sv, si = ops.reid_topk(q, g, 5, interpret=True)
    sv, si = np.asarray(sv), np.asarray(si)
    expect(bool((sv[:, 2:] == NEG_INF).all() and (si[:, 2:] == -1).all()),
           "reid_topk: bands beyond the gallery are not (NEG_INF, -1)")

    # empty gallery: the host fast path must return the same sentinel
    sv, si = ops.reid_topk(q, jnp.zeros((0, D), jnp.float32), 3,
                           interpret=True)
    expect(bool((np.asarray(sv) == NEG_INF).all()
                and (np.asarray(si) == -1).all()),
           "reid_topk: empty gallery does not return (NEG_INF, -1)")

    # fully-masked query rows (admit all-False) rank to the sentinel
    C, G = 4, 6
    g = jnp.asarray(rng.normal(size=(G, D)), jnp.float32)
    gc = jnp.asarray(rng.integers(0, C, G), jnp.int32)
    gf = jnp.full((G,), 7, jnp.int32)
    qf = jnp.full((3,), 7, jnp.int32)
    adm = jnp.zeros((3, C), bool).at[1].set(True)   # rows 0/2 fully masked
    sv, si = ops.reid_topk_masked(q, qf, adm, g, gc, gf, 2, interpret=True)
    sv, si = np.asarray(sv), np.asarray(si)
    expect(bool((sv[[0, 2]] == NEG_INF).all() and (si[[0, 2]] == -1).all()),
           "reid_topk_masked: fully-masked rows are not (NEG_INF, -1)")
    expect(bool((si[1] >= 0).all()),
           "reid_topk_masked: an admitted row with matching frames "
           "unexpectedly hit the sentinel")

    # frame mismatch masks every row the same way
    sv, si = ops.reid_topk_masked(q, qf, jnp.ones((3, C), bool), g, gc,
                                  gf + 1, 2, interpret=True)
    expect(bool((np.asarray(sv) == NEG_INF).all()
                and (np.asarray(si) == -1).all()),
           "reid_topk_masked: frame-mismatched galleries are not "
           "(NEG_INF, -1)")

    # the segment-ID entry: an injective relabeling of the frame tags must
    # be bit-identical to the frame variant (the consolidation plane's
    # trace-identity contract) ...
    q_seg = jnp.full((3,), 2, jnp.int32)        # frame 7 -> segment 2
    g_seg = jnp.full((G,), 2, jnp.int32)
    ssv, ssi = ops.reid_topk_segments(q, q_seg, adm, g, gc, g_seg, 2,
                                      interpret=True)
    msv, msi = ops.reid_topk_masked(q, qf, adm, g, gc, gf, 2,
                                    interpret=True)
    expect(bool(np.array_equal(np.asarray(ssv), np.asarray(msv))
                and np.array_equal(np.asarray(ssi), np.asarray(msi))),
           "reid_topk_segments: relabeled segment tags diverge from the "
           "frame-tag variant")
    # ... and a segment mismatch masks every row to the sentinel
    ssv, ssi = ops.reid_topk_segments(q, q_seg, jnp.ones((3, C), bool), g,
                                      gc, g_seg + 1, 2, interpret=True)
    expect(bool((np.asarray(ssv) == NEG_INF).all()
                and (np.asarray(ssi) == -1).all()),
           "reid_topk_segments: segment-mismatched galleries are not "
           "(NEG_INF, -1)")

    # the tile-granular entry: with EVERY tile admitted the fused
    # (camera, tile) cells reduce to camera admission, so the kernel must
    # be bit-identical to the segment variant (the tile differential's
    # trace-identity contract) ...
    TT = 4
    g_tile = jnp.asarray(rng.integers(0, TT, G), jnp.int32)
    g_ct = gc * TT + g_tile
    adm_ct = jnp.repeat(adm, TT, axis=1)        # admit_ct[q, c*TT+t]=adm[q,c]
    tsv, tsi = ops.reid_topk_tiles(q, q_seg, adm_ct, g, g_ct, g_seg, 2,
                                   interpret=True)
    expect(bool(np.array_equal(np.asarray(tsv), np.asarray(msv))
                and np.array_equal(np.asarray(tsi), np.asarray(msi))),
           "reid_topk_tiles: all-tiles-admitted diverges from the "
           "segment variant")
    # ... an admitted camera whose TILE is masked ranks to the sentinel
    adm_wrong = jnp.zeros((3, C * TT), bool).at[:, 0].set(True)
    off_cell0 = jnp.where(g_ct == 0, 1, g_ct)   # no row sits in cell 0
    tsv, tsi = ops.reid_topk_tiles(q, q_seg, adm_wrong, g, off_cell0,
                                   g_seg, 2, interpret=True)
    expect(bool((np.asarray(tsv) == NEG_INF).all()
                and (np.asarray(tsi) == -1).all()),
           "reid_topk_tiles: tile-mismatched galleries are not "
           "(NEG_INF, -1)")
    # ... and unlabeled gallery rows (cell -1) match nothing even under
    # an all-admitted mask
    tsv, tsi = ops.reid_topk_tiles(q, q_seg, jnp.ones((3, C * TT), bool),
                                   g, jnp.full((G,), -1, jnp.int32),
                                   g_seg, 2, interpret=True)
    expect(bool((np.asarray(tsv) == NEG_INF).all()
                and (np.asarray(tsi) == -1).all()),
           "reid_topk_tiles: unlabeled (cell -1) rows are not (NEG_INF, -1)")
    return out


def audit_kernels() -> list[Violation]:
    """Bounds proofs + sentinel probes; empty list = clean."""
    return _bounds_findings() + _sentinel_findings()
