import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the step function + abstract inputs (``repro.launch.steps``),
  3. ``.lower().compile()`` — sharding or memory bugs surface HERE,
  4. records ``memory_analysis()`` (fits-per-device proof),
     ``cost_analysis()`` (FLOPs/bytes for the roofline),
     and the collective-bytes tally parsed from the optimized HLO,
  5. writes a JSON artifact under ``results/dryrun/``.

Usage:
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
"""
import argparse
import dataclasses as _dc


def dataclassesdict(x):
    return _dc.asdict(x)
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.perf import PerfFlags, perf_flags
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import use_compile_cache
from repro.launch.shapes import SHAPES, cell_supported
from repro.launch.steps import build_step
from repro.parallel.sharding import (MULTI_POD_RULES, SINGLE_POD_RULES,
                                     mesh_context, pure_fsdp_rules)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per chip usable)

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the optimized HLO.

    Counts each op once via its result shape (the payload that crosses the
    interconnect at least once); ops inside while-loop bodies are multiplied
    by the loop trip count when it is statically inferable from the HLO
    (scan-lowered loops carry ``trip_count`` in backend_config comments —
    conservatively, we use static counts parsed from induction bounds when
    present, else 1).
    """
    totals: dict[str, float] = {}
    # map loop body computation name -> trip count (best effort)
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m or "-done" in m.group(0):
            continue
        kind = m.group(1)
        # result shape is the lhs type annotation: e.g. "%ag = f32[16,1024]{..} all-gather(...)"
        lhs = line.split("=", 1)
        if len(lhs) < 2:
            continue
        shapes = _SHAPE_RE.findall(lhs[1].split(m.group(0))[0])
        nbytes = 0
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0.0) + nbytes
    totals["total"] = sum(totals.values())
    return totals


def while_trip_counts(hlo_text: str) -> list[int]:
    """Trip counts of while loops when XLA annotated them."""
    return [int(x) for x in re.findall(r'trip_count["\s:=]+(\d+)', hlo_text)]


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, compile_: bool = True,
             causal_skip: bool = False, out_dir: str | None = None,
             flags: PerfFlags | None = None, tag: str = "") -> dict:
    cfg = get_config(arch)
    flags = flags or PerfFlags(causal_skip=causal_skip)
    causal_skip = flags.causal_skip
    supported, why = cell_supported(cfg, shape_name)
    mesh_name = "multipod" if multi_pod else "pod"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "family": cfg.family, "status": "skipped", "why": why,
           "causal_skip": causal_skip, "tag": tag,
           "flags": dataclassesdict(flags)}
    if not supported:
        return _finish(rec, out_dir)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = MULTI_POD_RULES if multi_pod else SINGLE_POD_RULES
    if (flags.dense_pure_fsdp and SHAPES[shape_name].kind == "train"
            and cfg.family in ("dense", "vlm")):
        rules = pure_fsdp_rules(rules)
    n_chips = int(np.prod(list(mesh.shape.values())))
    try:
        with perf_flags(flags), mesh_context(mesh, rules):
            jitted, abstract = build_step(cfg, mesh, rules, shape_name,
                                          **({"causal_skip": True}
                                             if causal_skip and shape_name == "train_4k"
                                             else {}))
            lowered = jitted.lower(*abstract)
            rec["lower_s"] = round(time.time() - t0, 1)
            if not compile_:
                rec["status"] = "lowered"
                return _finish(rec, out_dir)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        from repro.launch.hlo_analysis import collective_schedule
        coll = collective_schedule(hlo)        # loop-aware (trip-count x)
        coll_flat = collective_bytes(hlo)      # naive (loop bodies once)
        trips = while_trip_counts(hlo)
        from repro.launch.analytic import analytic_cell
        with perf_flags(flags):
            amodel = analytic_cell(cfg, shape_name, multi_pod=multi_pod,
                                   causal_skip=causal_skip)

        flops = float(cost.get("flops", 0.0))
        bytes_hbm = float(cost.get("bytes accessed", 0.0))
        rec.update(
            status="ok",
            n_chips=n_chips,
            hlo_flops=flops,
            hlo_bytes=bytes_hbm,
            collective_bytes=coll,
            collective_bytes_flat=coll_flat,
            analytic=dict(flops_chip=amodel.flops_chip,
                          hbm_chip=amodel.hbm_chip,
                          coll_chip=amodel.coll_chip, **amodel.detail),
            while_trip_counts=trips[:32],
            memory=dict(
                bytes_per_device=getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0),
                temp=getattr(mem, "temp_size_in_bytes", 0),
                args=getattr(mem, "argument_size_in_bytes", 0),
                output=getattr(mem, "output_size_in_bytes", 0),
                alias=getattr(mem, "alias_size_in_bytes", 0),
                generated_code=getattr(mem, "generated_code_size_in_bytes", 0),
            ),
            model_flops=model_flops(cfg, shape_name),
        )
        # roofline terms in per-chip seconds.  cost_analysis() describes the
        # per-device SPMD module (shapes in the optimized HLO are local
        # shards), so the values are already per-chip — no further division.
        rec["roofline"] = dict(
            compute_s=flops / PEAK_FLOPS,
            memory_s=bytes_hbm / HBM_BW,
            collective_s=coll["total"] / ICI_BW,
        )
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["roofline"]["dominant"] = dom
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"lower={rec.get('lower_s')}s compile={rec.get('compile_s')}s "
              f"flops={flops:.3e} bytes={bytes_hbm:.3e} "
              f"coll={coll['total']:.3e} dom={dom}")
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: {rec['error']}")
    return _finish(rec, out_dir)


def model_flops(cfg, shape_name: str) -> float:
    """Analytic MODEL_FLOPS: 6·N·D for train, 2·N_active·D for inference."""
    s = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if s.kind == "train":
        tokens = s.global_batch * s.seq_len
        return 6.0 * n_active * tokens
    if s.kind == "prefill":
        tokens = s.global_batch * s.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * s.global_batch  # decode: one token per request


def _finish(rec: dict, out_dir: str | None):
    out_dir = out_dir or RESULTS_DIR
    os.makedirs(out_dir, exist_ok=True)
    suffix = rec.get("tag") or ("_cs" if rec.get("causal_skip") else "")
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    slim = {k: v for k, v in rec.items() if k != "traceback"}
    with open(path, "w") as f:
        json.dump(slim, f, indent=1)
    if rec.get("traceback"):
        with open(path + ".err", "w") as f:
            f.write(rec["traceback"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--causal-skip", action="store_true",
                    help="balanced-causal attention schedule (perf variant)")
    ap.add_argument("--opt", action="store_true",
                    help="all beyond-paper perf flags on; artifacts get _opt")
    ap.add_argument("--flags", default=None,
                    help="comma list of PerfFlags fields to enable")
    ap.add_argument("--tag", default=None, help="artifact filename suffix")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    if args.opt:
        flags = PerfFlags.all_on()
        tag = args.tag or "_opt"
    elif args.flags:
        flags = PerfFlags(**{k: True for k in args.flags.split(",")})
        tag = args.tag or ("_" + "-".join(sorted(args.flags.split(","))))
    else:
        flags = PerfFlags(causal_skip=args.causal_skip)
        tag = args.tag or ("_cs" if args.causal_skip else "")

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                if args.skip_existing:
                    suffix = "_cs" if args.causal_skip else ""
                    p = os.path.join(RESULTS_DIR,
                                     f"{arch}__{shape}__{'multipod' if mp else 'pod'}{suffix}.json")
                    if os.path.exists(p):
                        st = json.load(open(p)).get("status")
                        if st in ("ok", "skipped"):
                            continue
                rec = run_cell(arch, shape, mp, compile_=not args.no_compile,
                               flags=flags, tag=tag)
                n_ok += rec["status"] in ("ok", "skipped", "lowered")
                n_fail += rec["status"] == "error"
    print(f"done: {n_ok} ok/skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
