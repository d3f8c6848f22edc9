# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import drift, kernels_bench, scenarios, tables
from repro.launch.serve import device_line, use_compile_cache

ALL = {
    "policy_sweep": scenarios.policy_sweep,
    "serving_sweep": scenarios.serving_sweep,
    "serving_shard_sweep": scenarios.serving_shard_sweep,
    "gallery_sweep": scenarios.gallery_sweep,
    "drift_sweep": scenarios.drift_sweep,
    "transport_sweep": scenarios.transport_sweep,
    "query_churn_sweep": scenarios.query_churn_sweep,
    "tile_sweep": scenarios.tile_sweep,
    "soak_130": scenarios.soak_130,
    "sec3_potential": tables.sec3_potential,
    "fig10_anoncampus": tables.fig10_anoncampus,
    "fig11_duke": tables.fig11_duke,
    "fig12_porto": tables.fig12_porto,
    "fig13_camera_scaling": tables.fig13_camera_scaling,
    "fig14_frame_skipping": tables.fig14_frame_skipping,
    "fig15_replay": tables.fig15_replay,
    "fig16_profiling": tables.fig16_profiling,
    "fig17_identity_detection": tables.fig17_identity_detection,
    "sec6_drift": drift.run,
    "kernels": kernels_bench.run,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None, choices=list(ALL))
    ap.add_argument("--bench-dir", default=None, metavar="DIR",
                    help="write machine-readable BENCH_<scenario>.json files "
                    "(admitted_steps, unique_frames, wall, p50/p99 round "
                    "latency per config) for every sweep that records them")
    args = ap.parse_args()
    names = args.only or list(ALL)
    use_compile_cache()
    print(f"# {device_line()}", file=sys.stderr)

    print("name,us_per_call,derived")
    failed = []
    for name in names:
        t0 = time.time()
        scenarios.pop_bench_records(name)  # drop stale in-process records
        try:
            rows = ALL[name]()
        except Exception as e:  # noqa: BLE001 — report and continue the suite
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            failed.append(name)
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}")
        recs = scenarios.pop_bench_records(name)
        if args.bench_dir and recs:
            os.makedirs(args.bench_dir, exist_ok=True)
            path = os.path.join(args.bench_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"scenario": name, "records": recs}, f, indent=1)
            print(f"# {name}: {len(recs)} records -> {path}", file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# failed: {' '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
