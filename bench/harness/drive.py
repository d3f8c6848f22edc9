"""One run of one cell: world, engine, warm-up, measured window, check.

The loop is closed: the engine serves ticks one after another, each tick
one second of video from every camera, and tick t+1 needs tick t's state,
so ticks as fast as the engine takes them is the highest rate it sustains.
Query arrivals are scheduled in video time, so what a run serves is fixed
by its seed and can be compared with the reference; every seed serves the
same recording, and so the same work (``harness.world``).

A tick's latency runs from the start of its ``ingest`` call to the return
of ``tick()``, which copies the round's outputs to the host.  Building the
tick's payload (the generator's work) and submitting the queries due are
timed apart, inside the window's wall time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

from harness import reference, traffic, world as world_mod
from harness.world import seed_rng

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
# a tick slower than the second of video it serves has failed
TICK_LIMIT_S = 1.0
# the process's resource usage reported over the window
RUSAGE = ("ru_utime", "ru_stime", "ru_minflt", "ru_majflt", "ru_nvcsw",
          "ru_nivcsw")


class RunError(RuntimeError):
    """The run cannot produce a result (no chip, stream too short...)."""


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# observation of the program: compiles, round records
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts the compile steps JAX reports (tracing a new signature,
    lowering, backend compilation), so a window that compiled shows."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith(self.PREFIX):
            self.events += 1
            self.seconds += duration


class GcClock:
    """Seconds the interpreter spent in garbage collection, by generation,
    while ``on``."""

    def __init__(self):
        self.on = False
        self.seconds = [0.0, 0.0, 0.0]
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds[info["generation"]] += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._cb)


class Recorder:
    """The ``record_trace`` sink handed to ``tick``: keeps the round
    records of the sampled queries, keyed by query and tick, and, while
    ``rounds`` is a list, the (f_curr, mask) of every record of every round
    for the rank-call shapes.  A round holds each query once, so a query
    seen twice since the last boundary starts the next round."""

    def __init__(self, sampled: set):
        self.sampled = sampled
        self.tick = -1
        self.by_q: dict = {}
        self.rounds: list | None = None
        self._cur: dict = {}

    def new_tick(self, t: int) -> None:
        self._close()
        self.tick = t

    def _close(self) -> None:
        if self.rounds is not None and self._cur:
            self.rounds.append((self.tick, list(self._cur.values())))
        self._cur = {}

    def append(self, r: dict) -> None:
        q = r["qid"]
        if q in self.sampled:
            self.by_q.setdefault(q, {}).setdefault(self.tick, []).append(r)
        if self.rounds is not None:
            if q in self._cur:
                self._close()
            self._cur[q] = (int(r["f_curr"]), r["mask"])

    def extend(self, rs) -> None:
        for r in rs:
            self.append(r)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


def find_cell(bench: dict, name: str) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(w["name"], w["config"], w["traffic"], int(w["chips"]))
    raise RunError(f"no workload {name!r} in BENCHMARK.json")


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"JAX found no TPU (platform {devs[0].platform!r}); "
                       f"nothing was run")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program however fast it compiled."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def policy_of(cfg: dict, mix: dict) -> reference.Policy:
    s = cfg["serve"]
    return reference.Policy(
        s_thresh=s["s_thresh"], t_thresh=s["t_thresh"], exit_t=s["exit_t"],
        match_thresh=s["match_thresh"], feat_alpha=s["feat_alpha"],
        relax_factor=s["relax_factor"],
        replay_speed=float(mix["replay_speed"]),
        self_window=s["self_window"], retention=s["retention"])


def build_engine(cfg: dict, mix: dict, w: dict, chips: int):
    """The engine through ``repro.api.serve``: one chip serves alone, more
    chips serve as one fleet with the query axis sharded over them."""
    from repro import api as rexcam

    s = cfg["serve"]
    model = rexcam.profile(w["history"], time_limit=w["profile_until"],
                           n_bins=s["n_bins"], bin_width=s["bin_width"],
                           tile_grid=s["tile_grid"])
    policy = rexcam.SearchPolicy(
        scheme=s["scheme"], s_thresh=s["s_thresh"], t_thresh=s["t_thresh"],
        exit_t=s["exit_t"], match_thresh=s["match_thresh"],
        feat_alpha=s["feat_alpha"], relax_factor=s["relax_factor"],
        replay_speed=float(mix["replay_speed"]),
        self_window=s["self_window"])
    eng = rexcam.serve(model, embed_fn=lambda x: x, policy=policy,
                       max_batch=s["max_batch"], retention=s["retention"],
                       geo_adj=w["net"].geo_adjacent,
                       shards=chips if chips > 1 else None,
                       tile_grid=s["tile_grid"])
    eng.prime_batch(cfg["queries"]["batch_cap"])
    eng.prime_gallery(cfg["queries"]["gallery_rows_cap"])
    return eng


def payload(w: dict, t: int):
    """Every camera's detections at step t, as feature rows, and their
    tiles on tile configurations."""
    gal, feats, tiles = w["gal"], w["feats"], w["tiles"]
    frames, tl = {}, {}
    for c in range(gal.shape[0]):
        v = gal[c, t]
        v = v[v >= 0]
        if len(v):
            frames[c] = feats[v]
            if tiles is not None:
                tl[c] = tiles[v]
    return frames, (tl if tiles is not None else None)


def live_count(eng) -> int:
    return sum(1 for q in eng.queries.values() if not q.done)


@dataclasses.dataclass
class Window:
    lat: list                 # seconds per tick
    gen_s: float              # payload + submission seconds
    ingest: list              # seconds per ingest call
    wall_s: float
    t_first: int              # first video step of the window
    t_last: int               # last video step served
    compiles: int
    live: list                # live queries, every 16th tick
    gallery_rows: int         # the engine's padded round-gallery rows
    work: dict                # the engine's tick counters, summed
    gc_s: list                # seconds in garbage collection, by generation
    host: dict                # the process's resource usage in the window


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        control: bool = False, require_tpu: bool = True,
        t_process: float | None = None, out=sys.stderr,
        cfg: dict | None = None, mix: dict | None = None) -> dict:
    """One run; returns the result object (the last line's content).
    ``cfg``/``mix`` stand in for the cell's files (the tests' small
    worlds); ``require_tpu=False`` lets the tests drive a run on the CPU."""
    t_process = time.perf_counter() if t_process is None else t_process
    device = device_info(cell.chips, require_tpu)
    import jax
    from harness import peaks as peaks_mod
    peak = (peaks_mod.peaks(device["kind"]) if require_tpu else None)
    cache = use_compile_cache() if require_tpu else "off"
    counter = CompileCounter()
    cfg = load_config(cell.config) if cfg is None else cfg
    mix = traffic.load(cell.traffic) if mix is None else mix
    if "replay_skip" in mix:
        raise RunError("replay_skip is not a traffic parameter")

    w = world_mod.build(cfg, seed)
    arrivals = traffic.Arrivals(w, cfg, mix)
    # the sampled queries are drawn from the seed, by query id
    pick = seed_rng(seed, 11)
    share = float(cfg["check"]["query_share"])
    eng = build_engine(cfg, mix, w, cell.chips)
    rec = Recorder(set())
    feats = w["feats"]
    stream = w["stream"]
    submitted = {}
    target = int(cfg["queries"]["target"])
    ramping = True

    def submit_due(t):
        for vid in arrivals.due(t, ramping):
            qid = len(submitted)
            eng.submit_query(qid, feats[vid], int(stream.cam[vid]),
                             int(stream.t_out[vid]))
            submitted[qid] = (t, vid)
            if qid == 0 or pick.random() < share:
                rec.sampled.add(qid)

    t = w["t0"]
    eng.t = t
    end_t = w["horizon"] - 1

    def step(t, span):
        submit_due(t)
        frames, tiles = payload(w, t)
        rec.new_tick(t)
        with span("bench.ingest"):
            a = time.perf_counter()
            if tiles is None:
                eng.ingest(frames)
            else:
                eng.ingest(frames, tiles)
            b = time.perf_counter()
        with span("bench.tick"):
            st = eng.tick(record_trace=rec)
        return a, b, time.perf_counter(), st

    nospan = lambda name: contextlib.nullcontext()  # noqa: E731
    # warm-up: every shape was primed; serve until the population is there
    wmin, wmax = (int(x) for x in mix["warmup_ticks"])
    while True:
        if t >= end_t:
            raise RunError("the stream ended during warm-up")
        live = live_count(eng)
        ramping = ramping and live < target
        served = t - arrivals.first
        if served >= wmin and (live >= target or served >= wmax):
            break
        step(t, nospan)
        t += 1
    ramping = False
    setup_s = time.perf_counter() - t_process
    print(f"setup: {setup_s!r} s to the first timed tick; warm-up served "
          f"{t - w['t0']} steps, {live_count(eng)} live queries "
          f"(target {target}); compile cache {cache}; "
          f"{counter.events} compile steps ({counter.seconds!r} s)",
          file=out)

    # the measured window
    logdir = None
    span = nospan
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
        rec.rounds = []
    gc.collect()
    gcc = GcClock()
    gcc.on = True
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    c0 = counter.events
    lat, ing, live = [], [], []
    gen = 0.0
    work = dict.fromkeys(("admitted_steps", "unique_frames", "embedded",
                          "content_steps", "matches"), 0)
    t_first = t
    win = jax.profiler.TraceAnnotation("bench.window") if trace else \
        contextlib.nullcontext()
    with win:
        w0 = time.perf_counter()
        deadline = w0 + seconds
        while time.perf_counter() < deadline:
            if t >= end_t:
                raise RunError(
                    f"the stream ended inside the window after {len(lat)} "
                    f"ticks: lengthen the configuration's stream")
            g0 = time.perf_counter()
            a, b, c, st = step(t, span)
            for k in work:
                work[k] += st[k]
            gen += a - g0
            lat.append(c - a)
            ing.append(b - a)
            if len(lat) % 16 == 1:          # scans every query ever served
                live.append(live_count(eng))
            t += 1
        wall = time.perf_counter() - w0
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    gcc.on = False
    gcc.close()
    rec.new_tick(t)
    window = Window(lat, gen, ing, wall, t_first, t - 1,
                    counter.events - c0, live,
                    int(getattr(eng, "padded_gallery_rows", -1)), work,
                    gcc.seconds, {k: getattr(ru1, k) - getattr(ru0, k)
                                  for k in RUSAGE})
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.devices()[:max(cell.chips, 1)])
    if trace:
        jax.profiler.stop_trace()

    # the check, after the window and the memory reading
    c0 = time.perf_counter()
    check = run_check(cfg, mix, w, submitted, rec, eng, window, control)
    print(f"check took {time.perf_counter() - c0!r} s", file=out)
    result = summarize(cell, cfg, window, setup_s, device, mem, check, out)
    if trace:
        from harness import layers
        try:
            layer, dev_extra, breakdown = layers.per_layer(
                cell, cfg, w, window, rec, logdir, peak, out)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        result["metrics"] = layer
        result["device"].update(dev_extra)
        if breakdown:
            result["breakdown"] = breakdown
    result["checks"] = result.pop("checks")
    return result


def run_check(cfg, mix, w, submitted, rec, eng, window, control):
    cmp = reference.Comparison(w, cfg, policy_of(cfg, mix), control=control)
    stream = w["stream"]
    for qid in sorted(rec.sampled):
        if qid not in submitted:
            continue
        t_sub, vid = submitted[qid]
        q = eng.queries.get(qid)
        final = None if q is None else (
            int(q.f_curr), int(q.phase), bool(q.done), len(q.matches),
            np.asarray(q.feat), int(getattr(q, "tile_q", -1)))
        cmp.query(qid, w["feats"][vid], int(stream.cam[vid]),
                  int(stream.t_out[vid]), t_sub, window.t_last,
                  rec.by_q.get(qid, {}), final)
    return dict(cmp=cmp, control=control,
                control_gap=cmp.control_gap() if control else None)


def summarize(cell, cfg, window: Window, setup_s, device, mem, check,
              out) -> dict:
    lat = np.asarray(window.lat)
    n = len(lat)
    limit = float(cfg["check"]["score_gap_limit"])
    cmp = check["cmp"]
    failed = int((lat > TICK_LIMIT_S).sum())
    p95 = float(np.percentile(lat, 95) * 1e3) if n else float("nan")
    print(f"window: {n} ticks in {window.wall_s!r} s, video steps "
          f"{window.t_first}..{window.t_last}; tick p50 "
          f"{float(np.percentile(lat, 50) * 1e3)!r} ms, p95 {p95!r} ms "
          f"({int(np.ceil(n * 0.05))} ticks at or above it), max "
          f"{float(lat.max() * 1e3)!r} ms", file=out)
    print(f"generator: {window.gen_s!r} s building payloads and submitting "
          f"({window.gen_s / window.wall_s:.4f} of the window); "
          f"ingest mean {float(np.mean(window.ingest) * 1e3)!r} ms", file=out)
    print(f"live queries over the window: min {min(window.live)} mean "
          f"{float(np.mean(window.live))!r} max {max(window.live)}",
          file=out)
    print(f"compile steps inside the window: {window.compiles}; round "
          f"gallery rows high-water mark {window.gallery_rows}", file=out)
    print("work per tick: " + ", ".join(
        f"{k} {v / max(n, 1)!r}" for k, v in window.work.items())
        + f"; garbage collection {window.gc_s!r} s by generation", file=out)
    print("host in the window: " + ", ".join(
        f"{k} {v!r}" for k, v in window.host.items()), file=out)
    print(f"check: {cmp.queries} queries over {cmp.rounds} rounds compared "
          f"with the reference; {'; '.join(cmp.notes) or 'no mismatch'}",
          file=out)
    gap = cmp.score_gap
    if check["control"]:
        # the control stands in the program's place: its gap is compared
        print(f"control: the program's score_gap {gap!r}; bfloat16 scores "
              f"of the same rounds read {check['control_gap']!r}", file=out)
        gap = check["control_gap"]
        gap = float("inf") if gap is None else gap
    checks = {
        "score_gap": {"value": gap, "limit": limit},
        "mismatches": {"value": cmp.mismatches, "limit": 0},
        "rounds_compared": {"value": cmp.rounds, "limit": 1},
    }
    correct = (gap <= limit and cmp.mismatches == 0
               and cmp.rounds >= 1)
    for k, v in checks.items():
        rel = "<=" if k != "rounds_compared" else ">="
        print(f"check {k}: {v['value']!r} (limit {rel} {v['limit']!r})",
              file=out)
    return dict(
        correct=bool(correct), attempted=n, failed=failed,
        metrics={
            "video_s_per_s": {"value": n / window.wall_s,
                              "unit": "video_s/s"},
            "tick_p95_ms": {"value": p95, "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        },
        device=dict(device, memory_peak_bytes=int(mem)),
        checks=checks)
