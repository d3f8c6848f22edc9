"""The harness driven end to end on the CPU at a small size (interpret-mode
kernels; everything but the look for a chip): a sound run is correct, and
``correct`` comes out false with the timed path broken underneath (a step
that returns its state unchanged, half the batch left out, an answer
altered where it is produced) and with the bfloat16 control."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402


@pytest.fixture
def fresh_jit():
    """Faults are planted in functions that jit traced already: drop the
    compiled programs before and after so each run traces what it calls."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct_and_the_line_has_its_schema():
    res, printed = tiny.run(seed=11)
    assert res["correct"], printed
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    from harness import drive
    names = [m["name"] for m in drive.load_benchmark()["end_to_end"]]
    assert sorted(res["metrics"]) == sorted(names)
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # on a CPU a tick may pass a second (``failed``): timing means nothing
    assert res["attempted"] > 0 and 0 <= res["failed"] <= res["attempted"]
    c = res["checks"]
    assert c["mismatches"]["value"] == 0
    assert c["score_gap"]["value"] < c["score_gap"]["limit"]
    assert c["rounds_compared"]["value"] > 100
    json.dumps(res)
    assert "check score_gap" in printed


def test_traced_run_reports_per_layer_metrics_only():
    res, printed = tiny.run(seed=17, trace=True)
    assert res["correct"], printed
    # no chip in a CPU trace: only the benchmark's own span can be read
    assert set(res["metrics"]) == {"ingest_ms"}
    assert res["device"]["window_s"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("tile_grid,mix", [(8, "live"), (0, "replay")])
def test_tile_and_replay_paths_are_correct(tile_grid, mix):
    res, printed = tiny.run(seed=12, cfg=tiny.config(tile_grid=tile_grid),
                            mix=tiny.LIVE if mix == "live" else tiny.REPLAY)
    assert res["correct"], printed


def test_control_in_bfloat16_fails_the_score_gap():
    """The reference in bfloat16 put in the program's place: the harness's
    own comparison returns ``correct: false``, by the score gap alone."""
    res, printed = tiny.run(seed=13, control=True)
    assert not res["correct"], printed
    c = res["checks"]
    assert c["mismatches"]["value"] == 0
    assert c["score_gap"]["value"] > 3 * c["score_gap"]["limit"]
    program = float(printed.split("the program's score_gap ")[1].split(";")[0])
    assert program < c["score_gap"]["limit"]


def test_state_left_unchanged_is_not_correct(monkeypatch, fresh_jit):
    from repro.runtime import engine

    monkeypatch.setattr(engine, "advance",
                        lambda policy, windows, state, *a, **k: state)
    res, printed = tiny.run(seed=14, seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["mismatches"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(monkeypatch, fresh_jit):
    import jax.numpy as jnp
    from repro.kernels import ops

    real = ops.reid_topk_segments

    def half(queries, q_seg, admit, *a, **k):
        # every other row of the batch comes back unranked
        sv, si = real(queries, q_seg, admit, *a, **k)
        keep = jnp.arange(sv.shape[0])[:, None] % 2 == 0
        return jnp.where(keep, sv, -1e30), jnp.where(keep, si, -1)

    monkeypatch.setattr(ops, "reid_topk_segments", half)
    res, printed = tiny.run(seed=15, seconds=0.5)
    assert not res["correct"]


def test_answer_altered_where_produced_is_not_correct(monkeypatch,
                                                      fresh_jit):
    import jax.numpy as jnp
    from repro.kernels import ops

    real = ops.reid_topk_segments

    def nudged(*a, **k):
        sv, si = real(*a, **k)
        return jnp.where(si >= 0, sv + 1e-3, sv), si

    monkeypatch.setattr(ops, "reid_topk_segments", nudged)
    res, printed = tiny.run(seed=16, seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > \
        res["checks"]["score_gap"]["limit"]


def test_the_chip_entry_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "duke8.live", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=tiny.CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
