"""The static invariant plane (repro.analysis): lint rules REX001-005 on
the planted-violation fixture corpus, the jaxpr audit over every registered
jit entry, the Pallas kernel audit, RecompileGuard, and the REPRO_SANITIZE
runtime assertions.

The fixture corpus under ``tests/fixtures/analysis`` mirrors the source
layout (runtime/, core/, kernels/) because the rules scope by path; every
fixture declares its expected hits in ``# rex-expect: REXNNN=n`` headers
and the tests assert EXACT counts — a rule firing once too often is as red
as one that stopped firing.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(TESTS, ".."))
SRC = os.path.join(REPO, "src")
FIXTURES = os.path.join(TESTS, "fixtures", "analysis")

_EXPECT_RE = re.compile(r"#\s*rex-expect:\s*(REX\d+)\s*=\s*(\d+)")


def _fixture_files():
    out = []
    for dirpath, _dirs, files in os.walk(FIXTURES):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                out.append((os.path.relpath(path, FIXTURES), path))
    return out


# ---------------------------------------------------------------------------
# REX lint rules on the fixture corpus
# ---------------------------------------------------------------------------

def test_fixture_corpus_exact_counts():
    """Every fixture's per-rule violation count matches its rex-expect
    header exactly (0 for undeclared rules) — suppressed and clean lines
    must stay quiet, planted lines must all fire."""
    from repro.analysis.lint import RULES, lint_file

    assert _fixture_files(), "fixture corpus missing"
    fired = set()
    for rel, path in _fixture_files():
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        expected: dict[str, int] = {}
        for rule, n in _EXPECT_RE.findall(text):
            expected[rule] = expected.get(rule, 0) + int(n)
        got: dict[str, int] = {}
        for v in lint_file(path, text=text, virtual_path=rel):
            got[v.rule] = got.get(v.rule, 0) + 1
            fired.add(v.rule)
        assert got == expected, \
            f"{rel}: expected {expected}, linted {got}"
    # the corpus demonstrates every named rule at least once
    assert fired == set(RULES), f"rules never fired: {set(RULES) - fired}"


def test_clean_fixtures_are_quiet():
    from repro.analysis.lint import lint_file
    for name in ("runtime/clean_engine.py", "core/suppressed.py"):
        path = os.path.join(FIXTURES, *name.split("/"))
        assert lint_file(path, virtual_path=name) == []


def test_suppression_scopes():
    """Line-level, def-level and file-level ``# rex: disable`` all hold:
    the REX001 fixture plants three heavy-numpy calls but only the
    unsuppressed one (line-level + def-level waived) reports."""
    from repro.analysis.lint import lint_file
    path = os.path.join(FIXTURES, "runtime", "hot_numpy.py")
    vs = lint_file(path, virtual_path="runtime/hot_numpy.py")
    assert [v.rule for v in vs] == ["REX001"]
    assert "np.linalg.norm" in vs[0].msg


def test_violation_rendering_is_greppable():
    from repro.analysis.lint import Violation
    v = Violation("REX001", "runtime/engine.py", 42, "boom")
    assert str(v) == "runtime/engine.py:42: REX001 boom"


def test_repo_tree_is_lint_clean():
    """The gate's zero-at-HEAD half for the lint layer."""
    from repro.analysis.lint import lint_paths
    vs = lint_paths([os.path.join(SRC, "repro")], rel_to=REPO)
    assert vs == [], "\n".join(str(v) for v in vs)


def test_check_invariants_script_contract():
    """Exit-code contract of the CI gate: --fixtures exits NON-zero (the
    planted corpus demonstrates every rule), --only lint exits 0 at HEAD."""
    env = dict(os.environ, PYTHONPATH=SRC)
    script = os.path.join(REPO, "scripts", "check_invariants.py")
    r = subprocess.run([sys.executable, script, "--fixtures"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0, r.stdout + r.stderr
    assert "every rule demonstrated" in r.stdout
    r = subprocess.run([sys.executable, script, "--only", "lint"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# jaxpr audit
# ---------------------------------------------------------------------------

def test_jaxpr_audit_clean_at_head():
    """Every registered jit entry (engine steps, kernel wrappers, the fleet
    shard_map bodies on a 1-device mesh) traces without forbidden
    primitives, x64 promotions, weak-typed outputs or dynamic shapes."""
    from repro.analysis.jaxpr_audit import audit_jaxprs
    vs = audit_jaxprs()
    assert vs == [], "\n".join(str(v) for v in vs)


def test_jaxpr_audit_flags_debug_callback():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_closed_jaxpr

    @jax.jit
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    # jax.debug.print traces to the `debug_print` host-callback primitive
    vs = audit_closed_jaxpr("noisy", noisy.trace(jnp.ones(3)).jaxpr)
    assert any("forbidden primitive `debug_print`" in v.msg for v in vs)


def test_jaxpr_audit_flags_weak_type_output():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_closed_jaxpr

    @jax.jit
    def leaky(x):
        return x.sum(), 1.0        # python scalar output: weak-typed

    vs = audit_closed_jaxpr("leaky", leaky.trace(jnp.ones(3)).jaxpr)
    assert any("weak-typed" in v.msg for v in vs)


def test_jaxpr_audit_flags_f64():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import audit_closed_jaxpr

    @jax.jit
    def promote(x):
        return x.astype(jnp.float64) + 1

    with jax.enable_x64(True):
        traced = promote.trace(jnp.ones(3, jnp.float32))
    vs = audit_closed_jaxpr("promote", traced.jaxpr)
    assert any("float64" in v.msg for v in vs)


# ---------------------------------------------------------------------------
# RecompileGuard
# ---------------------------------------------------------------------------

def test_recompile_guard_trips_on_shape_polymorphism():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import RecompileError, RecompileGuard

    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones(4))                      # warmup signature
    with RecompileGuard({"f": f}):
        f(jnp.ones(4))                  # same shape: cached, fine
    with pytest.raises(RecompileError, match=r"f: \+1"):
        with RecompileGuard({"f": f}):
            f(jnp.ones(8))              # new shape: steady-state recompile
    with RecompileGuard({"f": f}, max_new=1):
        f(jnp.ones(16))                 # one new shape class allowed


def test_recompile_guard_reports_deltas_without_raising_mid_block():
    import jax
    import jax.numpy as jnp
    from repro.analysis.jaxpr_audit import RecompileGuard

    @jax.jit
    def g(x):
        return x + 1

    g(jnp.ones(2))
    guard = RecompileGuard({"g": g}, max_new=2)
    with guard:
        g(jnp.ones(3))
        g(jnp.ones(5))
        assert guard.new_compiles() == {"g": 2}


def test_fleet_steady_state_compiles_once_across_shard_counts():
    """THE acceptance case: shard counts {1, 2, 4, 8} on 8 fake CPU
    devices, RecompileGuard over every registered entry plus the fleet's
    shard_map jits, at most one new signature per entry after warmup.
    Runs in-process on the CI fleet step, else in a flag-setting
    subprocess (the flag must not leak into this runtime)."""
    import jax
    if jax.local_device_count() >= 8:
        import conftest
        conftest.fleet_case_recompile_guard()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, TESTS] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, "-c",
         "import conftest; conftest.fleet_case_recompile_guard()"],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


# ---------------------------------------------------------------------------
# kernel audit
# ---------------------------------------------------------------------------

def test_kernel_audit_clean_at_head():
    from repro.analysis.kernel_audit import audit_kernels
    vs = audit_kernels()
    assert vs == [], "\n".join(str(v) for v in vs)


def test_kernel_bounds_prover_flags_oob_index_map():
    from types import SimpleNamespace
    from repro.analysis.kernel_audit import check_record

    spec = SimpleNamespace(block_shape=(8, 128),
                           index_map=lambda i, j: (i, j))
    rec = dict(kernel="bad", grid=(3, 2), in_specs=[spec], out_specs=None,
               out_shape=None, operand_shapes=[(16, 256)])
    vs = check_record(rec)        # grid point (2, 0) reads rows 16..24
    assert len(vs) == 1 and "out of bounds" in vs[0].msg

    rec["operand_shapes"] = [(24, 256)]
    assert check_record(rec) == []


def test_kernel_audit_flags_block_off_the_tpu_tiling():
    """A (1, 1) VMEM block over an (nq, ng) table — what the tile kernel's
    liveness input once was — is refused by Mosaic: the audit flags it on
    CPU.  The same table as a whole-array SMEM spec, or read through a
    scalar-prefetch index map, is legal."""
    from types import SimpleNamespace
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import jax
    import jax.numpy as jnp
    from repro.analysis.kernel_audit import _capture_call, check_record

    spec = SimpleNamespace(block_shape=(1, 1), index_map=lambda i, j: (i, j))
    rec = dict(kernel="live", grid=(2, 8), in_specs=[spec], out_specs=None,
               out_shape=None, operand_shapes=[(2, 8)])
    vs = check_record(rec)
    assert len(vs) == 1 and "tiling rule" in vs[0].msg
    rec["in_specs"] = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    assert check_record(rec) == []

    def call(live, x):
        return pl.pallas_call(
            lambda *refs: None,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(2,),
                in_specs=[pl.BlockSpec((8, 128),
                                       lambda i, live: (live[i], 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i, live: (i, 0))),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32))(live, x)

    x = np.zeros((16, 128), np.float32)
    ok, = _capture_call(call, np.array([1, 0], np.int32), x)
    assert check_record(ok) == []
    oob, = _capture_call(call, np.array([0, 2], np.int32), x)
    assert "out of bounds" in check_record(oob)[0].msg


def test_kernel_capture_intercepts_without_execution():
    import jax.numpy as jnp
    from repro.analysis.kernel_audit import _capture_call
    from repro.kernels.reid_topk import reid_topk

    calls = []
    q = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    g = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)), jnp.float32)
    records = _capture_call(reid_topk, q, g, 2)
    assert calls == []            # nothing ran
    assert len(records) == 1
    rec = records[0]
    assert rec["kernel"] == "_reid_kernel"
    assert rec["grid"] and rec["in_specs"]


# ---------------------------------------------------------------------------
# REPRO_SANITIZE runtime assertions
# ---------------------------------------------------------------------------

def test_sanitize_transport_reentrancy_assertion():
    """Armed: a fetch issued from inside the on_dead callback raises.
    Disarmed: the same callback is merely (dubious but) permitted."""
    from repro.analysis import sanitize
    from repro.runtime.transport import InProcTransport

    sanitize.enable()
    try:
        tr = InProcTransport()
        tr.on_dead = lambda peer: tr.fetch("w1", "k", lambda: 1)
        with pytest.raises(AssertionError, match="re-entered"):
            tr._fail_peer("w0")
    finally:
        sanitize.disable()

    tr2 = InProcTransport()
    got = []
    tr2.on_dead = lambda peer: got.append(tr2.fetch("w1", "k", lambda: 1))
    tr2._fail_peer("w0")
    assert got == [1]


def test_sanitize_env_latch_toggles_debug_nans():
    import jax
    from repro.analysis import sanitize

    before = bool(jax.config.jax_debug_nans)
    sanitize.enable()
    assert sanitize.enabled() and jax.config.jax_debug_nans
    sanitize.disable()
    assert not sanitize.enabled()
    assert bool(jax.config.jax_debug_nans) is False
    if before:                      # restore whatever the session had
        sanitize.enable()
