"""Jaxpr-level audit of the registered jit entry points + RecompileGuard.

``audit_jaxprs`` traces every entry in ``repro.analysis.registry`` through
its real jit wrapper and walks the ClosedJaxpr (sub-jaxprs included — scan/
while/cond/pjit/shard_map/pallas bodies) for:

  * forbidden primitives — host callbacks (``pure_callback``,
    ``io_callback``, ``debug_callback``/debug prints, outfeed/infeed): a
    host sync inside the round body silently serializes the fleet;
  * f64 / complex128 avals — an accidental x64 promotion doubles the hot
    path's bandwidth and breaks cross-backend bit-reproducibility;
  * weak-typed ENTRY OUTPUTS — a weak output re-promotes downstream and
    makes the abstract signature depend on python scalar history;
  * non-integer (dynamic) shape dims — every entry must be fully
    shape-monomorphic or the compile cache can never converge.

``RecompileGuard`` is the runtime half of the compile-discipline story: it
snapshots each entry's jit cache size (count of compiled abstract
signatures) on enter and asserts at most ``max_new`` new signatures
appeared on exit.  Benchmarks enter it after warmup, so a steady-state
recompile (shape churn, a non-static kwarg, an epoch leaking into the
signature) fails fast instead of showing up as a 10x wall regression.
"""
from __future__ import annotations

from repro.analysis.lint import Violation

__all__ = ["FORBIDDEN_PRIMITIVES", "audit_closed_jaxpr", "audit_jaxprs",
           "RecompileError", "RecompileGuard"]

# Primitives that re-enter python or touch the host from inside a trace.
FORBIDDEN_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call", "infeed", "outfeed",
}

_BANNED_DTYPES = ("float64", "complex128")


def _sub_jaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def _from(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from _from(x)
        elif isinstance(v, dict):
            for x in v.values():
                yield from _from(x)

    for v in params.values():
        yield from _from(v)


def _walk(jaxpr, visit, seen: set[int]):
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, visit, seen)


def audit_closed_jaxpr(name: str, closed) -> list[Violation]:
    """Audit one entry's ClosedJaxpr; findings report as rule ``JAXPR``."""
    out: list[Violation] = []
    where = f"<jit:{name}>"

    def visit(eqn):
        prim = eqn.primitive.name
        if prim in FORBIDDEN_PRIMITIVES:
            out.append(Violation(
                "JAXPR", where, 0,
                f"forbidden primitive `{prim}` — host callbacks/debug "
                "prints must not reach a registered serving entry"))
        for var in tuple(eqn.outvars) + tuple(eqn.invars):
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) in _BANNED_DTYPES:
                out.append(Violation(
                    "JAXPR", where, 0,
                    f"{dtype} aval at primitive `{prim}` — unexpected x64 "
                    "promotion in the hot path"))
            shape = getattr(aval, "shape", None)
            if shape is not None and not all(
                    isinstance(d, int) for d in shape):
                out.append(Violation(
                    "JAXPR", where, 0,
                    f"dynamic shape {shape} at primitive `{prim}` — entries "
                    "must be shape-monomorphic"))

    _walk(closed.jaxpr, visit, set())
    for i, aval in enumerate(closed.out_avals):
        if getattr(aval, "weak_type", False):
            out.append(Violation(
                "JAXPR", where, 0,
                f"output {i} is weak-typed ({aval}) — anneal with an "
                "explicit dtype before returning"))
    # duplicate findings (same aval flowing through many eqns) collapse
    return sorted(set(out), key=lambda v: (v.path, v.msg))


def audit_jaxprs(entries=None) -> list[Violation]:
    """Trace + audit every registered entry (see ``registry.entries``)."""
    from repro.analysis import registry
    if entries is None:
        entries = registry.entries()
    out: list[Violation] = []
    for e in entries:
        args, kwargs = e.example()
        traced = e.fn.trace(*args, **kwargs)
        out.extend(audit_closed_jaxpr(e.name, traced.jaxpr))
    return out


# ---------------------------------------------------------------------------
# RecompileGuard
# ---------------------------------------------------------------------------

class RecompileError(AssertionError):
    """A registered jit entry compiled more new signatures than allowed."""


class RecompileGuard:
    """Assert the serving loop's compile caches stay (near-)frozen.

    ``entries`` maps name -> jitted callable (anything exposing
    ``_cache_size()``); defaults to the module-level registry.  On exit, any
    entry that gained more than ``max_new`` compiled signatures raises
    ``RecompileError`` naming the offenders and their deltas.

        with RecompileGuard.for_engine(eng, max_new=1):
            for _ in range(steady_ticks):
                eng.tick()

    ``max_new=1`` encodes the acceptance contract: each entry compiles at
    most once after warmup (a genuinely new shape class may appear once;
    per-tick churn trips immediately).
    """

    def __init__(self, entries: dict | None = None, *, max_new: int = 0,
                 label: str = ""):
        if entries is None:
            from repro.analysis.registry import jit_entry_fns
            entries = jit_entry_fns()
        self.entries = dict(entries)
        self.max_new = max_new
        self.label = label
        self._base: dict[str, int] | None = None

    @classmethod
    def for_engine(cls, eng, *, max_new: int = 0, label: str = ""):
        """Registry entries plus — for a sharded fleet — the engine's
        CURRENT per-mesh shard_map jits."""
        from repro.analysis.registry import jit_entry_fns
        entries = jit_entry_fns()
        if hasattr(eng, "_fns"):           # ShardedServingEngine
            (f_admit, f_rank, f_rank_seg, f_advance, f_admit_tiles,
             f_rank_tiles) = eng._fns()
            entries["fleet.admit@shard_map"] = f_admit
            entries["fleet.rank_advance@shard_map"] = f_rank
            entries["fleet.rank_advance_seg@shard_map"] = f_rank_seg
            entries["fleet.advance@shard_map"] = f_advance
            entries["fleet.admit_tiles@shard_map"] = f_admit_tiles
            entries["fleet.rank_advance_tiles@shard_map"] = f_rank_tiles
        return cls(entries, max_new=max_new, label=label)

    @staticmethod
    def _size(fn) -> int:
        return int(fn._cache_size())

    def __enter__(self) -> "RecompileGuard":
        self._base = {n: self._size(f) for n, f in self.entries.items()}
        return self

    def new_compiles(self) -> dict[str, int]:
        assert self._base is not None, "guard not entered"
        return {n: self._size(f) - self._base[n]
                for n, f in self.entries.items()}

    def check(self) -> None:
        bad = {n: d for n, d in self.new_compiles().items()
               if d > self.max_new}
        if bad:
            tag = f" [{self.label}]" if self.label else ""
            detail = ", ".join(f"{n}: +{d}" for n, d in sorted(bad.items()))
            raise RecompileError(
                f"steady-state recompiles{tag} (allowed {self.max_new} new "
                f"signature(s) per entry): {detail}")

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.check()
