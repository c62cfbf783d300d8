"""Trace-contract linter: static audit of the substrate's invariants.

The batched substrate (``core/backend.py`` + compiled plans) relies on
contracts that nothing enforced until now — they fail silently, as
recompiles or wrong-but-plausible numbers, never as exceptions.  This
module checks them statically and reports *suggestion-bearing*
diagnostics in the registry's error style:

``weak-const``
    A 0-d constant is baked into the traced closure.  Every rebind of
    the closure (a Python scalar captured from an outer scope, a
    freshly-built 0-d array) re-traces and re-compiles; passed as an
    argument it would be a stable tracer instead.

``bucket-bypass``
    A jit boundary is traced at a large, non-power-of-two leading
    shape, bypassing the substrate's bucket policy
    (:func:`repro.core.backend.bucket`): a sweep over nearby sizes
    compiles one executable per size instead of O(log B) total.  On a
    plan, the check is that its cached ``bucket`` still matches the
    policy (drift guard for subclasses / deserialized plans).

``f64-promotion``
    Under x64, a strongly-typed float64 scalar (``np.float64``, a 0-d
    f64 array) silently promotes a float32 kernel to float64 — double
    the traffic, and a different executable than the f32 trace.  On a
    plan, the packed solver arrays must already be float64: float32
    arrays are promoted on *every* run.

``padding-escape``
    A placed grid's padding lanes must stay exactly neutral
    (``n = f = b_s = 0`` wherever ``mask`` is False) and its occupied
    lanes finite — a swap/broadcast that writes live numbers into
    masked lanes corrupts every masked reduction downstream.

Entry points: :func:`lint_callable` (trace-level rules),
:func:`lint_plan` / :func:`lint_grid` (compiled-artifact rules), and
the :func:`lint` dispatcher.  ``python -m repro.analysis.report
--lint`` runs the whole catalog over the in-repo kernels and plans.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from ..core import backend as backend_mod
from ..core import topology as topology_mod
from ..core.backend import HAVE_JAX

if HAVE_JAX:
    import jax

#: Rule catalog: identifier -> one-line description (docs/analysis.md
#: renders this table; ``rules=`` arguments validate against it).
RULES = {
    "weak-const": "0-d constant baked into a traced closure "
                  "(re-traces on every rebind)",
    "bucket-bypass": "jit boundary traced at a large non-power-of-two "
                     "leading shape (one executable per size)",
    "f64-promotion": "silent float32 -> float64 promotion under x64, "
                     "or non-float64 packed solver arrays",
    "padding-escape": "placed-grid padding carries live numbers outside "
                      "its mask (or masked-in cells are non-finite)",
}

#: Leading sizes below this never trip ``bucket-bypass``: tiny shapes
#: re-trace cheaply and are usually structural, not batch axes.
MIN_BUCKET_DIM = 64
MIN_BUCKET_ELEMS = 1024


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One linter finding, registry-style: what broke, where, and the
    concrete fix."""

    rule: str          # key of RULES
    severity: str      # "error" | "warning"
    target: str        # what was linted ("map_stream", "plan[batch]")
    message: str
    suggestion: str

    def __str__(self) -> str:
        return (f"[{self.rule}] {self.target}: {self.message} "
                f"— fix: {self.suggestion}")


def _check_rules(rules: Iterable[str] | None) -> tuple[str, ...]:
    if rules is None:
        return tuple(RULES)
    rules = tuple(rules)
    for r in rules:
        if r not in RULES:
            from ..api.registry import unknown_key_error
            raise unknown_key_error("lint rule", r, tuple(RULES))
    return rules


def _iter_jaxprs(jaxpr):
    """The jaxpr and every sub-jaxpr reachable from it (call-like
    primitives, control flow, pallas kernel bodies)."""
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    stack.append(getattr(sub, "jaxpr", sub))
            for branch in eqn.params.get("branches", ()) or ():
                stack.append(getattr(branch, "jaxpr", branch))


# ---------------------------------------------------------------------------
# Callable rules
# ---------------------------------------------------------------------------


def _lint_weak_const(closed, target: str) -> list[Diagnostic]:
    out = []
    for i, const in enumerate(closed.consts):
        shape = getattr(const, "shape", None)
        if shape == ():
            val = np.asarray(const).item()
            out.append(Diagnostic(
                rule="weak-const", severity="warning", target=target,
                message=f"0-d constant ({val!r}) is baked into the "
                        f"traced closure (const #{i}); rebinding the "
                        f"closure re-traces and re-compiles",
                suggestion="pass the scalar as a traced argument (or "
                           "bind it with functools.partial of a "
                           "hashable static value)"))
    return out


def _lint_bucket_bypass(closed, target: str) -> list[Diagnostic]:
    out = []
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "jit":
                continue
            for iv in eqn.invars:
                aval = getattr(iv, "aval", None)
                shape = getattr(aval, "shape", ())
                if not shape:
                    continue
                lead = int(shape[0])
                size = int(math.prod(shape))
                if (lead >= MIN_BUCKET_DIM and size >= MIN_BUCKET_ELEMS
                        and backend_mod.bucket(lead) != lead):
                    out.append(Diagnostic(
                        rule="bucket-bypass", severity="warning",
                        target=target,
                        message=f"jit boundary traced at leading shape "
                                f"{lead} (operand {tuple(shape)}); a "
                                f"sweep over nearby sizes compiles one "
                                f"executable per size",
                        suggestion=f"pad the leading axis to the "
                                   f"substrate bucket "
                                   f"(repro.core.backend.bucket({lead})"
                                   f" = {backend_mod.bucket(lead)}, "
                                   f"pad_rows) and mask/slice back"))
    return out


def _lint_f64_promotion(fn, args, target: str) -> list[Diagnostic]:
    if not HAVE_JAX:
        return []
    with backend_mod.x64():
        closed = jax.make_jaxpr(fn)(*args)
    out = []
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = getattr(eqn.invars[0], "aval", None)
            dst = eqn.params.get("new_dtype")
            if src is None or dst is None:
                continue
            if str(src.dtype) == "float32" and str(dst) == "float64":
                out.append(Diagnostic(
                    rule="f64-promotion", severity="warning",
                    target=target,
                    message="a strongly-typed float64 scalar/array in "
                            "the trace promotes float32 data to "
                            "float64 under x64 (double the traffic, a "
                            "second executable)",
                    suggestion="use a Python float (weak type) or cast "
                               "the constant to the kernel dtype "
                               "(jnp.float32(...)) before tracing"))
                break  # one diagnostic per trace is enough signal
        if out:
            break
    return out


def lint_callable(fn: Callable, *args: Any, name: str | None = None,
                  rules: Iterable[str] | None = None) -> list[Diagnostic]:
    """Run the trace-level rules over ``fn(*args)`` (traced, never
    executed).  Unknown rule names fail with a suggestion."""
    active = _check_rules(rules)
    if not HAVE_JAX:
        return []
    target = name or getattr(fn, "__name__", None) or \
        getattr(getattr(fn, "func", None), "__name__", "callable")
    closed = jax.make_jaxpr(fn)(*args)
    out: list[Diagnostic] = []
    if "weak-const" in active:
        out += _lint_weak_const(closed, target)
    if "bucket-bypass" in active:
        out += _lint_bucket_bypass(closed, target)
    if "f64-promotion" in active:
        out += _lint_f64_promotion(fn, args, target)
    return out


# ---------------------------------------------------------------------------
# Plan / grid rules
# ---------------------------------------------------------------------------


def lint_grid(grid: topology_mod.PlacedGrid, *, target: str = "grid",
              rules: Iterable[str] | None = None) -> list[Diagnostic]:
    """``padding-escape`` over one packed ``(B, D, K)`` grid."""
    active = _check_rules(rules)
    out: list[Diagnostic] = []
    if "padding-escape" not in active:
        return out
    mask = np.asarray(grid.mask)
    for field in ("n", "f", "bs"):
        arr = np.asarray(getattr(grid, field))
        escaped = (~mask) & (arr != 0)
        if escaped.any():
            b, d, k = (int(x[0]) for x in np.nonzero(escaped))
            out.append(Diagnostic(
                rule="padding-escape", severity="error", target=target,
                message=f"padding lane (b={b}, d={d}, k={k}) carries "
                        f"{field} = {arr[b, d, k]!r} outside the "
                        f"occupancy mask; masked reductions downstream "
                        f"will absorb it",
                suggestion="re-pack with repro.core.topology."
                           "pack_placed (padding must stay exactly "
                           "zero), or zero the swapped array under "
                           "~mask before run()"))
    live = np.asarray(grid.f, dtype=float), np.asarray(grid.bs, dtype=float)
    for field, arr in zip(("f", "bs"), live):
        bad = mask & ~np.isfinite(arr)
        if bad.any():
            b, d, k = (int(x[0]) for x in np.nonzero(bad))
            out.append(Diagnostic(
                rule="padding-escape", severity="error", target=target,
                message=f"occupied cell (b={b}, d={d}, k={k}) has "
                        f"non-finite {field} = {arr[b, d, k]!r}",
                suggestion="check the spec/calibration that produced "
                           "this cell; the solvers assume finite "
                           "inputs on every masked-in lane"))
    return out


def _lint_plan_arrays(arrays: dict[str, np.ndarray], target: str
                      ) -> list[Diagnostic]:
    out = []
    for field, arr in arrays.items():
        if arr.dtype != np.float64:
            out.append(Diagnostic(
                rule="f64-promotion", severity="warning", target=target,
                message=f"packed solver array {field!r} has dtype "
                        f"{arr.dtype}; the solvers compute in float64, "
                        f"so every run() pays a promotion copy",
                suggestion="pack float64 once (np.asarray(..., "
                           "np.float64)) instead of promoting per run"))
    return out


def lint_plan(plan, *, rules: Iterable[str] | None = None
              ) -> list[Diagnostic]:
    """Run the compiled-artifact rules over one :class:`repro.api.Plan`.

    Scalar / placed / simulate plans carry no packed solver arrays or
    padding masks, so they lint clean by construction."""
    from ..api import plan as plan_mod
    active = _check_rules(rules)
    out: list[Diagnostic] = []
    if isinstance(plan, plan_mod.BatchPlan):
        target = "plan[batch]"
        if "f64-promotion" in active:
            out += _lint_plan_arrays(
                {"n": plan.n, "f": plan.f, "bs": plan.bs}, target)
        if "bucket-bypass" in active:
            expect = (backend_mod.bucket(len(plan)), plan.n.shape[1])
            if tuple(plan.bucket) != expect:
                out.append(Diagnostic(
                    rule="bucket-bypass", severity="warning",
                    target=target,
                    message=f"plan.bucket = {tuple(plan.bucket)} no "
                            f"longer matches the substrate policy "
                            f"{expect}; its jit-cache entry will not "
                            f"be shared",
                    suggestion="recompile the plan (api.compile) "
                               "instead of carrying one across a "
                               "bucket-policy change"))
    elif isinstance(plan, plan_mod.PlacedBatchPlan):
        target = "plan[placed-batch]"
        if "f64-promotion" in active:
            out += _lint_plan_arrays(
                {"grid.n": plan.grid.n, "grid.f": plan.grid.f,
                 "grid.bs": plan.grid.bs}, target)
        if "padding-escape" in active:
            out += lint_grid(plan.grid, target=target,
                             rules=("padding-escape",))
        if "bucket-bypass" in active:
            B, D, K = plan.grid.n.shape
            expect = (backend_mod.bucket(B * D), K)
            if tuple(plan.bucket) != expect:
                out.append(Diagnostic(
                    rule="bucket-bypass", severity="warning",
                    target=target,
                    message=f"plan.bucket = {tuple(plan.bucket)} no "
                            f"longer matches the substrate policy "
                            f"{expect}",
                    suggestion="recompile the plan (api.compile)"))
    return out


def lint(obj, *args: Any, **kwargs: Any) -> list[Diagnostic]:
    """Dispatch: a :class:`PlacedGrid` or :class:`Plan` goes to the
    artifact rules, anything callable to the trace rules."""
    from ..api import plan as plan_mod
    if isinstance(obj, topology_mod.PlacedGrid):
        return lint_grid(obj, **kwargs)
    if isinstance(obj, plan_mod.Plan):
        return lint_plan(obj, **kwargs)
    if callable(obj):
        return lint_callable(obj, *args, **kwargs)
    raise TypeError(
        f"cannot lint {type(obj).__name__}: expected a callable, a "
        f"Plan, or a PlacedGrid")
