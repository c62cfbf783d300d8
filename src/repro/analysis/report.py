"""Static-analysis report: derived features next to Table II, plus the
lint sweep.

This is the closing of the loop the auditor exists for: every Table II
kernel in this repo has *two* independent feature sources — the paper's
hand-transcribed stream counts (``core/table2.py``) and the counts the
jaxpr walker derives from the kernel's own trace (:mod:`.traffic` /
:mod:`.features`).  :func:`cross_check` pushes both through the same
ECM bridge (:func:`repro.api.registry.from_loop_features`) and compares
the resulting serial fractions ``f``:

* **exact cells** — the derived ``(reads, writes, rfo)`` must equal the
  Table II row integer-for-integer and the two ``f`` values must agree
  to ``EXACT_F_TOL``;
* **write-allocate-ambiguous cells** — the *functional* (out-of-place)
  forms of DSCAL/DAXPY carry one RFO stream the paper's in-place C
  loops do not; their ``f`` must stay within ``AMBIGUOUS_BOUND``
  (docs/known-issues.md quantifies the actual gap at 0–3%).

The measured Table II ``f`` is reported alongside as a *diagnostic*
column only: ECM-predicted vs measured ``f`` differs by design (the
model is an upper bound on overlap), so the gate compares static
against Table II **through the same model**, never against the
measurement.

CLI::

    python -m repro.analysis.report               # cross-check, CLX
    python -m repro.analysis.report --arch ROME   # another machine
    python -m repro.analysis.report --lint        # lint the repo corpus
    python -m repro.analysis.report --json        # machine-readable

``--lint`` exits non-zero when any diagnostic fires, so CI can gate on
it; the cross-check exits non-zero when any cell breaks its bound.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from typing import Callable, Sequence

from ..core.backend import HAVE_JAX
from ..core.table2 import ARCHS, TABLE2

#: |f_static - f_table| / f_table bound for write-allocate-ambiguous
#: cells (functional DSCAL/DAXPY forms); exact cells use EXACT_F_TOL.
AMBIGUOUS_BOUND = 0.15
EXACT_F_TOL = 1e-3
#: Derived flops/iter may carry a reduction-accumulator epsilon
#: (one add per block, ~1/8192 per iteration at the suite sizes).
FLOP_TOL = 0.01


@dataclasses.dataclass(frozen=True)
class Case:
    """One static-suite cell: a Table II row and how to rebuild its
    kernel as a traceable callable."""

    table_name: str                       # Table II row to reproduce
    label: str                            # display name (variant-tagged)
    build: Callable[[], tuple]            # () -> (fn, args)
    reuse: bool = True                    # layer condition on/off
    exact: bool = True                    # counts must match the table


def _map_case(name: str, n_arrays: int, *, in_place: bool = False,
              scalars: int = 1):
    def build():
        import jax.numpy as jnp
        from ..kernels.stream import LANES, map_stream
        n = LANES * 64
        s = jnp.arange(1, scalars + 1, dtype=jnp.float32) if scalars > 1 \
            else jnp.float32(3.0)
        arrays = tuple(jnp.ones(n, jnp.float32) for _ in range(n_arrays))
        return (functools.partial(map_stream, name, in_place=in_place,
                                  interpret=True),
                (s, *arrays))
    return build


def _reduce_case(name: str, n_arrays: int):
    def build():
        import jax.numpy as jnp
        from ..kernels.stream import LANES, reduce_stream
        n = LANES * 64
        arrays = tuple(jnp.ones(n, jnp.float32) for _ in range(n_arrays))
        return (functools.partial(reduce_stream, name, interpret=True),
                arrays)
    return build


def _jacobi_case(version: int):
    def build():
        import jax.numpy as jnp
        from ..kernels.jacobi import jacobi_v1, jacobi_v2
        a = jnp.ones((66, 128), jnp.float32)
        if version == 1:
            return (functools.partial(jacobi_v1, interpret=True),
                    (a, jnp.float32(0.25)))
        f = jnp.ones((66, 128), jnp.float32)
        return (functools.partial(jacobi_v2, ax=0.25, ay=0.25, b1=0.5,
                                  relax=1.0, interpret=True), (a, f))
    return build


def static_suite() -> tuple[Case, ...]:
    """Every Table II row as a (kernel builder, reuse flag, exactness)
    cell — plus the functional DSCAL/DAXPY variants whose extra RFO
    stream is the documented write-allocate ambiguity."""
    return (
        Case("DCOPY", "DCOPY", _map_case("dcopy", 1)),
        Case("DSCAL", "DSCAL (in-place)",
             _map_case("dscal", 1, in_place=True)),
        Case("DSCAL", "DSCAL (functional)", _map_case("dscal", 1),
             exact=False),
        Case("DAXPY", "DAXPY (in-place)",
             _map_case("daxpy", 2, in_place=True)),
        Case("DAXPY", "DAXPY (functional)", _map_case("daxpy", 2),
             exact=False),
        Case("ADD", "ADD", _map_case("add", 2)),
        Case("STREAM", "STREAM", _map_case("stream", 2)),
        Case("WAXPBY", "WAXPBY", _map_case("waxpby", 2, scalars=2)),
        Case("Schoenauer", "Schoenauer", _map_case("schoenauer", 3)),
        Case("vectorSUM", "vectorSUM", _reduce_case("vectorsum", 1)),
        Case("DDOT1", "DDOT1", _reduce_case("ddot1", 1)),
        Case("DDOT2", "DDOT2", _reduce_case("ddot2", 2)),
        Case("DDOT3", "DDOT3", _reduce_case("ddot3", 3)),
        Case("JacobiL2-v1", "JacobiL2-v1", _jacobi_case(1), reuse=True),
        Case("JacobiL3-v1", "JacobiL3-v1", _jacobi_case(1), reuse=False),
        Case("JacobiL2-v2", "JacobiL2-v2", _jacobi_case(2), reuse=True),
        Case("JacobiL3-v2", "JacobiL3-v2", _jacobi_case(2), reuse=False),
    )


def _bridge_f(name: str, reads: int, writes: int, rfo: int,
              flops: float, read_only: bool, arch: str) -> float:
    from ..api.registry import from_loop_features
    rs = from_loop_features(name, reads=reads, writes=writes, rfo=rfo,
                            flops_per_iter=flops, machine=arch,
                            read_only=read_only)
    return rs.spec.f[arch]


def cross_check(arch: str = "CLX", cases: Sequence[Case] | None = None
                ) -> list[dict]:
    """Derive features for every suite cell and compare against Table II
    through the shared ECM bridge.  Each row dict carries the derived
    and tabulated counts, both bridged ``f`` values, the measured ``f``
    (diagnostic), the applicable bound, and ``ok``."""
    from .features import features
    if arch not in ARCHS:
        from ..api.registry import unknown_key_error
        raise unknown_key_error("architecture", arch, ARCHS)
    rows = []
    for case in (static_suite() if cases is None else cases):
        fn, args = case.build()
        lf = features(fn, *args, name=case.label, reuse=case.reuse)
        ref = TABLE2[case.table_name]
        counts_match = (
            lf.reads == ref.reads and lf.writes == ref.writes
            and lf.rfo == ref.rfo
            and abs(lf.flops_per_iter - ref.flops_per_iter) <= FLOP_TOL)
        f_static = _bridge_f(case.label, lf.reads, lf.writes, lf.rfo,
                             lf.flops_per_iter, lf.read_only, arch)
        f_table = _bridge_f(case.table_name, ref.reads, ref.writes,
                            ref.rfo, ref.flops_per_iter, ref.read_only,
                            arch)
        f_err = abs(f_static - f_table) / f_table
        bound = EXACT_F_TOL if case.exact else AMBIGUOUS_BOUND
        ok = f_err <= bound and (counts_match or not case.exact)
        rows.append({
            "label": case.label, "table": case.table_name, "arch": arch,
            "exact": case.exact, "reuse": case.reuse,
            "static": {"reads": lf.reads, "writes": lf.writes,
                       "rfo": lf.rfo,
                       "flops": round(lf.flops_per_iter, 4)},
            "table2": {"reads": ref.reads, "writes": ref.writes,
                       "rfo": ref.rfo, "flops": ref.flops_per_iter},
            "counts_match": counts_match,
            "f_static": f_static, "f_table_ecm": f_table,
            "f_err": f_err, "bound": bound,
            "f_measured": ref.f.get(arch),
            "ok": ok,
        })
    return rows


# ---------------------------------------------------------------------------
# Lint corpus: the repo's own kernels and plans (false-positive guard)
# ---------------------------------------------------------------------------


def lint_corpus() -> list:
    """Lint every in-repo traceable kernel plus a compiled batch plan, a
    placed-batch plan, and a packed grid.  The repo's own artifacts
    must come back clean — any diagnostic here is either a real
    regression or a linter false positive, and both block CI."""
    # Note .lint the module, not the package-level lint() dispatcher —
    # the function shadows the submodule on the package namespace.
    from .lint import lint_callable, lint_grid, lint_plan
    diags = []
    for case in static_suite():
        fn, args = case.build()
        diags += lint_callable(fn, *args, name=case.label)

    import jax.numpy as jnp
    from ..kernels.rmsnorm import rmsnorm
    x = jnp.ones((64, 128), jnp.float32)
    w = jnp.ones((128,), jnp.float32)
    diags += lint_callable(functools.partial(rmsnorm, interpret=True), x, w,
                           name="rmsnorm")

    from .. import api
    batch = api.ScenarioBatch([
        api.Scenario.on("CLX").run("DCOPY", 12).run("DDOT2", 8),
        api.Scenario.on("CLX").run("STREAM", 10).run("DDOT1", 6),
    ])
    diags += lint_plan(api.compile(batch))

    from ..core import topology
    from ..core.sharing import Group
    topo = topology.preset("CLX-2S")
    d0, d1 = topo.domain_names[:2]
    grid = topology.pack_placed(topo, [
        [topology.Placed(Group(n=4, f=0.33, bs=102.4), d0)],
        [topology.Placed(Group(n=2, f=0.5, bs=102.4), d0),
         topology.Placed(Group(n=2, f=0.5, bs=102.4), d1)],
    ])
    diags += lint_grid(grid)
    return diags


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _fmt_counts(c: dict) -> str:
    return f"R{c['reads']} W{c['writes']} RFO{c['rfo']} F{c['flops']:g}"


def main(argv: Sequence[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.report",
        description="static traffic analysis vs Table II, and the "
                    "trace-contract lint sweep")
    parser.add_argument("--arch", default="CLX", choices=ARCHS,
                        help="architecture for the f cross-check")
    parser.add_argument("--lint", action="store_true",
                        help="lint the in-repo kernel/plan corpus "
                             "instead of cross-checking")
    parser.add_argument("--json", action="store_true",
                        help="emit machine-readable JSON")
    args = parser.parse_args(argv)

    if not HAVE_JAX:
        print("jax is not available: static analysis needs a tracer",
              file=sys.stderr)
        return 2

    if args.lint:
        diags = lint_corpus()
        if args.json:
            print(json.dumps([dataclasses.asdict(d) for d in diags],
                             indent=2))
        else:
            for d in diags:
                print(d)
            print(f"{len(diags)} diagnostic(s) over the repo corpus")
        return 1 if diags else 0

    rows = cross_check(args.arch)
    if args.json:
        print(json.dumps(rows, indent=2))
    else:
        hdr = (f"{'kernel':<20} {'static':>22} {'Table II':>20} "
               f"{'f_static':>9} {'f_table':>8} {'f_meas':>7} "
               f"{'err':>7}  status")
        print(f"static cross-check on {args.arch} "
              f"(exact tol {EXACT_F_TOL:g}, ambiguous bound "
              f"{AMBIGUOUS_BOUND:.0%})")
        print(hdr)
        for r in rows:
            meas = r["f_measured"]
            print(f"{r['label']:<20} {_fmt_counts(r['static']):>22} "
                  f"{_fmt_counts(r['table2']):>20} "
                  f"{r['f_static']:>9.4f} {r['f_table_ecm']:>8.4f} "
                  f"{meas if meas is None else format(meas, '7.3f')} "
                  f"{r['f_err']:>6.2%}  "
                  f"{'ok' if r['ok'] else 'FAIL'}"
                  f"{'' if r['exact'] else ' (ambiguous)'}")
    return 0 if all(r["ok"] for r in rows) else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
