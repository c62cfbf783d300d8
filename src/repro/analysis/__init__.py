"""Static kernel-feature analysis: jaxpr traffic auditing and the
trace-contract linter.

The paper's model needs exactly two things per kernel — its memory
streams and its flops per iteration.  This package derives both from
the kernel's own jaxpr instead of a hand-transcribed table:

  traffic  — :func:`audit`: walk the closed jaxpr (through
             pallas_call / scan / while / jit / cond), classify every
             buffer as a streaming load, store, RFO write-allocate,
             resident operand, or accumulator, and count flops.
  features — :func:`features` / :func:`derive`: collapse a
             :class:`TrafficAudit` into per-iteration
             :class:`LoopFeatures` (reads/writes/rfo/flops — the Table
             II row shape), with layer-condition reuse and a
             write-allocate policy toggle.
  lint     — :func:`lint`: trace-contract diagnostics (weak consts
             baked into traces, bucket-policy bypass, silent f32→f64
             promotion, placed-grid padding escapes), in the
             registry's suggestion-bearing error style.
  report   — ``python -m repro.analysis.report``: the derived features
             next to Table II and the calibrated values, plus the
             repo-corpus lint sweep CI gates on.

The features feed the resolution chain as the ``"static"`` rung:
``api.from_static_analysis(fn, args)`` /
``KernelSpec.from_static_analysis`` — same ECM bridge as
``from_loop_features``, no measurement and no transcription.
"""

from .features import LoopFeatures, derive, features
from .lint import (RULES, Diagnostic, lint, lint_callable, lint_grid,
                   lint_plan)
from .traffic import Stream, TrafficAudit, audit

_REPORT_NAMES = ("cross_check", "lint_corpus", "static_suite")


def __getattr__(name: str):
    # Lazy: importing .report at package-import time shadows
    # ``python -m repro.analysis.report`` (runpy warns about the
    # double-import) — resolve its names on first use instead.
    if name in _REPORT_NAMES:
        from . import report
        return getattr(report, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "audit", "TrafficAudit", "Stream",
    "features", "derive", "LoopFeatures",
    "lint", "lint_callable", "lint_plan", "lint_grid", "Diagnostic",
    "RULES",
    "cross_check", "lint_corpus", "static_suite",
]
