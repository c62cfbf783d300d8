"""Batched estimators: recover ``(f, b_s)`` from measured scaling curves.

The forward model is the paper's own (Eqs. 1–5, via
:func:`repro.core.sharing.utilization_curve`): a homogeneous run of a
kernel with request fraction ``f`` and saturated bandwidth ``b_s`` attains

    b(n) = b_s · U(n; f)

aggregate bandwidth on ``n`` cores, where ``U`` is the sub-saturation
utilization law — ``min(1, n·f)`` for the ideal queue interface (which is
also what the memsim instrument realizes) or the latency-penalty
recursion for real hardware.  Fitting inverts this curve: ``b_s`` from the
plateau, ``f`` from the single-core point and the knee position.

The estimator is a *profile least squares* over a fixed ``f`` grid: for
every candidate ``f`` the optimal ``b_s`` is closed-form (the model is
linear in ``b_s``), so the residual profile over the grid is computed for
**all (kernel, arch, seed) cells at once** — one vectorized numpy pass or
one ``jax.vmap``-ped, jitted pass, no per-cell Python loop — followed by
a sub-grid refinement of the winning ``f`` inside its bracket.  The
refinement is jacobian-based Gauss–Newton over the identical vectorized
residual (analytic ``∂U/∂f`` from
:func:`repro.core.sharing.utilization_curve_grad` on numpy, ``jax.jvp``
on jax): quadratic convergence instead of the retired golden section's
fixed φ-rate bracket shrink, at a third of the residual evaluations,
plus *free* curvature-based confidence intervals from the Gauss–Newton
normal matrix (``ScalingFit.f_sigma`` / ``bs_sigma``).  Seed ensembles
aggregate into medians with percentile confidence intervals
(:func:`aggregate_ensemble`), and :func:`calibrated_specs` materializes
the result as first-class :class:`repro.core.table2.KernelSpec` objects
that ``Group.of``, the topology solver, and the desync engines consume
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Mapping, Sequence

import numpy as np

from ..core import backend as backend_mod
from ..core.backend import HAVE_JAX
from ..core.sharing import (UTILIZATION_MODES, solve_batch,
                            utilization_curve, utilization_curve_grad)
from ..core.table2 import TABLE2, KernelSpec
from ..obs import metrics
from ..obs import trace as trace_mod
from .traces import PairTrace, ScalingTrace, TraceSet

#: Default candidate grid: log-spaced so relative resolution is uniform
#: across the physical range of ``f`` (~0.08 on CLX stencils to ~1 on Rome).
DEFAULT_F_GRID = np.geomspace(0.01, 1.0, 512)


def forward_bandwidth(n, f, bs, *, utilization: str = "queue",
                      p0_factor: float = 0.5) -> np.ndarray:
    """The Eq. 1–5 forward model: aggregate bandwidth of a homogeneous run
    at each core count ``n`` (broadcasts like numpy)."""
    u = utilization_curve(n, f, mode=utilization, p0_factor=p0_factor)
    return np.asarray(bs) * u


@dataclasses.dataclass(frozen=True)
class ScalingFit:
    """Per-cell ``(f, b_s)`` estimates for a batch of scaling traces.

    ``f_sigma`` / ``bs_sigma`` are per-cell curvature (1σ) uncertainties
    from the Gauss–Newton normal matrix at the optimum — the local
    sensitivity of the fit to measurement noise, complementary to the
    cross-seed percentile CIs of :func:`aggregate_ensemble`.  A cell
    whose curve never leaves saturation has ``f_sigma = inf`` (the knee
    position is unidentifiable from a flat plateau).  ``n_evals`` counts
    residual evaluations per cell (grid profile + refinement), the
    quantity the Gauss–Newton migration reduced; ``refine`` records which
    refiner produced the numbers.
    """

    f: np.ndarray          # (C,) fitted request fractions
    bs: np.ndarray         # (C,) fitted saturated bandwidths [GB/s]
    rss: np.ndarray        # (C,) residual sum of squares at the optimum
    traces: tuple[ScalingTrace, ...]
    utilization: str
    backend: str
    f_sigma: np.ndarray | None = None    # (C,) curvature 1σ of f
    bs_sigma: np.ndarray | None = None   # (C,) curvature 1σ of b_s
    refine: str = "gauss-newton"
    n_evals: int = 0

    def __len__(self) -> int:
        return len(self.traces)

    def cells(self) -> dict[tuple[str, str], list[int]]:
        """Indices grouped by (kernel, arch) — one entry per seed."""
        out: dict[tuple[str, str], list[int]] = {}
        for i, tr in enumerate(self.traces):
            out.setdefault((tr.kernel, tr.arch), []).append(i)
        return out


@dataclasses.dataclass(frozen=True)
class CalibratedValue:
    """Seed-ensemble estimate of one model input: median + percentile CI.

    ``sigma`` is the median per-seed *curvature* uncertainty (1σ, from
    the Gauss–Newton normal matrix) — how sharply the residual pins the
    value within one trace, vs. the ``lo``/``hi`` percentile band which
    measures spread *across* seeds.  0.0 when the fit carried no
    curvature information (a :class:`ScalingFit` constructed without
    sigmas)."""

    value: float
    lo: float
    hi: float
    n_seeds: int
    sigma: float = 0.0

    @property
    def spread(self) -> float:
        return self.hi - self.lo


# ---------------------------------------------------------------------------
# The batched profile-least-squares pass
# ---------------------------------------------------------------------------

_EPS = 1e-30


def _profile_rss_np(n, y, mask, f_grid, utilization, p0_factor):
    """Residual profile over the ``f`` grid for all cells at once.

    ``n, y, mask``: ``(C, N)`` padded cell arrays; ``f_grid``: ``(F,)``.
    Returns ``(rss (C, F), bs_star (C, F))`` where ``bs_star`` is the
    closed-form optimal ``b_s`` at each candidate ``f``.
    """
    u = utilization_curve(n[:, None, :], f_grid[None, :, None],
                          mode=utilization, p0_factor=p0_factor)  # (C,F,N)
    u = np.where(mask[:, None, :], u, 0.0)
    ym = np.where(mask[:, None, :], y[:, None, :], 0.0)
    num = (ym * u).sum(axis=-1)
    den = np.maximum((u * u).sum(axis=-1), _EPS)
    bs_star = num / den                                         # (C, F)
    resid = ym - bs_star[..., None] * u
    rss = (np.where(mask[:, None, :], resid, 0.0) ** 2).sum(axis=-1)
    return rss, bs_star


_INVPHI = (np.sqrt(5.0) - 1.0) / 2.0
_REFINE_ITERS = 32  # bracket shrinks by φ⁻¹ per iter: ~1e-6 of a grid step
_GN_ITERS = 12      # Gauss–Newton is quadratic near the optimum; 12
                    # trust-clipped steps inside the grid bracket land at
                    # machine precision with a third of golden's evals

#: The supported sub-grid refiners.  "golden" is a deprecated escape
#: hatch kept so the Gauss–Newton re-baseline is reversible.
REFINE_METHODS = ("gauss-newton", "golden")


def _refine_evals(refine: str, n_grid: int) -> int:
    """Residual evaluations per cell: the grid profile plus what the
    refiner spends (jacobian evaluations count as one residual pass —
    the derivative rides along analytically)."""
    if refine == "golden":
        return n_grid + 2 + 2 * _REFINE_ITERS + 1
    return n_grid + 2 * _GN_ITERS + 1


def _rss_at_np(n, y, mask, f, utilization, p0_factor):
    """RSS and closed-form ``b_s`` at one candidate ``f`` per cell
    (``f`` shape ``(C,)``)."""
    u = utilization_curve(n, f[:, None], mode=utilization,
                          p0_factor=p0_factor)
    u = np.where(mask, u, 0.0)
    ym = np.where(mask, y, 0.0)
    bs = (ym * u).sum(axis=-1) / np.maximum((u * u).sum(axis=-1), _EPS)
    rss = (np.where(mask, ym - bs[:, None] * u, 0.0) ** 2).sum(axis=-1)
    return rss, bs


def _refine_golden_np(n, y, mask, a, b, utilization, p0_factor):
    """Golden-section refinement inside the winning grid bracket
    ``[a, b]`` — vectorized over cells, fixed iteration count.
    Deprecated: the default refiner is :func:`_refine_gn_np`."""
    c = b - _INVPHI * (b - a)
    d = a + _INVPHI * (b - a)
    rc, _ = _rss_at_np(n, y, mask, c, utilization, p0_factor)
    rd, _ = _rss_at_np(n, y, mask, d, utilization, p0_factor)
    for _ in range(_REFINE_ITERS):
        left = rc < rd
        a = np.where(left, a, c)
        b = np.where(left, d, b)
        c = b - _INVPHI * (b - a)
        d = a + _INVPHI * (b - a)
        rc, _ = _rss_at_np(n, y, mask, c, utilization, p0_factor)
        rd, _ = _rss_at_np(n, y, mask, d, utilization, p0_factor)
    return 0.5 * (a + b)


def _gn_terms_np(n, y, mask, f, utilization, p0_factor):
    """One Gauss–Newton linearization of the *profiled* residual
    ``r(f) = y − b_s*(f)·u(f)`` at ``f`` (``(C,)``), with ``b_s*``'s own
    ``f``-dependence carried through (variable projection).  Returns
    ``(step, rss, bs)`` where ``step`` solves the 1-d normal equation
    ``(Σ (dm)²)·δ = Σ dm·r`` for the model derivative ``dm = ∂(b_s*·u)/∂f``.
    """
    u, du = utilization_curve_grad(n, f[:, None], mode=utilization,
                                   p0_factor=p0_factor)
    u = np.where(mask, u, 0.0)
    du = np.where(mask, du, 0.0)
    ym = np.where(mask, y, 0.0)
    su2 = (u * u).sum(axis=-1)
    syu = (ym * u).sum(axis=-1)
    bs = syu / np.maximum(su2, _EPS)
    dbs = ((ym * du).sum(axis=-1) * su2
           - syu * 2.0 * (u * du).sum(axis=-1)) \
        / np.maximum(su2 * su2, _EPS)
    dm = dbs[:, None] * u + bs[:, None] * du
    r = ym - bs[:, None] * u
    rss = (r * r).sum(axis=-1)
    step = (dm * r).sum(axis=-1) / np.maximum((dm * dm).sum(axis=-1),
                                              _EPS)
    return step, rss, bs


def _refine_gn_np(n, y, mask, f0, a, b, utilization, p0_factor):
    """Trust-clipped Gauss–Newton on the profiled residual, seeded at the
    grid argmin and confined to its bracket ``[a, b]`` (the same bracket
    golden section searched, so the two refiners converge to the same
    local optimum).  A step that fails to reduce the RSS is rejected and
    the trust radius quartered — the deterministic safeguard both the
    numpy and jax implementations share, so backends agree."""
    f = f0.copy()
    rss, _ = _rss_at_np(n, y, mask, f, utilization, p0_factor)
    trust = b - a
    for _ in range(_GN_ITERS):
        step, _, _ = _gn_terms_np(n, y, mask, f, utilization, p0_factor)
        cand = np.clip(f + np.clip(step, -trust, trust), a, b)
        rss_c, _ = _rss_at_np(n, y, mask, cand, utilization, p0_factor)
        ok = rss_c <= rss
        f = np.where(ok, cand, f)
        rss = np.where(ok, rss_c, rss)
        trust = np.where(ok, trust, 0.25 * trust)
    return f


def _curvature_np(n, y, mask, f, bs, rss, utilization, p0_factor):
    """Curvature (1σ) uncertainties from the two-parameter Gauss–Newton
    normal matrix at the optimum: ``J = [b_s·∂U/∂f, U]`` per sample,
    ``cov = σ²·(JᵀJ)⁻¹`` with ``σ² = rss/(m−2)``.  A flat (all-saturated)
    curve has no ``f`` information → ``f_sigma = inf`` and ``b_s``
    falls back to its one-parameter variance."""
    u, du = utilization_curve_grad(n, f[:, None], mode=utilization,
                                   p0_factor=p0_factor)
    u = np.where(mask, u, 0.0)
    du = np.where(mask, du, 0.0)
    j1 = bs[:, None] * du
    a11 = (j1 * j1).sum(axis=-1)
    a12 = (j1 * u).sum(axis=-1)
    a22 = (u * u).sum(axis=-1)
    det = a11 * a22 - a12 * a12
    m_eff = mask.sum(axis=-1)
    s2 = rss / np.maximum(m_eff - 2, 1)
    ok = det > 1e-12 * np.maximum(a11 * a22, _EPS)
    with np.errstate(divide="ignore", invalid="ignore"):
        f_sigma = np.where(ok, np.sqrt(np.maximum(s2 * a22, 0.0)
                                       / np.where(ok, det, 1.0)),
                           np.inf)
        bs_sigma = np.where(
            ok, np.sqrt(np.maximum(s2 * a11, 0.0) / np.where(ok, det, 1.0)),
            np.sqrt(s2 / np.maximum(a22, _EPS)))
    return f_sigma, bs_sigma


def _fit_cells_np(n, y, mask, f_grid, utilization, p0_factor,
                  refine="gauss-newton"):
    rss, _ = _profile_rss_np(n, y, mask, f_grid, utilization, p0_factor)
    j = rss.argmin(axis=-1)
    F = len(f_grid)
    a = f_grid[np.clip(j - 1, 0, F - 1)]
    b = f_grid[np.clip(j + 1, 0, F - 1)]
    if refine == "golden":
        f_hat = _refine_golden_np(n, y, mask, a, b, utilization,
                                  p0_factor)
    else:
        f_hat = _refine_gn_np(n, y, mask, f_grid[j], a, b, utilization,
                              p0_factor)
    rss_hat, bs_hat = _rss_at_np(n, y, mask, f_hat, utilization,
                                 p0_factor)
    f_sigma, bs_sigma = _curvature_np(n, y, mask, f_hat, bs_hat, rss_hat,
                                      utilization, p0_factor)
    return f_hat, bs_hat, rss_hat, f_sigma, bs_sigma


if HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..core.sharing import utilization_curve_jax

    def _fit_single_jax(n, y, mask, f_grid, p0_factor, n_max, *, mode,
                        refine="gauss-newton"):
        """One cell: profile RSS over the f grid + sub-grid refinement
        (trust-clipped Gauss–Newton by default; the deprecated golden
        section behind ``refine="golden"``).  Shapes: ``n, y, mask`` are
        ``(N,)``; vmapped over the cell axis."""
        ym = jnp.where(mask, y, 0.0)

        def rss_at(f):
            u = utilization_curve_jax(n, f, mode=mode,
                                      p0_factor=p0_factor, n_max=n_max)
            u = jnp.where(mask, u, 0.0)
            bs = (ym * u).sum() / jnp.maximum((u * u).sum(), _EPS)
            rss = ((jnp.where(mask, ym - bs * u, 0.0)) ** 2).sum()
            return rss, bs

        def u_du(f):
            """``(U, ∂U/∂f)`` at scalar ``f`` — forward mode for the
            explicit laws, reverse mode for the fixed point (its
            ``custom_vjp`` has no jvp rule, by design)."""
            curve = functools.partial(utilization_curve_jax, n, mode=mode,
                                      p0_factor=p0_factor, n_max=n_max)
            if mode == "fixedpoint":
                return curve(f), jax.jacrev(curve)(f)
            return jax.jvp(curve, (f,), (jnp.ones_like(f),))

        u = utilization_curve_jax(n[None, :], f_grid[:, None], mode=mode,
                                  p0_factor=p0_factor, n_max=n_max)  # (F, N)
        u = jnp.where(mask[None, :], u, 0.0)
        bs_star = (ym[None, :] * u).sum(-1) / \
            jnp.maximum((u * u).sum(-1), _EPS)
        rss = (jnp.where(mask[None, :],
                         ym[None, :] - bs_star[:, None] * u, 0.0) ** 2
               ).sum(-1)                                        # (F,)
        F = f_grid.shape[0]
        j = jnp.argmin(rss)
        a = f_grid[jnp.clip(j - 1, 0, F - 1)]
        b = f_grid[jnp.clip(j + 1, 0, F - 1)]

        if refine == "golden":
            def body(_, state):
                a, b, c, d, rc, rd = state
                left = rc < rd
                a = jnp.where(left, a, c)
                b = jnp.where(left, d, b)
                c = b - _INVPHI * (b - a)
                d = a + _INVPHI * (b - a)
                rc = rss_at(c)[0]
                rd = rss_at(d)[0]
                return a, b, c, d, rc, rd

            c = b - _INVPHI * (b - a)
            d = a + _INVPHI * (b - a)
            state = (a, b, c, d, rss_at(c)[0], rss_at(d)[0])
            a2, b2, *_ = lax.fori_loop(0, _REFINE_ITERS, body, state)
            f_hat = 0.5 * (a2 + b2)
        else:
            # Trust-clipped Gauss–Newton on the profiled residual:
            # identical algorithm (and accept/reject rule) to
            # _refine_gn_np, so the backends agree.
            def gn_body(_, state):
                f, rss_f, trust = state
                uf, duf = u_du(f)
                uf = jnp.where(mask, uf, 0.0)
                duf = jnp.where(mask, duf, 0.0)
                su2 = (uf * uf).sum()
                syu = (ym * uf).sum()
                bs = syu / jnp.maximum(su2, _EPS)
                dbs = ((ym * duf).sum() * su2
                       - syu * 2.0 * (uf * duf).sum()) \
                    / jnp.maximum(su2 * su2, _EPS)
                dm = dbs * uf + bs * duf
                r = ym - bs * uf
                step = (dm * r).sum() / jnp.maximum((dm * dm).sum(),
                                                    _EPS)
                cand = jnp.clip(f + jnp.clip(step, -trust, trust), a, b)
                rss_c = rss_at(cand)[0]
                ok = rss_c <= rss_f
                return (jnp.where(ok, cand, f),
                        jnp.where(ok, rss_c, rss_f),
                        jnp.where(ok, trust, 0.25 * trust))

            f0 = f_grid[j]
            state = (f0, rss_at(f0)[0], b - a)
            f_hat, *_ = lax.fori_loop(0, _GN_ITERS, gn_body, state)

        rss_hat, bs_hat = rss_at(f_hat)

        # Curvature (1σ) from the 2-parameter normal matrix at the
        # optimum — same formulas as _curvature_np.
        uf, duf = u_du(f_hat)
        uf = jnp.where(mask, uf, 0.0)
        duf = jnp.where(mask, duf, 0.0)
        j1 = bs_hat * duf
        a11 = (j1 * j1).sum()
        a12 = (j1 * uf).sum()
        a22 = (uf * uf).sum()
        det = a11 * a22 - a12 * a12
        m_eff = mask.sum()
        s2 = rss_hat / jnp.maximum(m_eff - 2, 1)
        okc = det > 1e-12 * jnp.maximum(a11 * a22, _EPS)
        safe_det = jnp.where(okc, det, 1.0)
        f_sigma = jnp.where(
            okc, jnp.sqrt(jnp.maximum(s2 * a22, 0.0) / safe_det), jnp.inf)
        bs_sigma = jnp.where(
            okc, jnp.sqrt(jnp.maximum(s2 * a11, 0.0) / safe_det),
            jnp.sqrt(s2 / jnp.maximum(a22, _EPS)))
        return f_hat, bs_hat, rss_hat, f_sigma, bs_sigma

    def _build_jax_fit(mode: str, n_max: int, refine: str):
        """Jitted vmap of the per-cell fit for one shape bucket;
        registered in the substrate's process-wide solver cache."""
        vmapped = jax.vmap(
            functools.partial(_fit_single_jax, mode=mode, n_max=n_max,
                              refine=refine),
            in_axes=(0, 0, 0, None, None))
        return jax.jit(vmapped)

    def _fit_cells_jax(n, y, mask, f_grid, utilization, p0_factor,
                       refine="gauss-newton"):
        C, N = n.shape
        # Only the recursion law compiles an n-dependent loop; the queue
        # law shares one executable per (C, N, F) bucket.
        n_max = int(n.max()) if (n.size and utilization == "recursion") \
            else 0
        n_max_b = backend_mod.bucket(n_max) if n_max else 0
        Cb = backend_mod.bucket(C)
        fitter = backend_mod.jitted(
            ("calibrate.fit_scaling", utilization, refine, Cb, N,
             len(f_grid), n_max_b),
            lambda: _build_jax_fit(utilization, n_max_b, refine))
        with backend_mod.x64():
            # Padded cells are all-masked: their fit runs on zeros and
            # is sliced off below, so real cells are bit-for-bit the
            # unpadded pass.
            out = fitter(
                jnp.asarray(backend_mod.pad_rows(
                    np.asarray(n, np.float64), Cb), jnp.float64),
                jnp.asarray(backend_mod.pad_rows(
                    np.asarray(y, np.float64), Cb), jnp.float64),
                jnp.asarray(backend_mod.pad_rows(
                    np.asarray(mask, bool), Cb)),
                jnp.asarray(f_grid, jnp.float64),
                jnp.float64(p0_factor))
        return tuple(np.asarray(x)[:C] for x in out)


def fit_scaling(traces: TraceSet | Sequence[ScalingTrace], *,
                utilization: str = "queue",
                f_grid: np.ndarray | None = None, p0_factor: float = 0.5,
                backend: str = "auto",
                refine: str = "gauss-newton") -> ScalingFit:
    """Fit ``(f, b_s)`` for every scaling trace in one batched pass.

    ``utilization`` must match the instrument that produced the traces:
    ``"queue"`` for memsim-generated curves (and idealized interfaces),
    ``"recursion"`` (or its ``"fixedpoint"`` self-consistent limit) for
    real-hardware measurements with a soft knee.
    ``backend``: ``"numpy"``, ``"jax"`` (vmapped + jitted), or ``"auto"``
    — resolved by the substrate (:func:`repro.core.backend.resolve`)
    against the number of cells, like every batched path.  The jitted fit
    kernel — grid profile plus the sub-grid refinement — is one
    compiled plan per (cell-bucket, law, refiner) in the substrate's
    cache, so repeated fits of same-shaped trace sets skip
    recompilation.

    ``refine`` selects the sub-grid refiner inside the winning grid
    bracket:

    * ``"gauss-newton"`` (default) — jacobian-based Gauss–Newton over the
      identical profiled residual, with analytic ``∂U/∂f``
      (:func:`repro.core.sharing.utilization_curve_grad` / ``jax.jvp``).
      Quadratic convergence, ~1/3 the residual evaluations of golden
      section, and curvature-based ``f_sigma``/``bs_sigma`` CIs for free.
    * ``"golden"`` — **deprecated** escape hatch: the pre-jacobian
      golden-section bracket shrink, kept one release so the re-baseline
      is reversible (docs/known-issues.md).  Emits a
      ``DeprecationWarning``; both refiners converge to the same bracket
      optimum within ~1e-9 relative.
    """
    if not isinstance(traces, TraceSet):
        traces = TraceSet(scaling=tuple(traces))
    if refine not in REFINE_METHODS:
        raise ValueError(
            f"unknown refine method {refine!r} (choose from "
            f"{REFINE_METHODS})")
    if refine == "golden":
        warnings.warn(
            "refine='golden' is deprecated: the golden-section refiner "
            "is retired in favor of jacobian-based Gauss-Newton (same "
            "optimum, fewer residual evaluations, curvature CIs); this "
            "escape hatch will be removed once the re-baseline has "
            "soaked", DeprecationWarning, stacklevel=2)
    if not traces.scaling:
        return ScalingFit(f=np.zeros(0), bs=np.zeros(0), rss=np.zeros(0),
                          traces=(), utilization=utilization,
                          backend=backend, f_sigma=np.zeros(0),
                          bs_sigma=np.zeros(0), refine=refine)
    if utilization not in UTILIZATION_MODES:
        raise ValueError(f"unknown utilization mode {utilization!r}")
    f_grid = DEFAULT_F_GRID if f_grid is None else np.asarray(f_grid)
    n, y, mask, tr = traces.to_arrays()
    backend = backend_mod.resolve(backend, n.shape[0])
    with trace_mod.span("calibrate.fit", cells=int(n.shape[0]),
                        backend=backend, utilization=utilization,
                        refine=refine) as sp:
        if backend == "jax":
            f_hat, bs_hat, rss, f_sig, bs_sig = _fit_cells_jax(
                n, y, mask, f_grid, utilization, p0_factor, refine)
        else:
            f_hat, bs_hat, rss, f_sig, bs_sig = _fit_cells_np(
                n, y, mask, f_grid, utilization, p0_factor, refine)
        n_evals = _refine_evals(refine, len(f_grid))
        if trace_mod.enabled():
            # Per-cell evals and convergence: rss is the converged
            # residual sum of squares of each (kernel, arch, seed) cell.
            sp.set(evals_per_cell=n_evals,
                   rss_max=float(rss.max()) if rss.size else 0.0,
                   rss_median=float(np.median(rss)) if rss.size else 0.0)
            metrics.counter("calibrate.fit.cells").inc(int(n.shape[0]))
            metrics.counter("calibrate.fit.evals").inc(
                n_evals * int(n.shape[0]))
            for r in rss:
                metrics.histogram("calibrate.fit.rss").observe(float(r))
    return ScalingFit(f=f_hat, bs=bs_hat, rss=rss, traces=tuple(tr),
                      utilization=utilization, backend=backend,
                      f_sigma=f_sig, bs_sigma=bs_sig, refine=refine,
                      n_evals=n_evals)


def fit_scaling_cell(trace: ScalingTrace, **kwargs) -> tuple[float, float]:
    """Scalar convenience: fit one trace, return ``(f, b_s)``.  The
    sequential per-cell baseline the benchmark compares the batched pass
    against is a Python loop over this function."""
    fit = fit_scaling([trace], **kwargs)
    return float(fit.f[0]), float(fit.bs[0])


# ---------------------------------------------------------------------------
# Seed-ensemble aggregation → calibrated specs
# ---------------------------------------------------------------------------


def aggregate_ensemble(fit: ScalingFit, *, ci: float = 0.9
                       ) -> dict[tuple[str, str],
                                 dict[str, CalibratedValue]]:
    """Collapse a seed ensemble into per-(kernel, arch) estimates.

    Returns ``{(kernel, arch): {"f": CalibratedValue,
    "bs": CalibratedValue}}`` with the median as the point estimate and
    the central ``ci`` percentile interval over seeds as the confidence
    band (degenerate — lo == hi == value — for single-seed cells).
    The per-seed curvature sigmas (when the fit carries them) aggregate
    as their median into :attr:`CalibratedValue.sigma` — the
    within-trace counterpart of the across-seed percentile band.
    """
    lo_q, hi_q = 50 * (1 - ci), 50 * (1 + ci)
    out: dict[tuple[str, str], dict[str, CalibratedValue]] = {}
    for key, idx in fit.cells().items():
        cell: dict[str, CalibratedValue] = {}
        for field, arr, sig in (("f", fit.f, fit.f_sigma),
                                ("bs", fit.bs, fit.bs_sigma)):
            vals = arr[idx]
            cell[field] = CalibratedValue(
                value=float(np.median(vals)),
                lo=float(np.percentile(vals, lo_q)),
                hi=float(np.percentile(vals, hi_q)),
                n_seeds=len(idx),
                sigma=float(np.median(sig[idx])) if sig is not None
                else 0.0)
        out[key] = cell
    return out


def calibrated_specs(fit: ScalingFit, *,
                     templates: Mapping[str, KernelSpec] | None = None,
                     ci: float = 0.9) -> dict[str, KernelSpec]:
    """Materialize a fit as first-class :class:`KernelSpec` objects.

    Each kernel present in the fit gets one spec whose ``f``/``bs``
    mappings cover every fitted architecture (ensemble medians).  When a
    ``templates`` mapping (default: Table II) has a spec of the same
    name, its stream decomposition is inherited via
    :meth:`KernelSpec.from_calibration`, so ECM prediction and the
    desync engines consume the calibrated spec unchanged.
    """
    templates = TABLE2 if templates is None else templates
    agg = aggregate_ensemble(fit, ci=ci)
    per_kernel: dict[str, tuple[dict, dict]] = {}
    for (kern, arch), cell in sorted(agg.items()):
        f_map, bs_map = per_kernel.setdefault(kern, ({}, {}))
        f_map[arch] = min(cell["f"].value, 1.0)
        bs_map[arch] = cell["bs"].value
    return {
        kern: KernelSpec.from_calibration(
            kern, f_map, bs_map, template=templates.get(kern))
        for kern, (f_map, bs_map) in per_kernel.items()
    }


# ---------------------------------------------------------------------------
# Saturation-envelope fit from paired measurements (Eq. 4 in reverse)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EnvelopeFit:
    """Per-arch least-squares solution of Eq. 4 from paired totals:
    ``bs[arch][kernel]`` is the kernel's inferred homogeneous saturated
    bandwidth; any mix's envelope follows as the thread-weighted mean."""

    bs: dict[str, dict[str, float]]
    residual: dict[str, float]     # RMS of (measured − fitted) totals

    def envelope(self, arch: str, groups: Sequence[tuple[str, int]]
                 ) -> float:
        """Eq. 4 for an arbitrary mix ``[(kernel, n), ...]`` on ``arch``."""
        n_tot = sum(n for _, n in groups)
        if n_tot == 0:
            return 0.0
        return sum(n * self.bs[arch][k] for k, n in groups) / n_tot


def fit_envelope(pairs: Sequence[PairTrace]) -> EnvelopeFit:
    """Recover per-kernel ``b_s`` from saturated paired totals.

    Eq. 4 makes the mix envelope *linear* in the per-kernel saturated
    bandwidths: ``b_total = Σ (n_i / n_tot) · b_s,i``.  Stacking every
    pair trace of an architecture gives an overdetermined linear system,
    solved here per arch via ridge-stabilized normal equations — all
    architectures in one batched ``np.linalg.solve`` call.
    """
    pairs = tuple(pairs)
    if not pairs:
        return EnvelopeFit(bs={}, residual={})
    archs = sorted({p.arch for p in pairs})
    kernels = sorted({k for p in pairs for k in p.kernels})
    a_idx = {a: i for i, a in enumerate(archs)}
    k_idx = {k: i for i, k in enumerate(kernels)}
    A, K = len(archs), len(kernels)
    gram = np.zeros((A, K, K))
    rhs = np.zeros((A, K))
    rows: dict[str, list[tuple[np.ndarray, float]]] = {a: [] for a in archs}
    for p in pairs:
        row = np.zeros(K)
        n_tot = sum(p.n)
        for k, n in zip(p.kernels, p.n):
            row[k_idx[k]] += n / n_tot
        y = sum(p.bandwidth)
        ai = a_idx[p.arch]
        gram[ai] += np.outer(row, row)
        rhs[ai] += row * y
        rows[p.arch].append((row, y))
    # Tiny ridge keeps uncovered kernels solvable; they come out ~0 and
    # are reported as NaN below.
    ridge = 1e-9 * np.maximum(np.trace(gram, axis1=1, axis2=2), 1.0) / K
    gram += ridge[:, None, None] * np.eye(K)[None]
    sol = np.linalg.solve(gram, rhs[..., None])[..., 0]      # (A, K)
    covered = np.zeros((A, K), dtype=bool)
    for p in pairs:
        for k in p.kernels:
            covered[a_idx[p.arch], k_idx[k]] = True
    bs = {a: {k: (float(sol[a_idx[a], k_idx[k]])
                  if covered[a_idx[a], k_idx[k]] else float("nan"))
              for k in kernels}
          for a in archs}
    residual = {}
    for a in archs:
        errs = [y - float(row @ sol[a_idx[a]]) for row, y in rows[a]]
        residual[a] = float(np.sqrt(np.mean(np.square(errs))))
    return EnvelopeFit(bs=bs, residual=residual)


# ---------------------------------------------------------------------------
# Paired-share prediction from calibrated specs (one batched solve)
# ---------------------------------------------------------------------------


def predict_pairs(specs: Mapping[str, KernelSpec],
                  pairs: Sequence[PairTrace], *,
                  utilization: str | float = "queue") -> np.ndarray:
    """Model-predicted per-group bandwidths for every pair trace, solved
    in **one** :func:`repro.core.sharing.solve_batch` call (the PR-2
    batch machinery).  Returns ``(len(pairs), 2)`` GB/s."""
    pairs = tuple(pairs)
    if not pairs:
        return np.zeros((0, 2))
    n = np.array([p.n for p in pairs], dtype=np.float64)
    f = np.array([[specs[k].f[p.arch] for k in p.kernels] for p in pairs])
    bs = np.array([[specs[k].bs[p.arch] for k in p.kernels]
                   for p in pairs])
    batch = solve_batch(n, f, bs, utilization=utilization)
    return batch.bw_group
