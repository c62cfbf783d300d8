"""THE PAPER'S CONTRIBUTION: the analytic bandwidth-sharing model (Eqs. 4–5).

Given groups of threads concurrently executing different memory-bound loop
kernels on one contention domain, predict the memory-bandwidth share each
group (and each core) attains.  Inputs per group: thread count ``n``, memory
request fraction ``f``, and homogeneous saturated bandwidth ``b_s``.

The model generalizes naturally from the paper's two groups to N groups —
the request-proportional arbitration (Eq. 5) and the thread-weighted
saturation envelope (Eq. 4) are both linear in the groups.  We use the
N-group form throughout (the desync simulator routinely has >2 distinct
kernels in flight).

Two execution paths solve the same equations:

* the **scalar path** (:func:`predict`) — the original single-domain API,
  now a thin wrapper over the array core; returns plain-float
  :class:`SharePrediction` objects and stays the reference implementation;
* the **batched path** (:func:`solve_batch` / :func:`predict_batch`) —
  solves B independent scenarios of up to G groups in one shot, either with
  vectorized numpy or with a ``jax.vmap``-ped, jitted kernel.  Full-domain
  sweeps (benchmarks/fig6_full_domain.py, fig9_pairings.py) and topology
  solves (core/topology.py) go through this path.

Scenarios are rectangular arrays ``n, f, bs`` of shape ``(B, G)``; ragged
group lists are padded with ``n = 0`` entries, which are exactly neutral in
Eqs. 4–5 (they contribute nothing to any sum).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os
from typing import Sequence

import numpy as np

from . import backend as backend_mod
from .backend import HAVE_JAX  # re-export: the probe lives on the substrate
from .table2 import KernelSpec
from ..obs import metrics, trace

if HAVE_JAX:  # pragma: no branch - capability guard, not dispatch
    import jax
    import jax.numpy as jnp
    from jax import lax


@dataclasses.dataclass(frozen=True)
class Group:
    """One group of threads all executing the same kernel."""

    n: int          # number of threads
    f: float        # memory request fraction of the kernel (Eq. 2/3)
    bs: float       # saturated bandwidth of the kernel, homogeneous run
    name: str = ""

    @staticmethod
    def of(kernel: KernelSpec, arch: str, n: int) -> "Group":
        if arch not in kernel.f or arch not in kernel.bs:
            from ..api.registry import unknown_key_error
            known = sorted(set(kernel.f) & set(kernel.bs))
            raise unknown_key_error("architecture", arch, known)
        return Group(n=n, f=kernel.f[arch], bs=kernel.bs[arch],
                     name=kernel.name)


@dataclasses.dataclass(frozen=True)
class SharePrediction:
    groups: tuple[Group, ...]
    b_overlap: float            # Eq. 4 saturation envelope [GB/s]
    alphas: tuple[float, ...]   # Eq. 5 request shares, sum to 1
    bw_group: tuple[float, ...]  # attained bandwidth per group [GB/s]

    @property
    def bw_per_core(self) -> tuple[float, ...]:
        return tuple(b / g.n if g.n else 0.0
                     for b, g in zip(self.bw_group, self.groups))

    @property
    def total_bw(self) -> float:
        return sum(self.bw_group)


def overlapped_saturated_bw(groups: Sequence[Group]) -> float:
    """Paper Eq. (4): thread-weighted mean of homogeneous saturated bws."""
    n_tot = sum(g.n for g in groups)
    if n_tot == 0:
        return 0.0
    return sum(g.n * g.bs for g in groups) / n_tot


def request_shares(groups: Sequence[Group]) -> tuple[float, ...]:
    """Paper Eq. (5): share of requests (hence bandwidth) per group."""
    weights = [g.n * g.f for g in groups]
    tot = sum(weights)
    if tot == 0.0:
        return tuple(0.0 for _ in groups)
    return tuple(w / tot for w in weights)


def predict(groups: Sequence[Group], *, saturated: bool | None = None,
            utilization: str | float = "recursion",
            p0_factor: float = 0.5) -> SharePrediction:
    """Bandwidth share per group.

    The envelope is ``U(n_t; f̄) · b(mix)``: the Eq. 4 mix envelope scaled by
    the interface utilization at the *mean* request fraction
    ``f̄ = Σ nᵢfᵢ / n_t``.  At saturation U → 1 and the model is exactly
    Eqs. 4–5; below saturation each group's share degrades to its demand
    (paper Sect. IV: the model "can also be applied to the nonsaturated
    case").

    ``utilization`` selects the sub-saturation law:
      * ``"recursion"`` — the paper's simplified latency-penalty recursion
        (Hofmann et al.), penalty ``p0 = p0_factor · T_Mem`` (paper uses
        p0_factor = 1/2; the full model fits it per machine).  Soft knee,
        matches real hardware (paper Fig. 7).
      * ``"queue"`` — ideal work-conserving interface, ``U = min(1, f̄·n_t)``.
        Hard knee, matches the idealized queue instrument (core/memsim.py).
      * a float — externally calibrated utilization.
    ``saturated=True`` forces U = 1.

    This is now a thin wrapper over the vectorized array core
    (:func:`_solve_arrays_np`) with batch size 1; :func:`solve_batch` runs
    the same math over many scenarios at once.
    """
    groups = tuple(groups)
    if not groups:
        return SharePrediction(groups=(), b_overlap=0.0, alphas=(),
                               bw_group=())
    n = np.array([[g.n for g in groups]], dtype=np.float64)
    f = np.array([[g.f for g in groups]], dtype=np.float64)
    bs = np.array([[g.bs for g in groups]], dtype=np.float64)
    b, alphas, util, bw = _solve_arrays_np(
        n, f, bs, utilization=utilization, p0_factor=p0_factor,
        saturated=saturated)
    return SharePrediction(
        groups=groups, b_overlap=float(b[0]),
        alphas=tuple(float(a) for a in alphas[0]),
        bw_group=tuple(float(x) for x in bw[0]))


def pair(kernel_a: KernelSpec, kernel_b: KernelSpec, arch: str,
         n_a: int, n_b: int, **kwargs) -> SharePrediction:
    """Convenience: the paper's two-kernel scenario on architecture ``arch``."""
    return predict([Group.of(kernel_a, arch, n_a),
                    Group.of(kernel_b, arch, n_b)], **kwargs)


def gain_vs_self(kernel_a: KernelSpec, kernel_b: KernelSpec, arch: str,
                 n_each: int) -> float:
    """Paper Fig. 9 bar height: relative bandwidth gain/loss of kernel A when
    paired with B (each on ``n_each`` cores), normalized to A self-paired."""
    mixed = pair(kernel_a, kernel_b, arch, n_each, n_each)
    homo = pair(kernel_a, kernel_a, arch, n_each, n_each)
    return mixed.bw_group[0] / homo.bw_group[0]


def runtime(groups: Sequence[Group], work_bytes: Sequence[float]
            ) -> tuple[float, ...]:
    """Predicted wall time per group to move ``work_bytes`` at the shared
    bandwidth (bytes / (bw per group)).  Used by the desync simulator."""
    pred = predict(groups)
    return tuple(
        wb / (bw * 1e9) if bw > 0 else float("inf")
        for wb, bw in zip(work_bytes, pred.bw_group)
    )


# ---------------------------------------------------------------------------
# Batched solver: B scenarios × G groups in one call.
# ---------------------------------------------------------------------------

_TINY = 1e-300  # division guard far below any physical n·f product

#: The named sub-saturation utilization laws (floats and ``saturated=True``
#: are accepted separately by the solvers).
UTILIZATION_MODES = ("queue", "recursion", "fixedpoint")

#: Bisection depth of the fixed-point utilization solve: 60 halvings of
#: [0, 1] put the bracket below float64 resolution, so the numpy and jax
#: forward passes agree bitwise.
_FP_BISECT_ITERS = 60

#: Least recursion bound the jitted solver compiles its loop for: steps
#: past a row's thread count leave its utilization as it is, so a short
#: loop changes no bit, and every thread count up to it shares one
#: program (one per row and group bucket on an 8-core domain).
MIN_RECURSION_BOUND = 8

#: The jax solve's results come back in pieces of about this many bytes,
#: copied concurrently.  From a TPU v5e one float64 array came back at
#: 0.26–0.33 GB/s whatever its layout; the 33.5 MB of a 2^19-row solve
#: in 2 MiB pieces in 23 ms, against 25–27 ms in pieces of 1 or 4 MiB.
_FETCH_PIECE_BYTES = 2 << 20


def _fixedpoint_u_np(n, f, p0_factor):
    """Self-consistent utilization ``u = min(1, n·f / (1 + p0·f·u·(n−1)))``
    by bisection on the monotone residual ``r(u) = u − S(u)``."""
    c = p0_factor * f * np.maximum(n - 1.0, 0.0)
    lo = np.zeros_like(c)
    hi = np.ones_like(c)
    for _ in range(_FP_BISECT_ITERS):
        mid = 0.5 * (lo + hi)
        r = mid - np.minimum(1.0, n * f / (1.0 + c * mid))
        below = r < 0
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    return 0.5 * (lo + hi)


def utilization_curve(n, f, *, mode: str = "recursion",
                      p0_factor: float = 0.5) -> np.ndarray:
    """Sub-saturation interface utilization ``U(n; f)``, vectorized.

    ``n`` and ``f`` broadcast against each other; entries with ``n == 0``
    (or ``f == 0`` in recursion/fixedpoint mode) return 1.0, matching the
    neutral handling inside :func:`_solve_arrays_np`.  Modes:

    * ``"queue"`` — ideal work-conserving interface, ``U = min(1, f·n)``
      (the hard knee of the queue instrument, core/memsim.py);
    * ``"recursion"`` — the simplified latency-penalty recursion of
      Hofmann et al. with ``t_ecm = 1``, ``t_mem = f`` and penalty
      ``p0 = p0_factor · f`` (the soft knee of real hardware, paper
      Fig. 7; equivalent to :func:`repro.core.ecm.scaling_curve`);
    * ``"fixedpoint"`` — the recursion law's self-consistent limit,
      ``u = min(1, n·f / (1 + p0·f·u·(n−1)))``, solved as a fixed point.
      Same soft knee, but the jax path registers a ``custom_vjp`` via the
      implicit function theorem, so backprop costs one elementwise linear
      solve instead of unrolling iterations (docs/model.md).

    This is the single implementation of the utilization law: the batched
    solver evaluates it at each scenario's ``(n_tot, f̄)``, and the
    calibration fit (repro.calibrate.fit) evaluates it over whole scaling
    curves as the Eq. 1–5 forward model — so the two cannot drift.
    """
    n, f = np.broadcast_arrays(np.asarray(n, dtype=np.float64),
                               np.asarray(f, dtype=np.float64))
    active = n > 0
    if mode == "queue":
        return np.where(active, np.minimum(1.0, f * n), 1.0)
    if mode == "recursion":
        # Carry the recursion forward over core counts, freezing each
        # entry at its own n via masking (entries differ in n, share f).
        p0 = p0_factor * f
        u = f.copy()
        n_max = int(n.max()) if n.size else 0
        for i in range(2, n_max + 1):
            t_i = 1.0 + p0 * u * (i - 1)
            u = np.where(i <= n, np.minimum(1.0, i * f / t_i), u)
        return np.where(active & (f > 0), u, 1.0)
    if mode == "fixedpoint":
        u = _fixedpoint_u_np(n, f, p0_factor)
        return np.where(active & (f > 0), u, 1.0)
    from ..api.registry import unknown_key_error
    raise unknown_key_error("utilization mode", mode,
                            list(UTILIZATION_MODES))


def utilization_curve_grad(n, f, *, mode: str = "recursion",
                           p0_factor: float = 0.5
                           ) -> tuple[np.ndarray, np.ndarray]:
    """``(U(n; f), ∂U/∂f)`` for every utilization law, vectorized numpy.

    The derivative is carried analytically through the law itself —
    forward-mode through the recursion sweep, the implicit function
    theorem for the fixed point — so the calibration fit's Gauss–Newton
    refinement (repro.calibrate.fit) gets exact jacobians on the numpy
    backend, matching ``jax.jvp`` over :func:`utilization_curve_jax` on
    the jax backend.  Neutral entries (``n == 0`` / ``f == 0``) return
    ``(1, 0)``; saturated entries have exactly zero derivative (the min
    clamps).
    """
    n, f = np.broadcast_arrays(np.asarray(n, dtype=np.float64),
                               np.asarray(f, dtype=np.float64))
    active = n > 0
    if mode == "queue":
        u = np.where(active, np.minimum(1.0, f * n), 1.0)
        du = np.where(active & (f * n < 1.0), n, 0.0)
        return u, du
    if mode == "recursion":
        p0 = p0_factor * f
        u = f.copy()
        du = np.ones_like(f)
        n_max = int(n.max()) if n.size else 0
        for i in range(2, n_max + 1):
            t_i = 1.0 + p0 * u * (i - 1)
            dt_i = (p0_factor * u + p0 * du) * (i - 1)
            val = i * f / t_i
            dval = i / t_i - i * f * dt_i / (t_i * t_i)
            upd = i <= n
            u = np.where(upd, np.minimum(1.0, val), u)
            du = np.where(upd, np.where(val < 1.0, dval, 0.0), du)
        live = active & (f > 0)
        return np.where(live, u, 1.0), np.where(live, du, 0.0)
    if mode == "fixedpoint":
        u = _fixedpoint_u_np(n, f, p0_factor)
        # IFT on h(u, f) = u + p0·f·(n−1)·u² − n·f = 0 (unsaturated):
        # du/df = (n − p0·(n−1)·u²) / (1 + 2·p0·f·(n−1)·u).
        c = p0_factor * f * np.maximum(n - 1.0, 0.0)
        saturated = n * f >= 1.0 + c
        du = np.where(
            saturated, 0.0,
            (n - p0_factor * np.maximum(n - 1.0, 0.0) * u * u)
            / (1.0 + 2.0 * c * u))
        live = active & (f > 0)
        return np.where(live, u, 1.0), np.where(live, du, 0.0)
    from ..api.registry import unknown_key_error
    raise unknown_key_error("utilization mode", mode,
                            list(UTILIZATION_MODES))


def _solve_arrays_np(n: np.ndarray, f: np.ndarray, bs: np.ndarray, *,
                     utilization: str | float, p0_factor: float,
                     saturated: bool | None
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Vectorized Eqs. 4–5 over ``(B, G)`` arrays.

    Returns ``(b_overlap (B,), alphas (B,G), util (B,), bw_group (B,G))``.
    Entries with ``n == 0`` are neutral padding.  Reference implementation:
    the scalar :func:`predict` wraps this with B = 1.
    """
    n = np.asarray(n, dtype=np.float64)
    f = np.asarray(f, dtype=np.float64)
    bs = np.asarray(bs, dtype=np.float64)
    n_tot = n.sum(axis=-1)
    safe_n = np.maximum(n_tot, 1.0)

    # Eq. 4: thread-weighted saturation envelope.
    b = np.where(n_tot > 0, (n * bs).sum(axis=-1) / safe_n, 0.0)

    # Eq. 5: request-proportional arbitration.
    w = n * f
    w_sum = w.sum(axis=-1)
    alphas = np.where(w_sum[..., None] > 0,
                      w / np.maximum(w_sum, _TINY)[..., None], 0.0)

    # Interface utilization at the mean request fraction (sub-saturation).
    f_mean = np.where(n_tot > 0, w_sum / safe_n, 0.0)
    active = n_tot > 0
    if saturated is True:
        util = np.ones_like(b)
    elif isinstance(utilization, (int, float)):
        util = np.where(active, float(utilization), 1.0)
    elif utilization in UTILIZATION_MODES:
        util = utilization_curve(n_tot, f_mean, mode=utilization,
                                 p0_factor=p0_factor)
    else:
        raise ValueError(f"unknown utilization mode {utilization!r}")

    bw = alphas * (util * b)[..., None]
    return b, alphas, util, bw


if HAVE_JAX:

    def _softmin_jax(a, b, beta):
        """Smooth minimum ``−(1/β)·log(e^{−βa} + e^{−βb})``: a lower bound
        on ``min(a, b)`` approaching it as β → ∞, with everywhere-defined
        gradients (the saturation knee stops being a kink).  Stable via
        ``logaddexp``."""
        return -jnp.logaddexp(-beta * a, -beta * b) / beta

    def _min_fn(beta):
        """The saturation min of the gradient path: exact ``jnp.minimum``
        when ``beta`` is None (a.e.-correct subgradients, the default),
        the β-softmin otherwise."""
        if beta is None:
            return jnp.minimum
        return functools.partial(_softmin_jax, beta=beta)

    @functools.lru_cache(maxsize=None)
    def _fixedpoint_u_jax(beta):
        """The ``"fixedpoint"`` utilization law with a ``custom_vjp``.

        Forward: bisection on ``r(u) = u − S(u)`` with
        ``S(u) = min(1, n·f / (1 + p0·f·u·(n−1)))`` — ``r`` is strictly
        increasing (S is decreasing in u), so the root is unique and 60
        halvings of [0, 1] pin it to float64 resolution, matching
        :func:`_fixedpoint_u_np` bitwise.

        Backward: the implicit function theorem on the converged solution
        instead of unrolling the bisection.  With ``u* = S(u*)``,
        ``du* = ∂S/∂θ · dθ / (1 − ∂S/∂u)`` — and since ``∂S/∂u ≤ 0`` the
        denominator is ≥ 1, so the "linear solve" is one well-conditioned
        elementwise division.
        """
        smin = _min_fn(beta)

        def S(u, n, f, p0):
            c = p0 * f * jnp.maximum(n - 1.0, 0.0)
            return smin(1.0, n * f / (1.0 + c * u))

        @jax.custom_vjp
        def fixed_u(n, f, p0):
            def body(_, lohi):
                lo, hi = lohi
                mid = 0.5 * (lo + hi)
                below = mid - S(mid, n, f, p0) < 0
                return (jnp.where(below, mid, lo),
                        jnp.where(below, hi, mid))

            lo = jnp.zeros_like(n * f)
            lo, hi = lax.fori_loop(0, _FP_BISECT_ITERS, body,
                                   (lo, lo + 1.0))
            return 0.5 * (lo + hi)

        def fwd(n, f, p0):
            u = fixed_u(n, f, p0)
            return u, (u, n, f, p0)

        def bwd(res, g):
            u, n, f, p0 = res
            # S is elementwise, so vjp against ones is exactly ∂S/∂u.
            _, vjp_u = jax.vjp(lambda uu: S(uu, n, f, p0), u)
            ds_du = vjp_u(jnp.ones_like(u))[0]
            lam = g / (1.0 - ds_du)
            _, vjp_theta = jax.vjp(
                lambda nn, ff, pp: S(u, nn, ff, pp), n, f, p0)
            return vjp_theta(lam)

        fixed_u.defvjp(fwd, bwd)
        return fixed_u

    def utilization_curve_jax(n, f, *, mode: str, p0_factor, n_max: int,
                              beta: float | None = None):
        """JAX twin of :func:`utilization_curve` (broadcasting inputs;
        ``n_max`` is the static recursion bound, shared across a vmapped
        batch).  The single jax implementation of the utilization law —
        used by the batched solver below and by the calibration fit
        (repro.calibrate.fit), so the two cannot drift.  ``beta`` selects
        the saturation min of the *gradient path*: None (default) keeps
        the exact ``jnp.minimum``, a float smooths it with
        :func:`_softmin_jax` — forward callers always pass None, so
        values never change."""
        smin = _min_fn(beta)
        active = n > 0
        if mode == "queue":
            return jnp.where(active, smin(1.0, f * n), 1.0)
        if mode == "recursion":
            p0 = p0_factor * f
            u0 = f + 0.0 * n   # broadcast of the u(1) = f seed

            def body(i, u):
                fi = i.astype(u.dtype)
                t_i = 1.0 + p0 * u * (fi - 1.0)
                return jnp.where(fi <= n, smin(1.0, fi * f / t_i), u)

            u = lax.fori_loop(2, n_max + 1, body, u0)
            return jnp.where(active & (f > 0), u, 1.0)
        if mode == "fixedpoint":
            nn, ff = jnp.broadcast_arrays(n + 0.0 * f, f + 0.0 * n)
            u = _fixedpoint_u_jax(beta)(
                nn * 1.0, ff * 1.0, jnp.asarray(p0_factor, nn.dtype))
            return jnp.where(active & (f > 0), u, 1.0)
        raise ValueError(f"unknown utilization mode {mode!r}")

    def _solve_single_jax(n, f, bs, p0_aux, n_max, *, mode: str,
                          beta: float | None = None):
        """One scenario (shape ``(G,)``); vmapped over the batch axis.

        ``p0_aux`` carries ``p0_factor`` (recursion) or the fixed
        utilization (mode "fixed").  ``n_max`` is the loop bound, shared
        across the batch so the vmapped ``fori_loop`` stays uniform.
        ``beta`` is the gradient path's softmin knob (see
        :func:`utilization_curve_jax`); every piece of this solver other
        than the saturation min is already smooth, so the whole Eq. 4–5
        chain is differentiable end to end.
        """
        n_tot = n.sum()
        safe_n = jnp.maximum(n_tot, 1.0)
        b = jnp.where(n_tot > 0, (n * bs).sum() / safe_n, 0.0)
        w = n * f
        w_sum = w.sum()
        alphas = jnp.where(w_sum > 0, w / jnp.maximum(w_sum, _TINY), 0.0)
        f_mean = jnp.where(n_tot > 0, w_sum / safe_n, 0.0)
        active = n_tot > 0
        if mode == "saturated":
            util = jnp.ones_like(b)
        elif mode == "fixed":
            util = jnp.where(active, p0_aux, 1.0)
        else:  # queue / recursion / fixedpoint: the shared law
            util = utilization_curve_jax(n_tot, f_mean, mode=mode,
                                         p0_factor=p0_aux, n_max=n_max,
                                         beta=beta)
        bw = alphas * util * b
        return b, alphas, util, bw

    def _fetch_pieces(values: int) -> int:
        """How many pieces a flat float64 result of ``values`` comes back
        in: one per :data:`_FETCH_PIECE_BYTES` begun."""
        return max(1, -(-8 * values // _FETCH_PIECE_BYTES))

    def _build_jax_solver(mode: str, n_max: int, rows: tuple[int, int]):
        """Jitted vmap of the single-scenario solver for one shape
        bucket; registered in the substrate's process-wide cache.

        The program takes ``n, f, bs`` flat as ``(Bb·G,)`` and lays them
        out as ``rows`` = ``(Bb, G)`` on the device: a TPU runtime copies
        a flat float64 array in as it is, but splits a ``(rows, G)`` one
        into tiles by a host transpose per 128 rows or so (110,595 of
        them for the three inputs of a 2^19-row solve, each an event in
        a profiler trace).
        It returns its four outputs ``(b, alphas, util, bw)`` flat, each
        as a tuple of :func:`_fetch_pieces` consecutive pieces, which
        :func:`_fetch_outputs` copies back concurrently."""
        vmapped = jax.vmap(
            functools.partial(_solve_single_jax, mode=mode, n_max=n_max),
            in_axes=(0, 0, 0, None))

        def solve(n, f, bs, p0_aux):
            n, f, bs = (x.reshape(rows) for x in (n, f, bs))
            return tuple(
                tuple(jnp.array_split(x, _fetch_pieces(x.size)))
                for x in (a.ravel() for a in vmapped(n, f, bs, p0_aux)))

        # The program takes its name from this function: keep the
        # solver's, which device traces are searched for.
        solve.__name__ = solve.__qualname__ = _solve_single_jax.__name__
        return jax.jit(solve)

    @functools.cache
    def _fetch_pool() -> concurrent.futures.ThreadPoolExecutor:
        """The threads that land the solve's pieces: made on first use,
        kept for the process (a forked child makes its own)."""
        return concurrent.futures.ThreadPoolExecutor(
            os.cpu_count() or 1, thread_name_prefix="sharing-fetch")

    os.register_at_fork(after_in_child=_fetch_pool.cache_clear)

    def _land(job) -> None:
        into, piece = job
        into[...] = np.asarray(piece)

    def _fetch_outputs(outputs) -> list[np.ndarray]:
        """Each flat output on the host, from its pieces on the device.

        Every piece's copy starts at once; the pieces of each output
        land in their places in one buffer of its own, on a pool of
        threads, as they arrive: the waits and the copies release the
        interpreter lock, so the slow float64 copies overlap."""
        for pieces in outputs:
            for piece in pieces:
                piece.copy_to_host_async()
        flat, jobs = [], []
        for pieces in outputs:
            buf = np.empty(sum(p.shape[0] for p in pieces), np.float64)
            flat.append(buf)
            start = 0
            for piece in pieces:
                jobs.append((buf[start:start + piece.shape[0]], piece))
                start += piece.shape[0]
        list(_fetch_pool().map(_land, jobs))
        return flat

    def _build_jax_grad_solver(mode: str, n_max: int, beta: float | None,
                               argnums: tuple[int, ...]):
        """Jitted vmap of ``jacrev`` over the single-scenario solver's
        ``bw_group`` output — reverse mode so the ``"fixedpoint"`` law's
        ``custom_vjp`` (one linear solve per backward pass) is what runs;
        registered in the same substrate cache as the forward solvers."""
        def bw_of(n_, f_, bs_, aux):
            return _solve_single_jax(n_, f_, bs_, aux, n_max, mode=mode,
                                     beta=beta)[3]

        jac = jax.jacrev(bw_of, argnums=argnums)
        return jax.jit(jax.vmap(jac, in_axes=(0, 0, 0, None)))

    def _solve_arrays_jax(n, f, bs, *, utilization, p0_factor, saturated):
        """JAX twin of :func:`_solve_arrays_np` (float64 via local x64).

        The jitted solver is fetched from the substrate's cache keyed by
        the padded ``(B, G)`` bucket (plus the static recursion bound),
        so nearby batch sizes share one XLA executable: inputs are
        padded with neutral ``n = 0`` rows up to the bucket and the
        outputs sliced back — exactly neutral in Eqs. 4–5, so the real
        rows are bit-for-bit the unpadded solve.
        """
        if saturated is True:
            mode, aux = "saturated", 0.0
        elif isinstance(utilization, (int, float)):
            mode, aux = "fixed", float(utilization)
        elif utilization in UTILIZATION_MODES:
            mode, aux = utilization, p0_factor
        else:
            raise ValueError(f"unknown utilization mode {utilization!r}")
        n = np.asarray(n, dtype=np.float64)
        B, G = n.shape
        # Only the recursion mode compiles an n-dependent loop; the
        # other modes share one executable per (B, G) bucket.
        n_max = int(n.sum(axis=-1).max()) if (n.size and mode == "recursion") \
            else 0
        n_max_b = backend_mod.bucket(n_max, minimum=MIN_RECURSION_BOUND) \
            if n_max else 0
        Bb = backend_mod.bucket(B)
        solver = backend_mod.jitted(
            ("sharing.solve_batch", mode, Bb, G, n_max_b),
            lambda: _build_jax_solver(mode, n_max_b, (Bb, G)))
        with backend_mod.x64():
            # The solver cannot start before its inputs arrive, so
            # waiting for them here costs nothing and keeps the copies
            # in under ``put`` rather than ``wait``.
            with trace.span("sharing.jax.put"):
                args = jax.block_until_ready([jnp.asarray(
                    backend_mod.pad_rows(np.asarray(a, dtype=np.float64),
                                         Bb).reshape(-1), jnp.float64)
                    for a in (n, f, bs)])
            with trace.span("sharing.jax.wait"):
                out = jax.block_until_ready(
                    solver(*args, jnp.float64(aux)))
            with trace.span("sharing.jax.get"):
                b, alphas, util, bw = _fetch_outputs(out)
                metrics.counter("sharing.jax.get_bytes").inc(
                    b.nbytes + alphas.nbytes + util.nbytes + bw.nbytes)
                return (b[:B], alphas.reshape(Bb, G)[:B], util[:B],
                        bw.reshape(Bb, G)[:B])


@dataclasses.dataclass(frozen=True)
class BatchSharePrediction:
    """Solution of B independent sharing scenarios (arrays, batch-first)."""

    n: np.ndarray          # (B, G) thread counts (float, 0 = padding)
    f: np.ndarray          # (B, G) request fractions
    bs: np.ndarray         # (B, G) saturated bandwidths [GB/s]
    b_overlap: np.ndarray  # (B,)   Eq. 4 envelopes [GB/s]
    alphas: np.ndarray     # (B, G) Eq. 5 request shares
    util: np.ndarray       # (B,)   interface utilization factors
    bw_group: np.ndarray   # (B, G) attained bandwidth per group [GB/s]
    names: tuple[tuple[str, ...], ...] | None = None  # (B, G) group labels

    @property
    def bw_per_core(self) -> np.ndarray:
        return np.divide(self.bw_group, self.n,
                         out=np.zeros_like(self.bw_group),
                         where=self.n > 0)

    @property
    def total_bw(self) -> np.ndarray:
        return self.bw_group.sum(axis=-1)

    def __len__(self) -> int:
        return self.bw_group.shape[0]

    def scenario(self, i: int) -> "SharePrediction":
        """Materialize scenario ``i`` as a scalar-API prediction (padding
        groups dropped).  Group names survive the round trip when the batch
        was built with them (see :func:`groups_to_arrays`)."""
        keep = [j for j in range(self.n.shape[1]) if self.n[i, j] > 0]
        groups = tuple(Group(n=int(self.n[i, j]), f=float(self.f[i, j]),
                             bs=float(self.bs[i, j]),
                             name=(self.names[i][j] if self.names is not None
                                   else ""))
                       for j in keep)
        return SharePrediction(
            groups=groups, b_overlap=float(self.b_overlap[i]),
            alphas=tuple(float(self.alphas[i, j]) for j in keep),
            bw_group=tuple(float(self.bw_group[i, j]) for j in keep))


def solve_arrays(n: np.ndarray, f: np.ndarray, bs: np.ndarray, *,
                 backend: str = "auto",
                 utilization: str | float = "recursion",
                 p0_factor: float = 0.5, saturated: bool | None = None
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The validated array core behind :func:`solve_batch`.

    ``n``, ``f``, ``bs`` must already be float64 arrays of shape
    ``(B, G)`` — compiled execution plans (:mod:`repro.api.plan`) call
    this directly to skip re-validation on every run.  Returns
    ``(b_overlap (B,), alphas (B,G), util (B,), bw_group (B,G))``.

    ``backend`` resolves through the substrate
    (:func:`repro.core.backend.resolve`): ``"auto"`` picks jax when
    importable and ``B >= DEFAULT_JAX_CUTOFF`` (64).
    """
    backend = backend_mod.resolve(backend, n.shape[0])
    solve = _solve_arrays_jax if backend == "jax" else _solve_arrays_np
    if not trace.enabled():  # hot path: no attr dicts, no span object
        return solve(n, f, bs, utilization=utilization,
                     p0_factor=p0_factor, saturated=saturated)
    with trace.span("sharing.solve_arrays", backend=backend,
                    B=int(n.shape[0]), G=int(n.shape[1]),
                    utilization=str(utilization)):
        return solve(n, f, bs, utilization=utilization,
                     p0_factor=p0_factor, saturated=saturated)


def solve_batch(n, f, bs, names=None, *,
                utilization: str | float = "recursion",
                p0_factor: float = 0.5, saturated: bool | None = None,
                backend: str = "auto") -> BatchSharePrediction:
    """Solve Eqs. 4–5 for a batch of scenarios.

    ``n``, ``f``, ``bs``: array-likes of shape ``(B, G)`` (a single ``(G,)``
    scenario is promoted to B = 1).  Groups with ``n = 0`` act as padding.
    ``names``: optional ``(B, G)`` nested sequence of group labels, carried
    through to :meth:`BatchSharePrediction.scenario` (padding entries "").
    ``backend``: ``"jax"`` (vmapped + jitted), ``"numpy"``, or ``"auto"``
    (resolved by the substrate, see :func:`repro.core.backend.resolve`).
    Both backends compute in float64 and agree with the scalar
    :func:`predict` to ~1e-12 relative.
    """
    n = np.atleast_2d(np.asarray(n, dtype=np.float64))
    f = np.atleast_2d(np.asarray(f, dtype=np.float64))
    bs = np.atleast_2d(np.asarray(bs, dtype=np.float64))
    if not (n.shape == f.shape == bs.shape):
        raise ValueError(
            f"shape mismatch: n{n.shape} f{f.shape} bs{bs.shape}")
    if names is not None:
        names = tuple(tuple(row) for row in names)
        if len(names) != n.shape[0] or \
                any(len(row) != n.shape[1] for row in names):
            raise ValueError(
                f"names rows {[len(r) for r in names]} do not match "
                f"n{n.shape}")
    b, alphas, util, bw = solve_arrays(
        n, f, bs, backend=backend, utilization=utilization,
        p0_factor=p0_factor, saturated=saturated)
    return BatchSharePrediction(n=n, f=f, bs=bs, b_overlap=b, alphas=alphas,
                                util=util, bw_group=bw, names=names)


# ---------------------------------------------------------------------------
# Gradient path: jacobians of the Eq. 4–5 solve wrt its inputs.
# ---------------------------------------------------------------------------

#: Gradient input names → positional argument of the single-scenario
#: solver (``plan.grad(wrt=...)`` uses the same vocabulary).
WRT_ARGNUM = {"cores": 0, "f": 1, "b_s": 2}


def _resolve_grad_mode(utilization, saturated):
    """Map the solver's ``utilization``/``saturated`` knobs onto the jax
    kernel's static mode + traced aux, exactly like the forward path."""
    if saturated is True:
        return "saturated", 0.0
    if isinstance(utilization, (int, float)):
        return "fixed", float(utilization)
    if utilization in UTILIZATION_MODES:
        return utilization, None
    raise ValueError(f"unknown utilization mode {utilization!r}")


def solve_arrays_and_grad(n, f, bs, *, wrt=("f", "b_s"),
                          utilization: str | float = "recursion",
                          p0_factor: float = 0.5,
                          saturated: bool | None = None,
                          softmin_beta: float | None = None,
                          backend: str = "auto"
                          ) -> tuple[tuple[np.ndarray, np.ndarray,
                                           np.ndarray, np.ndarray],
                                     dict[str, np.ndarray]]:
    """Forward Eq. 4–5 solve plus jacobians of ``bw_group`` wrt inputs.

    Returns ``((b, alphas, util, bw), grads)`` where the forward tuple is
    exactly :func:`solve_arrays` (same ``backend`` dispatch, exact min)
    and ``grads[name]`` has shape ``(B, G, G)`` with
    ``grads[name][b, i, j] = ∂ bw_group[b, i] / ∂ name[b, j]``.

    ``wrt`` ⊆ ``("cores", "f", "b_s")`` — ``"cores"`` differentiates wrt
    the (relaxed, real-valued) thread counts ``n``.  The jacobians run in
    reverse mode on the jax backend, through :func:`_solve_single_jax`
    with lax selects everywhere (so padding rows stay neutral) and, in
    ``"fixedpoint"`` mode, through the implicit-function-theorem
    ``custom_vjp`` of :func:`_fixedpoint_u_jax`.  ``softmin_beta``
    smooths the saturation min *of the gradient path only* (forward
    values never change); None keeps exact a.e. subgradients.  The jitted
    jacobian kernel lives in the same :mod:`repro.core.backend`
    power-of-two bucket cache as the forward solvers, so repeat sweeps of
    nearby batch sizes share one compiled executable.

    Note the Eq. 4–5 coupling is global within a scenario: off-diagonal
    entries (group i's bandwidth wrt group j's inputs) are genuinely
    nonzero, and a padded ``n = 0`` group has zero sensitivity to its own
    ``f``/``b_s`` but a real ``"cores"`` column (adding threads to an
    empty slot changes the mix).  The placed-grid wrapper
    (:func:`solve_placed_and_grad`) zeroes masked lanes outright.
    """
    if not HAVE_JAX:
        raise RuntimeError(
            "solve_arrays_and_grad needs jax for the jacobian path (the "
            "forward-only solvers keep their numpy fallback); install "
            "jax[cpu] or finite-difference solve_arrays instead")
    wrt = tuple(wrt)
    for name in wrt:
        if name not in WRT_ARGNUM:
            from ..api.registry import unknown_key_error
            raise unknown_key_error("gradient input", name,
                                    sorted(WRT_ARGNUM))
    n = np.atleast_2d(np.asarray(n, dtype=np.float64))
    f = np.atleast_2d(np.asarray(f, dtype=np.float64))
    bs = np.atleast_2d(np.asarray(bs, dtype=np.float64))
    mode, fixed_aux = _resolve_grad_mode(utilization, saturated)
    forward = solve_arrays(
        n, f, bs, backend=backend, utilization=utilization,
        p0_factor=p0_factor, saturated=saturated)
    B, G = n.shape
    aux = p0_factor if fixed_aux is None else fixed_aux
    n_max = int(n.sum(axis=-1).max()) if (n.size and mode == "recursion") \
        else 0
    n_max_b = backend_mod.bucket(n_max) if n_max else 0
    beta = None if softmin_beta is None else float(softmin_beta)
    argnums = tuple(WRT_ARGNUM[name] for name in wrt)
    Bb = backend_mod.bucket(B)
    solver = backend_mod.jitted(
        ("sharing.grad", mode, beta, argnums, Bb, G, n_max_b),
        lambda: _build_jax_grad_solver(mode, n_max_b, beta, argnums))
    with trace.span("sharing.solve_grad", wrt=",".join(wrt), B=B, G=G,
                    mode=mode):
        with backend_mod.x64():
            jacs = solver(
                jnp.asarray(backend_mod.pad_rows(n, Bb), jnp.float64),
                jnp.asarray(backend_mod.pad_rows(f, Bb), jnp.float64),
                jnp.asarray(backend_mod.pad_rows(bs, Bb), jnp.float64),
                jnp.float64(aux))
    grads = {name: np.asarray(j)[:B]
             for name, j in zip(wrt, jacs)}
    return forward, grads


# ---------------------------------------------------------------------------
# Placement-batched solver: B scenarios × D domains × K groups in one call.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlacedBatchSharePrediction:
    """Solution of B placed scenarios over a padded domain grid.

    The axes are ``(B, D, K)``: B scenarios, each padded to D contention
    domains of up to K groups.  ``mask`` marks the *occupied* lanes —
    cells that carry a real placement (a genuine ``n = 0`` group is
    occupied; a padding lane is not).  Each ``(b, d)`` row is an
    independent Eq. 4–5 instance, so ``b_overlap`` and ``util`` are
    per-domain ``(B, D)`` arrays.
    """

    n: np.ndarray          # (B, D, K) thread counts (masked lanes 0)
    f: np.ndarray          # (B, D, K) request fractions (masked lanes 0)
    bs: np.ndarray         # (B, D, K) saturated bandwidths (masked lanes 0)
    mask: np.ndarray       # (B, D, K) bool, True = occupied lane
    b_overlap: np.ndarray  # (B, D)   Eq. 4 envelopes per domain [GB/s]
    alphas: np.ndarray     # (B, D, K) Eq. 5 request shares within a domain
    util: np.ndarray       # (B, D)   interface utilization per domain
    bw_group: np.ndarray   # (B, D, K) attained bandwidth per lane [GB/s]
    names: tuple[tuple[tuple[str, ...], ...], ...] | None = None

    def __len__(self) -> int:
        return self.bw_group.shape[0]

    @property
    def bw_per_core(self) -> np.ndarray:
        return np.divide(self.bw_group, self.n,
                         out=np.zeros_like(self.bw_group),
                         where=self.n > 0)

    @property
    def domain_bw(self) -> np.ndarray:
        """(B, D) total attained bandwidth per domain [GB/s]."""
        return self.bw_group.sum(axis=-1)

    @property
    def total_bw(self) -> np.ndarray:
        """(B,) aggregate attained bandwidth across every domain."""
        return self.bw_group.sum(axis=(-1, -2))


def solve_placed_batch(n, f, bs, *, mask=None, names=None,
                       utilization: str | float = "recursion",
                       p0_factor: float = 0.5,
                       saturated: bool | None = None,
                       backend: str = "auto"
                       ) -> PlacedBatchSharePrediction:
    """Solve Eqs. 4–5 for B placed scenarios in one flattened call.

    ``n``, ``f``, ``bs``: array-likes of shape ``(B, D, K)`` (a single
    ``(D, K)`` scenario is promoted to B = 1) — B scenarios, each padded
    to a common grid of D contention domains with up to K groups per
    domain.  Every ``(b, d)`` row is an independent Eq. 4–5 instance
    (memory controllers of different domains do not contend), so the
    whole grid flattens to one ``(B·D, K)`` :func:`solve_arrays` call —
    the same padded power-of-two bucketing (and therefore the same
    process-wide jit cache) the unplaced batched path uses, so ragged
    placement sweeps of nearby sizes share one compiled solver.

    ``mask`` marks occupied lanes (default ``n > 0``).  Masked-out lanes
    are forced to the neutral ``n = f = bs = 0`` *before* the solve —
    whatever garbage the padding carries (even NaN) cannot perturb the
    occupied lanes, and empty padded domains attain exactly zero
    bandwidth.  ``backend`` resolves on the flattened ``B·D`` row count
    through the substrate policy.
    """
    n = np.asarray(n, dtype=np.float64)
    if n.ndim == 2:
        n = n[None]
    f = np.broadcast_to(np.asarray(f, dtype=np.float64), n.shape)
    bs = np.broadcast_to(np.asarray(bs, dtype=np.float64), n.shape)
    if n.ndim != 3:
        raise ValueError(
            f"placed batches are (B, D, K) arrays, got shape {n.shape}")
    if mask is None:
        mask = n > 0
    else:
        mask = np.broadcast_to(np.asarray(mask, dtype=bool), n.shape)
    # Select, not multiply: np.where drops poisoned padding (NaN/inf
    # included) instead of propagating it through 0 * NaN.
    zero = np.zeros_like(n)
    n = np.where(mask, n, zero)
    f = np.where(mask, f, zero)
    bs = np.where(mask, bs, zero)
    B, D, K = n.shape
    with trace.span("sharing.solve_placed_batch", B=B, D=D, K=K):
        b, alphas, util, bw = solve_arrays(
            n.reshape(B * D, K), f.reshape(B * D, K), bs.reshape(B * D, K),
            backend=backend, utilization=utilization, p0_factor=p0_factor,
            saturated=saturated)
    return PlacedBatchSharePrediction(
        n=n, f=f, bs=bs, mask=mask,
        b_overlap=b.reshape(B, D), alphas=alphas.reshape(B, D, K),
        util=util.reshape(B, D), bw_group=bw.reshape(B, D, K),
        names=names)


def solve_placed_and_grad(n, f, bs, *, mask=None, names=None,
                          wrt=("f", "b_s"),
                          utilization: str | float = "recursion",
                          p0_factor: float = 0.5,
                          saturated: bool | None = None,
                          softmin_beta: float | None = None,
                          backend: str = "auto"
                          ) -> tuple[PlacedBatchSharePrediction,
                                     dict[str, np.ndarray]]:
    """Placed-grid twin of :func:`solve_arrays_and_grad`.

    Forward is exactly :func:`solve_placed_batch`; ``grads[name]`` has
    shape ``(B, D, K, K)`` with
    ``grads[name][b, d, i, j] = ∂ bw_group[b, d, i] / ∂ name[b, d, j]``
    (domains are independent Eq. 4–5 instances, so there are no cross-
    domain terms).  Masked-out lanes are forced to zero *on both jacobian
    axes*: padding does not exist in the scenario, so its sensitivities —
    including the mathematically nonzero ``"cores"`` column a relaxed
    empty slot would carry — are defined to be 0, and poisoned padding
    (NaN/inf) cannot leak into real lanes' gradients any more than it can
    into their values.
    """
    n = np.asarray(n, dtype=np.float64)
    if n.ndim == 2:
        n = n[None]
    f = np.broadcast_to(np.asarray(f, dtype=np.float64), n.shape)
    bs = np.broadcast_to(np.asarray(bs, dtype=np.float64), n.shape)
    if n.ndim != 3:
        raise ValueError(
            f"placed batches are (B, D, K) arrays, got shape {n.shape}")
    if mask is None:
        mask = n > 0
    else:
        mask = np.broadcast_to(np.asarray(mask, dtype=bool), n.shape)
    zero = np.zeros_like(n)
    n = np.where(mask, n, zero)
    f = np.where(mask, f, zero)
    bs = np.where(mask, bs, zero)
    B, D, K = n.shape
    (b, alphas, util, bw), flat_grads = solve_arrays_and_grad(
        n.reshape(B * D, K), f.reshape(B * D, K), bs.reshape(B * D, K),
        wrt=wrt, utilization=utilization, p0_factor=p0_factor,
        saturated=saturated, softmin_beta=softmin_beta, backend=backend)
    lane = mask[..., :, None] & mask[..., None, :]   # (B, D, K, K)
    grads = {name: np.where(lane, g.reshape(B, D, K, K), 0.0)
             for name, g in flat_grads.items()}
    pred = PlacedBatchSharePrediction(
        n=n, f=f, bs=bs, mask=mask,
        b_overlap=b.reshape(B, D), alphas=alphas.reshape(B, D, K),
        util=util.reshape(B, D), bw_group=bw.reshape(B, D, K),
        names=names)
    return pred, grads


def groups_to_arrays(scenarios: Sequence[Sequence[Group]]
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                tuple[tuple[str, ...], ...]]:
    """Pack ragged per-scenario group lists into padded ``(B, G)`` arrays
    plus a matching ``(B, G)`` grid of group names ("" for padding)."""
    g_max = max((len(s) for s in scenarios), default=0)
    shape = (len(scenarios), max(g_max, 1))
    n = np.zeros(shape)
    f = np.zeros(shape)
    bs = np.zeros(shape)
    names = [[""] * shape[1] for _ in scenarios]
    for i, sc in enumerate(scenarios):
        for j, g in enumerate(sc):
            n[i, j], f[i, j], bs[i, j] = g.n, g.f, g.bs
            names[i][j] = g.name
    return n, f, bs, tuple(tuple(row) for row in names)


def predict_batch(scenarios: Sequence[Sequence[Group]], **kwargs
                  ) -> BatchSharePrediction:
    """Batched :func:`predict` over a list of group lists."""
    return solve_batch(*groups_to_arrays(scenarios), **kwargs)
