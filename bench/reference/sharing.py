"""Plain reference of the bandwidth-sharing model (Afzal, Hager, Wellein,
arXiv:2011.00243, Eqs. 4-5) with the latency-penalty recursion for the
sub-saturated interface utilization (Hofmann et al., ``t_ecm = 1``,
``t_mem = f``, penalty ``p0 = p0_factor * f``).

Written from the paper, independent of the code under test: it imports
nothing from ``repro`` and takes its kernel numbers from the
configuration files under ``bench/configs``.  ``dtype`` is the precision
the whole computation runs in; the configurations state float64, and the
float32 run is the benchmark's control, which has to fail the comparison.
"""

from __future__ import annotations

import numpy as np


def utilization(n_tot, f_mean, *, p0_factor: float = 0.5, dtype=np.float64):
    """Interface utilization ``U(n; f)`` by the recursion
    ``u(1) = f``, ``u(i) = min(1, i f / (1 + p0 u(i-1) (i-1)))``, each
    entry stopped at its own ``n``.  1 where ``n`` or ``f`` is 0."""
    n = np.asarray(n_tot, dtype)
    f = np.asarray(f_mean, dtype)
    p0 = dtype(p0_factor) * f
    u = f.copy()
    n_max = int(n.max()) if n.size else 0
    for i in range(2, n_max + 1):
        t_i = dtype(1) + p0 * u * dtype(i - 1)
        u = np.where(i <= n, np.minimum(dtype(1), dtype(i) * f / t_i), u)
    return np.where((n > 0) & (f > 0), u, dtype(1))


def solve(n, f, bs, *, p0_factor: float = 0.5, dtype=np.float64):
    """Attained bandwidth of every group, ``(..., K)`` arrays in, the
    same shape out [GB/s].  Each row along the last axis is one memory
    interface shared by K groups of ``n`` threads with request fraction
    ``f`` and saturated bandwidth ``bs``; ``n = 0`` marks an empty lane.

    Eq. 4: the saturated envelope is the thread-weighted mean of ``bs``.
    Eq. 5: group g gets the share ``n_g f_g / sum(n f)`` of it, scaled by
    the utilization at the mean request fraction."""
    n = np.asarray(n, dtype)
    f = np.asarray(f, dtype)
    bs = np.asarray(bs, dtype)
    n_tot = n.sum(axis=-1)
    safe = np.maximum(n_tot, dtype(1))
    envelope = np.where(n_tot > 0, (n * bs).sum(axis=-1) / safe, dtype(0))
    w = n * f
    w_sum = w.sum(axis=-1)
    alphas = np.where(w_sum[..., None] > 0,
                      w / np.maximum(w_sum, np.finfo(dtype).tiny)[..., None],
                      dtype(0))
    f_mean = np.where(n_tot > 0, w_sum / safe, dtype(0))
    u = utilization(n_tot, f_mean, p0_factor=p0_factor, dtype=dtype)
    return alphas * (u * envelope)[..., None]


def gaps(got, want) -> np.ndarray:
    """Largest gap of each leading-axis entry between two bandwidth
    arrays, relative to each row's largest reference value (a row with
    all lanes 0 counts absolutely against 1 GB/s); NaN counts as an
    infinite gap."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want).max(axis=-1, keepdims=True), 1.0)
    gap = np.abs(got - want) / scale
    gap = np.where(np.isnan(gap), np.inf, gap)
    return gap.reshape(len(gap), -1).max(axis=1, initial=0.0)


def rel_gap(got, want) -> float:
    """The largest of :func:`gaps`; infinite where the shapes differ."""
    if np.shape(got) != np.shape(want):
        return float("inf")
    return float(gaps(got, want).max(initial=0.0))
