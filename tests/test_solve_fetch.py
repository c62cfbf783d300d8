"""The batch solve's way in and back: group-major planes in, and four
outputs in planes of concurrent pieces.

``_solve_arrays_jax`` sends the jitted solver its inputs flat in
group-major order (G planes of the padded bucket, one after another),
which the program solves as ``(G, Bb)`` planes; the program returns b
and util as one plane each and alphas and bw as G planes, each plane
split into pieces of about 2 MiB; every piece is copied back at once
and lands in its column of one ``(rows, planes)`` host buffer, so the
batch comes back as ``(B, G)`` rows.  These tests hold that path to the
vmapped row solve, in every utilization mode, padded and in several
pieces, check that the program holds no transpose, and count the bytes
it fetches.
"""

import functools
import os
import re

import numpy as np
import pytest

from repro.core import backend as backend_mod
from repro.core import sharing
from repro.obs import metrics

jax = pytest.importorskip("jax")
jnp = jax.numpy

#: (mode, solve_arrays keywords) for every law the flat solve runs.
MODES = {
    "recursion": dict(utilization="recursion", p0_factor=0.5,
                      saturated=None),
    "queue": dict(utilization="queue", p0_factor=0.5, saturated=None),
    "fixedpoint": dict(utilization="fixedpoint", p0_factor=0.5,
                       saturated=None),
    "fixed": dict(utilization=0.7, p0_factor=0.5, saturated=None),
    "saturated": dict(utilization="recursion", p0_factor=0.5,
                      saturated=True),
}


def _inputs(B, G, seed=0):
    """Thread counts with idle groups and whole idle rows, request
    fractions and saturated bandwidths."""
    rng = np.random.default_rng(seed + 1000 * B + G)
    n = rng.integers(0, 5, (B, G)).astype(np.float64)
    n[::7] = 0.0
    f = rng.uniform(0.05, 1.0, (B, G))
    bs = rng.uniform(10.0, 200.0, (B, G))
    return n, f, bs


def _one_by_one(n, f, bs, mode, kw):
    """The vmapped single-scenario solver on the same padded bucket, its
    four outputs fetched one at a time and sliced to the batch."""
    B, G = n.shape
    n_max = int(n.sum(axis=-1).max()) if mode == "recursion" else 0
    n_max_b = backend_mod.bucket(n_max) if n_max else 0
    aux = {"fixed": kw["utilization"], "saturated": 0.0}.get(
        mode, kw["p0_factor"])
    Bb = backend_mod.bucket(B)
    solver = jax.jit(jax.vmap(
        functools.partial(sharing._solve_single_jax, mode=mode,
                          n_max=n_max_b), in_axes=(0, 0, 0, None)))
    with backend_mod.x64():
        out = solver(*[jnp.asarray(backend_mod.pad_rows(a, Bb))
                       for a in (n, f, bs)], jnp.float64(aux))
        return tuple(np.asarray(x)[:B] for x in out)


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint64)


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("G", [1, 3, 8])
@pytest.mark.parametrize("B", [64, 100, 1027])
def test_flat_fetch_matches_the_four_outputs_bit_for_bit(B, G, mode):
    n, f, bs = _inputs(B, G)
    got = sharing._solve_arrays_jax(n, f, bs, **MODES[mode])
    ref = _one_by_one(n, f, bs, mode, MODES[mode])
    for g, r in zip(got, ref):
        assert g.shape == r.shape
        if G <= 3:
            np.testing.assert_array_equal(_bits(g), _bits(r))
        else:
            # XLA's CPU backend sums a row of eight lanes in another
            # order than eight planes: b and bw may differ in the last
            # bit or two (at most 6.1e-16 relative, G of 5 or 8).
            np.testing.assert_allclose(g, r, rtol=1e-15, atol=0)


@pytest.mark.parametrize("mode", ["queue", "recursion"])
def test_outputs_in_several_pieces_give_the_same_bits(mode):
    # 300,000 rows pad to 2^19: every plane 4 MiB, two pieces; b and
    # util two pieces each, alphas and bw six each.
    n, f, bs = _inputs(300_000, 3)
    assert sharing._fetch_pieces(backend_mod.bucket(300_000)) == 2
    got = sharing._solve_arrays_jax(n, f, bs, **MODES[mode])
    ref = _one_by_one(n, f, bs, mode, MODES[mode])
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(_bits(g), _bits(r))


@pytest.mark.parametrize("values,pieces", [
    (1, 1), (64 * 8, 1), (1 << 18, 1), ((1 << 18) + 1, 2),
    (1 << 21, 8), (3 << 19, 6), (1 << 26, 256)])
def test_fetch_pieces_are_about_two_mib(values, pieces):
    assert sharing._fetch_pieces(values) == pieces


@pytest.mark.parametrize("split", [(1, 1, 1, 1), (1, 7, 1, 3), (2, 2, 5, 5)])
def test_fetch_outputs_lands_every_piece_in_order(split):
    # Outputs of 1, 3, 1 and 2 planes of 100 values each.
    rows = 100
    landed = [np.arange(rows * p, dtype=np.float64).reshape(p, rows).T
              * np.pi + k for k, p in enumerate((1, 3, 1, 2))]
    with backend_mod.x64():
        outputs = tuple(
            tuple(tuple(jnp.array_split(jnp.asarray(plane), m))
                  for plane in x.T)
            for x, m in zip(landed, split))
        got = sharing._fetch_outputs(outputs, rows)
    for g, x in zip(got, landed):
        # One path for every size: a one-piece output lands in a
        # writable buffer of its own too, plane p in column p.
        assert g.flags.c_contiguous and g.flags.writeable and g.flags.owndata
        assert g.shape == x.shape
        np.testing.assert_array_equal(_bits(g), _bits(x))


def test_landing_pool_is_made_once_per_process():
    assert sharing._fetch_pool() is sharing._fetch_pool()


@pytest.mark.parametrize("B,G", [(64, 1), (100, 3), (1027, 8)])
def test_flat_fetch_gives_contiguous_float64_of_the_batch(B, G):
    n, f, bs = _inputs(B, G)
    b, alphas, util, bw = sharing._solve_arrays_jax(
        n, f, bs, **MODES["recursion"])
    assert [x.shape for x in (b, alphas, util, bw)] == [
        (B,), (B, G), (B,), (B, G)]
    for x in (b, alphas, util, bw):
        assert x.dtype == np.float64
        assert x.flags.c_contiguous


def test_inputs_cross_flat(monkeypatch):
    # A (rows, G) float64 array costs a TPU runtime a host transpose per
    # 128 rows or so, and a minor dimension of G fills G of 128 lanes;
    # the solver takes its three inputs flat, one plane a group.
    seen = []
    build = sharing._build_jax_solver

    def recording(mode, n_max, rows):
        solver = build(mode, n_max, rows)

        def solve(*args):
            seen.append([np.asarray(a) for a in args])
            return solver(*args)
        return solve

    monkeypatch.setattr(sharing, "_build_jax_solver", recording)
    monkeypatch.setattr(backend_mod, "jitted", lambda key, make: make())
    n, f, bs = _inputs(100, 3)
    sharing._solve_arrays_jax(n, f, bs, **MODES["queue"])
    [args] = seen
    assert [a.shape for a in args] == [(128 * 3,)] * 3 + [()]
    for flat, a in zip(args, (n, f, bs)):
        planes = flat.reshape(3, 128)
        for g in range(3):
            np.testing.assert_array_equal(
                _bits(planes[g]), _bits(np.r_[a[:, g], np.zeros(28)]))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_planar_solver_lowers_with_no_transpose(mode):
    # The program solves the group-major planes where they lie: a
    # (2^10, 3) bucket reshapes its flat inputs to (3, 8, 128) planes
    # and reduces over the leading group axis, transposing nothing.
    rows, G = 1 << 10, 3
    n_max = 8 if mode == "recursion" else 0
    solver = sharing._build_jax_solver(mode, n_max, (rows, G))
    flat = jax.ShapeDtypeStruct((rows * G,), jnp.float64)
    with backend_mod.x64():
        hlo = solver.lower(flat, flat, flat, jax.ShapeDtypeStruct(
            (), jnp.float64)).as_text()
    assert "tensor<3x8x128xf64>" in hlo
    assert not re.search(r"tensor<\d+x3xf64>", hlo)
    assert "transpose" not in hlo


@pytest.mark.parametrize("B,G", [(5, 0), (0, 3), (70, 0), (0, 0)])
def test_empty_batches_and_groups_keep_their_shapes(B, G):
    n, f, bs = (np.ones((B, G)) for _ in range(3))
    b, alphas, util, bw = sharing._solve_arrays_jax(
        n, f, bs, **MODES["recursion"])
    assert [x.shape for x in (b, alphas, util, bw)] == [
        (B,), (B, G), (B,), (B, G)]


@pytest.mark.parametrize("B,G", [(64, 1), (100, 3), (1027, 8)])
def test_get_bytes_counts_the_outputs_of_every_call(B, G):
    n, f, bs = _inputs(B, G)
    outputs = backend_mod.bucket(B) * (2 * G + 2) * 8
    counter = metrics.counter("sharing.jax.get_bytes")
    before = counter.value
    for calls in (1, 2):
        sharing._solve_arrays_jax(n, f, bs, **MODES["queue"])
        assert counter.value - before == calls * outputs


def test_concurrent_fetches_each_land_their_own_pieces():
    # More callers than cores share the one landing pool; each must get
    # back exactly its own values.
    import sys
    import threading

    callers = 4 * (os.cpu_count() or 1)
    rows = 257
    with backend_mod.x64():
        flats = [[np.arange(rows * (k + 1), dtype=np.float64).reshape(
            k + 1, rows).T + c for k in range(4)] for c in range(callers)]
        outputs = [tuple(tuple(tuple(jnp.array_split(jnp.asarray(p), 5))
                               for p in x.T) for x in flat)
                   for flat in flats]
    got = [None] * callers

    def fetch(c):
        got[c] = sharing._fetch_outputs(outputs[c], rows)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=fetch, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for g, flat in zip(got, flats):
        for x, want in zip(g, flat):
            np.testing.assert_array_equal(_bits(x), _bits(want))
