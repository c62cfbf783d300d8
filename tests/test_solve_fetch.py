"""The flat solve's way in and back: flat inputs, and four flat outputs
in concurrent pieces.

``_solve_arrays_jax`` sends the jitted solver its inputs flat, has it
return each of its four outputs flat and split into pieces of about 2
MiB, copies every piece back at once and lands each output's pieces in
one host buffer.  These tests hold that path to the solver's own four
outputs fetched one by one, bit for bit, in every utilization mode,
padded and in several pieces, and count the bytes it fetches.
"""

import functools
import os

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
        np.testing.assert_array_equal(_bits(g), _bits(r))


@pytest.mark.parametrize("mode", ["queue", "recursion"])
def test_outputs_in_several_pieces_give_the_same_bits(mode):
    # 65536 rows: b and util 512 KiB, one piece each; alphas and bw
    # 65536 x 10 x 8 B = 5 MiB, three pieces of unequal size each.
    n, f, bs = _inputs(40_000, 10)
    assert sharing._fetch_pieces(backend_mod.bucket(40_000) * 10) == 3
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
    flat = [np.arange(k, k + 100 * (k + 1), dtype=np.float64) * np.pi
            for k in range(4)]
    with backend_mod.x64():
        outputs = tuple(tuple(jnp.array_split(jnp.asarray(x), m))
                        for x, m in zip(flat, split))
        got = sharing._fetch_outputs(outputs)
    for g, x in zip(got, flat):
        # One path for every size: a one-piece output lands in a
        # writable buffer of its own too.
        assert g.flags.c_contiguous and g.flags.writeable and g.flags.owndata
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
    # 128 rows or so; the solver takes its three inputs flat instead.
    seen = []
    build = sharing._build_jax_solver

    def recording(mode, n_max, rows):
        solver = build(mode, n_max, rows)

        def solve(*args):
            seen.append([a.shape for a in args])
            return solver(*args)
        return solve

    monkeypatch.setattr(sharing, "_build_jax_solver", recording)
    monkeypatch.setattr(backend_mod, "jitted", lambda key, make: make())
    n, f, bs = _inputs(100, 3)
    sharing._solve_arrays_jax(n, f, bs, **MODES["queue"])
    assert seen == [[(128 * 3,)] * 3 + [()]]


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
    with backend_mod.x64():
        flats = [[np.arange(257 * (k + 1), dtype=np.float64) + c
                  for k in range(4)] for c in range(callers)]
        outputs = [tuple(tuple(jnp.array_split(jnp.asarray(x), 5))
                         for x in flat) for flat in flats]
    got = [None] * callers

    def fetch(c):
        got[c] = sharing._fetch_outputs(outputs[c])

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
