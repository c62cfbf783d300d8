"""One-chip smoke test: the system's main path on a TPU, end to end.

Run from the root of a checkout, on a machine with one TPU::

    python chip_smoke.py

One process holds the chip and runs every phase in turn:

* ``kernels``  — the 7 Table II map kernels and 4 reductions with
  ``impl="pallas"`` at N = 2**26 f32, and both Jacobi sweeps on an
  8192 x 8192 f32 grid, each against its jnp reference; the compiled HLO
  must hold the Pallas kernel (``tpu_custom_call``);
* ``sweep``    — ``api.compile`` of a placed ``ScenarioBatch`` of 2**16
  scenarios on ``ROME-2S-NPS4`` and on ``TPUv5e-pod4``, run on the jax
  backend and against the numpy backend;
* ``simulate`` — the HPCG-shaped program of ``benchmarks/hpcg_desync.py``
  at 64 ranks with a noise ensemble of 256 on the jax desync engine, its
  first 8 members against the numpy engine;
* ``fit``      — ``calibrate.fit_scaling`` over the full Table II x arch x
  seed grid on jax, against the numpy fit;
* ``serve``    — ``repro.serve``'s ``App`` in-process: one POST of 128
  lines of one structure plus lines of two others, ``/statsz``, drain.

Each phase prints one line with its size, its maximum error against the
reference and its wall seconds.  Those seconds are smoke timings
(compilation included), not benchmark numbers.  Any failure raises, and
the script exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Without a TPU the script exits non-zero before any phase runs.
"""

from __future__ import annotations

import asyncio
import json
import pathlib
import random
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.calibrate import fit_scaling  # noqa: E402
from repro.calibrate.traces import synthesize_ensemble  # noqa: E402
from repro.core import backend  # noqa: E402
from repro.core.machine import TPU_BY_DEVICE_KIND, TPU_V5E  # noqa: E402
from repro.core.table2 import ARCHS, TABLE2  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import App, ServeConfig, client, protocol  # noqa: E402

#: jax vs numpy solvers, both float64 (the verify notes' ~1e-9).
SOLVER_RTOL = 1e-9
#: Desync records, jax vs numpy engine (tests/test_desync_batch.py).
DESYNC_RTOL, DESYNC_ATOL = 1e-9, 1e-15

MAP_KERNELS = {"dscal": 1, "daxpy": 2, "add": 2, "stream": 2, "waxpby": 2,
               "dcopy": 1, "schoenauer": 3}
REDUCE_KERNELS = {"vectorsum": 1, "ddot1": 1, "ddot2": 2, "ddot3": 3}
#: rtol of each kernel against its jnp reference
#: (tests/test_kernels_stream.py, tests/test_kernels_attention.py).
MAP_RTOL, REDUCE_RTOL = 1e-6, 2e-5
JACOBI_KW = dict(ax=0.4, ay=0.6, b1=2.0, relax=0.9)


def report(phase: str, size: str, err: float, seconds: float) -> None:
    print(f"{phase}: {size}; max err {err:.3e}; {seconds:.2f} s "
          f"(smoke timing, not a benchmark)", flush=True)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - want) / scale, initial=0.0))


def _run_kernel(fn, static: tuple, args: tuple, *, interpret: bool,
                **static_kw):
    """Compile the jitted :mod:`repro.kernels.ops` entry ``fn`` for
    ``args``, prove that the Pallas kernel is in it, and run it."""
    compiled = fn.lower(*static, *args, **static_kw).compile()
    if not interpret and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{fn.__name__}{static}: no Pallas kernel "
                             f"in the compiled HLO")
    return jax.block_until_ready(compiled(*args))


@jax.jit
def _kernel_errors(got, want, rtol, atol):
    """(largest relative error, largest excess over ``atol + rtol *
    |want|``), reduced on the device: the arrays never leave it."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    diff = jnp.abs(got - want)
    scale = jnp.maximum(jnp.abs(want), jnp.finfo(jnp.float32).tiny)
    return jnp.max(diff / scale), jnp.max(diff - atol - rtol * jnp.abs(want))


def _check(got, want, *, rtol: float, atol: float = 0.0,
           what: str = "") -> float:
    rel, excess = (float(x) for x in _kernel_errors(got, want, rtol, atol))
    if not excess <= 0.0:
        raise AssertionError(f"{what}: off its reference by {rel:.3e} "
                             f"relative (rtol {rtol}, atol {atol})")
    return rel


def phase_kernels(n: int, width: int, *, interpret: bool,
                  seed: int = 0) -> float:
    """Every Table II Pallas kernel against its jnp reference.  Data and
    scalars are positive, so neither a reduction nor WAXPBY cancels,
    and the error is rounding alone.  Returns the largest relative
    error."""
    impl = "interpret" if interpret else "pallas"
    keys = jax.random.split(jax.random.key(seed), 5)
    arrays = [jax.random.uniform(k, (n,), jnp.float32) for k in keys[:3]]
    errs = []
    for name, n_in in MAP_KERNELS.items():
        s = (jnp.asarray([1.7, 0.3], jnp.float32) if name == "waxpby"
             else jnp.float32(0.7))
        args = (s, *arrays[:n_in])
        got = _run_kernel(ops.stream_map, (name,), args,
                          interpret=interpret, impl=impl)
        errs.append(_check(got, ops.stream_map(name, *args, impl="jnp"),
                           rtol=MAP_RTOL, what=name))
        del got
    for name, n_in in REDUCE_KERNELS.items():
        args = tuple(arrays[:n_in])
        got = _run_kernel(ops.stream_reduce, (name,), args,
                          interpret=interpret, impl=impl)
        errs.append(_check(got, ops.stream_reduce(name, *args, impl="jnp"),
                           rtol=REDUCE_RTOL, what=name))
    del arrays
    grid = jax.random.uniform(keys[3], (width, width), jnp.float32)
    rhs = jax.random.uniform(keys[4], (width, width), jnp.float32)
    got = _run_kernel(ops.jacobi_v1, (), (grid, 0.25), interpret=interpret,
                      impl=impl)
    errs.append(_check(got, ops.jacobi_v1(grid, 0.25, impl="jnp"),
                       rtol=MAP_RTOL, what="jacobi_v1"))
    del got
    got_b, got_r = _run_kernel(ops.jacobi_v2, (), (grid, rhs),
                               interpret=interpret, impl=impl, **JACOBI_KW)
    want_b, want_r = ops.jacobi_v2(grid, rhs, impl="jnp", **JACOBI_KW)
    # The updated grid keeps the absolute floor of its test for values
    # near zero (tests/test_kernels_attention.py); the residual is a
    # reduction over the whole grid.
    _check(got_b, want_b, rtol=1e-5, atol=1e-6, what="jacobi_v2 grid")
    errs.append(_check(got_r, want_r, rtol=REDUCE_RTOL,
                       what="jacobi_v2 residual"))
    return max(errs)


SWEEP_TOPOLOGIES = ("ROME-2S-NPS4", "TPUv5e-pod4")
_ROME_KERNELS = ("DCOPY", "DDOT2", "DAXPY", "Schoenauer", "STREAM",
                 "JacobiL2-v1")


def placed_sweep(topology: str, size: int, seed: int) -> api.ScenarioBatch:
    """``size`` random placements of up to 3 groups of 1-4 threads over
    ``topology``'s domains, within each domain's capacity.  ROME runs
    Table II kernels; the TPU pod runs phases given as ``(f, b_s)`` with
    the chip's HBM bandwidth."""
    rng = random.Random(seed)
    rome = topology.startswith("ROME")
    base = api.Scenario.on("ROME" if rome else "TPU").using(topology)
    domains = base.topo.domains
    scens = []
    for _ in range(size):
        sc, used = base, {}
        for _ in range(rng.randint(1, 3)):
            dom = rng.choice(domains)
            free = dom.n_cores - used.get(dom.name, 0)
            if not free:
                continue
            n = rng.randint(1, min(4, free))
            used[dom.name] = used.get(dom.name, 0) + n
            kernel = (rng.choice(_ROME_KERNELS) if rome
                      else (rng.uniform(0.05, 1.0), TPU_V5E.hbm_bw_gbs))
            sc = sc.placed(kernel, n, dom.name)
        scens.append(sc)
    return api.ScenarioBatch.of(scens)


def phase_sweep(size: int, *, seed: int = 0) -> float:
    """Compile and run a placed sweep per topology on jax; compare every
    group's bandwidth with the numpy backend."""
    errs = []
    for topology in SWEEP_TOPOLOGIES:
        plan = api.compile(placed_sweep(topology, size, seed))
        if plan.backend != "jax":
            raise AssertionError(
                f"{topology}: B={size} resolved to {plan.backend}, not jax")
        got = plan.run().raw.shares
        want = plan.run(backend="numpy").raw.shares
        np.testing.assert_allclose(got.bw_group, want.bw_group,
                                   rtol=SOLVER_RTOL, atol=0.0)
        errs.append(_rel_err(got.bw_group, want.bw_group))
    return max(errs)


MB = 1e6


def hpcg_program(ranks: int, ensemble: int, iterations: int) -> api.Scenario:
    """``benchmarks/hpcg_desync.py``'s fig1 iteration (SymGS -> DDOT2 ->
    allreduce -> DAXPY), ``iterations`` times, with its rank noise, on a
    dual-socket NPS4 Rome node with the ranks spread over the 8 domains."""
    topo = api.Scenario.on("ROME").using("ROME-2S-NPS4")
    domains = topo.topo.domain_names
    sc = (topo.ranks(ranks)
          .on_domains([domains[r * len(domains) // ranks]
                       for r in range(ranks)])
          .with_noise(6e-5, seed=0, ensemble=ensemble))
    for _ in range(iterations):
        sc = (sc.step("Schoenauer", 40 * MB, tag="symgs")
              .step("DDOT2", 8 * MB, tag="ddot2")
              .barrier()
              .step("DAXPY", 30 * MB, tag="daxpy"))
    return sc


def phase_simulate(ranks: int, ensemble: int, iterations: int, *,
                   members: int = 8) -> float:
    """The ensemble on the jax desync engine; its first ``members``
    members against the numpy engine, record for record."""
    got = api.simulate(hpcg_program(ranks, ensemble, iterations),
                       t_max=60.0, backend="jax")
    if got.raw.backend != "jax":
        raise AssertionError(f"simulate ran on {got.raw.backend}")
    k = min(members, ensemble)
    want = api.simulate(hpcg_program(ranks, k, iterations), t_max=60.0,
                        backend="numpy")
    for b in range(k):
        if len(got.records(b)) != len(want.records(b)):
            raise AssertionError(f"member {b}: record counts differ")
    np.testing.assert_allclose(got.raw.start[:k], want.raw.start,
                               rtol=DESYNC_RTOL, atol=DESYNC_ATOL)
    np.testing.assert_allclose(got.raw.end[:k], want.raw.end,
                               rtol=DESYNC_RTOL, atol=DESYNC_ATOL)
    return max(_rel_err(got.raw.start[:k], want.raw.start),
               _rel_err(got.raw.end[:k], want.raw.end))


def phase_fit(kernels, archs, seeds, *, n_events: int = 12_000) -> float:
    """``fit_scaling`` over the (kernel x arch x seed) trace grid on jax,
    against the numpy fit of the same traces."""
    traces = synthesize_ensemble(list(kernels), list(archs), list(seeds),
                                 noise=0.02, n_events=n_events)
    got = fit_scaling(traces, utilization="queue", backend="jax")
    if got.backend != "jax":
        raise AssertionError(f"fit ran on {got.backend}")
    want = fit_scaling(traces, utilization="queue", backend="numpy")
    for field in ("f", "bs"):
        np.testing.assert_allclose(getattr(got, field), getattr(want, field),
                                   rtol=SOLVER_RTOL, err_msg=field)
    return max(_rel_err(got.f, want.f), _rel_err(got.bs, want.bs))


def serve_rows(lines: int) -> list[dict]:
    """``lines`` requests of one structure (they coalesce into one tick
    of that many rows) plus two of each of two other structures."""
    rows = [{"id": k, "arch": "CLX",
             "groups": [{"kernel": "DCOPY", "n": 1 + k % 19},
                        {"kernel": "DDOT2", "n": 20 - (1 + k % 19)}]}
            for k in range(lines)]
    for k in range(2):
        rows.append({"id": lines + k, "arch": "ROME",
                     "groups": [{"kernel": "STREAM", "n": 4 + k},
                                {"kernel": "DDOT1", "n": 8}]})
        rows.append({"id": lines + 2 + k, "arch": "CLX",
                     "groups": [{"kernel": "DAXPY", "n": 3},
                                {"kernel": "DSCAL", "n": 5 + k},
                                {"kernel": "Schoenauer", "n": 6}]})
    return rows


async def _serve(lines: int) -> float:
    app = App(ServeConfig(tick_s=5e-3, max_batch=max(256, lines),
                          default_deadline_s=None))
    before = set(backend.cache_stats()["buckets"])
    port = await app.start("127.0.0.1", 0)
    loop = asyncio.get_running_loop()
    try:
        rows = serve_rows(lines)
        out = await loop.run_in_executor(
            None, lambda: client.solve("127.0.0.1", port, rows))
        status, stats = await loop.run_in_executor(
            None, lambda: client.get_json("127.0.0.1", port, "/statsz"))
    finally:
        await app.shutdown(drain=True)
    bad = [r for r in out if not r.get("ok")]
    if bad or len(out) != len(rows):
        raise AssertionError(f"{len(bad)} of {len(rows)} lines failed: "
                             f"{bad[:2]}")
    if status != 200:
        raise AssertionError(f"/statsz answered {status}")
    compiled = [label for label, b in stats["caches"]["jit"]["buckets"]
                .items() if label.startswith("sharing.solve_batch/")
                and label not in before and b["misses"] >= 1]
    if not compiled:
        raise AssertionError("no sharing.solve_batch jit key was compiled "
                             "by the serve tick")
    errs = []
    for row, resp in zip(rows, out):
        want = api.predict(protocol.parse_request(row).scenario,
                           backend="numpy")
        errs.append(_rel_err(resp["total_bw"], want.total_bw))
    if max(errs) > SOLVER_RTOL:
        raise AssertionError(f"served total_bw off by {max(errs):.3e}")
    return max(errs)


def phase_serve(lines: int) -> float:
    """Serve one POST through ``repro.serve`` in this process and drain."""
    return asyncio.run(_serve(lines))


def check_device() -> dict:
    """The device this process computes on; raises ``SystemExit``
    unless it is a TPU whose kind the machine table knows."""
    device = backend.device_info()
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU visible (JAX platform "
                         f"{device['platform']!r}); refusing to run")
    if device["kind"] not in TPU_BY_DEVICE_KIND:
        raise SystemExit(f"chip_smoke: unknown device_kind "
                         f"{device['kind']!r}; known: "
                         f"{sorted(TPU_BY_DEVICE_KIND)}")
    return device


def main() -> int:
    device = check_device()
    tpu = TPU_BY_DEVICE_KIND[device["kind"]]
    print(f"device: {device['count']} x {device['kind']} "
          f"({tpu.name}, {tpu.hbm_bw_gbs:g} GB/s HBM), jax "
          f"{jax.__version__}", flush=True)
    backend.enable_compile_cache()

    phases = [
        ("kernels", "N=2^26 f32 x 11 Table II kernels, jacobi v1/v2 on "
         "8192x8192 f32",
         lambda: phase_kernels(1 << 26, 8192, interpret=False)),
        ("sweep", "B=2^16 placed scenarios on each of "
         + ", ".join(SWEEP_TOPOLOGIES), lambda: phase_sweep(1 << 16)),
        ("simulate", "HPCG program x 15 iterations, R=64 ranks, E=256",
         lambda: phase_simulate(64, 256, 15)),
        ("fit", f"{len(TABLE2)} kernels x {len(ARCHS)} archs x 3 seeds",
         lambda: phase_fit(sorted(TABLE2), ARCHS, (0, 1, 2))),
        ("serve", "one POST of 128 + 4 ndjson lines",
         lambda: phase_serve(128)),
    ]
    for name, size, run in phases:
        t0 = time.perf_counter()
        err = run()
        report(name, size, err, time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
