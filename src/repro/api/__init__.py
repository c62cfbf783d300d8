"""The library's front door: declarative scenarios in, predictions out.

The paper's pitch is radical simplicity — two numbers per kernel,
``(f, b_s)``, predict any pairing — and this package is that simplicity
as an API.  Callers state *what* (kernels, machine, placement, noise)::

    from repro import api

    pred = api.predict(api.Scenario.on("CLX")
                       .run("DCOPY", 12).run("DDOT2", 8))
    pred.bw_per_core          # per-core GB/s for each kernel

and the library picks *how*: the scalar reference solver, the batched
numpy solver, the jitted jax backend, or the desync event engine —
see :mod:`repro.api.engine` for the dispatch table.  Callers that
evaluate the same structure repeatedly compile a *plan* instead::

    plan = api.compile(batch)     # trace once: pack, resolve, select jit
    plan.run()                    # bit-for-bit api.predict(batch)
    plan.run(f=f2, b_s=bs2)       # new numbers, no re-trace

Modules:
  scenario — the frozen ``Scenario`` builder + ``ScenarioBatch`` sweeps
  registry — one kernel-spec resolution chain (Table II name →
             calibration → (f, bs) → ECM-from-loop-features) with
             suggestion-bearing lookup errors
  plan     — ``compile``/``Plan.run``: the two-phase API the verbs
             are sugar over (docs/plans.md)
  engine   — ``predict`` / ``simulate`` one-shot sugar
  results  — the unified ``Prediction`` / ``BatchPrediction`` /
             ``SimulationResult`` schema with dict/ndjson export
             (streaming included)

The pre-facade entry points (``sharing.predict``, ``solve_batch``,
``topology.predict_placed``, ``DesyncSimulator``/``run_batch``,
``calibrate.fit_scaling``) remain supported — they are the engines the
facade dispatches to, and facade results are bit-for-bit theirs.
"""

from .engine import JAX_BATCH_CUTOFF, predict, simulate
from .plan import (BatchPlan, PlacedBatchPlan, PlacedPlan, Plan,
                   ScalarPlan, SimulatePlan, compile, derive_member_seed,
                   infer_verb, node_key, structure_key)
from .registry import (PROVENANCES, ResolvedSpec, from_loop_features,
                       from_static_analysis, known_archs, known_kernels,
                       resolve, suggest, unknown_key_error,
                       unknown_key_message)
from .results import (BatchPrediction, DomainShare, GroupShare,
                      PlacedBatchPrediction, Prediction, Sensitivities,
                      SimulationResult, dump_dicts, dump_ndjson,
                      iter_ndjson, load_ndjson)
from .scenario import (DEFAULT_WORK_BYTES, Noise, RunSpec, Scenario,
                       ScenarioBatch, StepSpec)

__all__ = [
    "predict", "simulate", "JAX_BATCH_CUTOFF",
    "compile", "Plan", "ScalarPlan", "PlacedPlan", "BatchPlan",
    "PlacedBatchPlan", "SimulatePlan", "derive_member_seed",
    "infer_verb", "node_key", "structure_key",
    "Scenario", "ScenarioBatch", "RunSpec", "StepSpec", "Noise",
    "DEFAULT_WORK_BYTES",
    "resolve", "ResolvedSpec", "from_loop_features",
    "from_static_analysis", "PROVENANCES", "known_kernels",
    "known_archs", "suggest", "unknown_key_error", "unknown_key_message",
    "Prediction", "BatchPrediction", "PlacedBatchPrediction",
    "SimulationResult", "Sensitivities", "GroupShare", "DomainShare",
    "dump_ndjson", "iter_ndjson", "dump_dicts", "load_ndjson",
]
