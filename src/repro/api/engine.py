"""The facade's one-shot verbs: ``predict(scenario)`` and
``simulate(scenario)``.

Both are sugar over the two-phase plan API (:mod:`repro.api.plan`):
``predict(x)`` is ``compile(x, verb="predict").run()`` and
``simulate(x)`` is ``compile(x, verb="simulate").run(...)`` — one trace,
one run, results bit-for-bit identical to the compiled path (that
equivalence is a tested invariant).  Callers that evaluate the same
structure repeatedly — sweeps, calibration inner loops, pod-plan
searches — should hold the plan and call ``run`` themselves.

The dispatch table (chosen at compile time, see
:func:`repro.api.plan.compile`):

=====================  =====================================================
scenario shape          engine
=====================  =====================================================
single, unplaced        scalar reference path (``sharing.predict``)
single, placed          topology solver (``topology.predict_placed``)
batch, unplaced         batched array solver (``sharing.solve_arrays``) —
                        numpy, or the substrate's cached jitted jax solver
                        when importable and B is at least
                        ``backend.DEFAULT_JAX_CUTOFF`` (64)
batch, placed on one    placed-grid solver
topology                (``sharing.solve_placed_batch`` over the packed
                        ``(B, D, K)`` occupancy grid; dispatch sees the
                        flattened ``B·D`` row count)
any, ``simulate``       batched desync event engine
                        (``desync_batch.run_encoded``; numpy reference or
                        the cached jitted ``lax.while_loop`` on request;
                        batch × noise-ensemble grids fuse into one run)
=====================  =====================================================

The old module-level entry points stay exactly as they are — they *are*
the engines — so the facade adds dispatch and a uniform result schema
(:mod:`repro.api.results`), never a second implementation.  Backend
resolution itself lives in one place for the whole tree:
:func:`repro.core.backend.resolve`.
"""

from __future__ import annotations

from .plan import compile as compile_plan
from .results import (BatchPrediction, PlacedBatchPrediction, Prediction,
                      SimulationResult)
from .scenario import Scenario, ScenarioBatch


def predict(scenario: Scenario | ScenarioBatch, *,
            backend: str | None = None
            ) -> Prediction | BatchPrediction | PlacedBatchPrediction:
    """Solve the sharing model (Eqs. 4–5) for a scenario or batch.

    One-shot sugar for ``compile(scenario, verb="predict").run(...)``.
    ``backend`` overrides the scenario's own backend option
    (``"numpy"`` / ``"jax"`` / ``"auto"``).  Returns a
    :class:`Prediction` for a single scenario, a
    :class:`BatchPrediction` for an unplaced batch, a
    :class:`PlacedBatchPrediction` for a batch placed on one topology.
    """
    return compile_plan(scenario, verb="predict").run(backend=backend)


def simulate(scenario: Scenario | ScenarioBatch, *,
             backend: str | None = None, t_max: float | None = None,
             on_deadlock: str = "mask") -> SimulationResult:
    """Run a scenario (or batch) through the desync event engine.

    One-shot sugar for ``compile(scenario, verb="simulate").run(...)``.
    A single scenario with ``.with_noise(..., ensemble=B)`` expands to B
    independent noise draws (member seeds derived deterministically from
    the scenario's seed via :func:`repro.api.plan.derive_member_seed`);
    a :class:`ScenarioBatch` simulates its B scenarios, each scenario's
    own ensemble fused in as adjacent rows (``result.members`` maps rows
    back to ``(scenario, member)``).  All members advance in **one**
    batched engine call.

    ``backend`` (``"numpy"`` default / ``"jax"``) and ``t_max`` override
    the scenarios' options; ``on_deadlock`` is the batched engine's
    masking contract (``"mask"`` or ``"raise"``).
    """
    return compile_plan(scenario, verb="simulate").run(
        backend=backend, t_max=t_max, on_deadlock=on_deadlock)
