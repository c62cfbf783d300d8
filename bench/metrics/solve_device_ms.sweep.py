"""Device time of the Eq. 4-5 solver program per window call [ms]: the
runs of the jitted, vmapped single-scenario solver in the trace, over the
calls the window made."""

from bench.metrics.common import program_seconds

#: The solver program, as the jit names it.
PROGRAM = r"_solve_single_jax"


def read(r):
    found = program_seconds(r, PROGRAM)
    if found is None or not r.info.get("calls"):
        return None
    return 1e3 * found[0] / r.info["calls"]
