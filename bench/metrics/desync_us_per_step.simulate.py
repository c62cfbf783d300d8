"""Device time of the desync engine's event loop per event step [us]: the
runs of its jitted ``while_loop`` program in the trace, over the steps
the engine reports for the traced calls."""

from bench.metrics.common import program_seconds

PROGRAM = r"^jit_runner$"


def read(r):
    found = program_seconds(r, PROGRAM)
    if found is None or not r.info.get("steps"):
        return None
    return 1e6 * found[0] / r.info["steps"]
