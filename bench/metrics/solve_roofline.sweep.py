"""Share of the memory roofline the Eq. 4-5 solver program reaches [%].

The least bytes one solve must move: the float64 inputs n, f and b_s of
shape (rows, K) in, and b (rows), alphas (rows, K), util (rows) and bw
(rows, K) out.  The solve does a few operations per byte, so on a chip
with no float64 peak the memory bound is the one that holds.  The least
time is those bytes over the chip's HBM bandwidth; the share is that time
over the program's device time in the trace."""

from bench.metrics.common import program_seconds
from bench.metrics import solve_bytes

PROGRAM = r"_solve_single_jax"


def read(r):
    found = program_seconds(r, PROGRAM)
    if found is None:
        return None
    secs, runs = found
    need = runs * solve_bytes.solve_bytes(r.info["rows"], r.info["groups"])
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / secs
