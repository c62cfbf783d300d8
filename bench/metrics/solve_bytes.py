"""Bytes one Eq. 4-5 solve needs at least: the operation count and
traffic functions of the benchmark, kept with it."""

F64 = 8


def solve_bytes(rows: int, groups: int) -> int:
    """float64 n, f, b_s of (rows, groups) read; b and util of (rows,)
    and alphas and bw of (rows, groups) written."""
    return F64 * (3 * rows * groups + 2 * rows + 2 * rows * groups)
