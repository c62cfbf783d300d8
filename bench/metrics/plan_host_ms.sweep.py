"""Host time of the placed plan around its flat solve, per window call
[ms]: the ``api.plan.run`` spans of ``repro.obs`` less the
``sharing.solve_arrays`` spans inside them.  That is swapping the new
numbers into the ``(B, D, K)`` grid, masking the padding lanes, reshaping
to ``(B*D, K)`` rows and back, and building the result."""


def read(r):
    calls = r.info.get("calls")
    run = r.span_seconds("api.plan.run")
    solve = r.span_seconds("sharing.solve_arrays")
    if not calls or not run or not solve:
        return None
    return 1e3 * (run - solve) / calls
