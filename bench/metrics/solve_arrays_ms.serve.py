"""Host time of the flat Eq. 4-5 solve [ms per coalesced solve]: the
``sharing.solve_arrays`` spans of ``repro.obs`` over the solves the
``serve.tick.batch`` histogram counts.  That is padding the rows, the
copies to and from the device and the wait for its program; nothing
where no solve reached the flat solver."""


def read(r):
    solves, _ = r.histogram("serve.tick.batch")
    secs = r.span_seconds("sharing.solve_arrays")
    if not solves or not secs:
        return None
    return 1e3 * secs / solves
