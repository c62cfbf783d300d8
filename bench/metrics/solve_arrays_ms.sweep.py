"""Host time of the flat Eq. 4-5 solve per window call [ms]: the
``sharing.solve_arrays`` spans of ``repro.obs``, from the host's side.
That is padding the rows to their bucket, the host-to-device and
device-to-host copies and the wait for the device program, whose own time
``solve_device_ms.sweep`` reads."""


def read(r):
    calls = r.info.get("calls")
    solve = r.span_seconds("sharing.solve_arrays")
    if not calls or not solve:
        return None
    return 1e3 * solve / calls
