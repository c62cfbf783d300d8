"""Rate of the flat Eq. 4-5 solve's way back in the window [GB/s]: the
bytes the program's ``sharing.jax.get_bytes`` counter gained, over the
time spent in the ``sharing.jax.get`` spans (the results' copy from the
device into NumPy).  Nothing from a program without the counter or the
span."""

COUNTER = "sharing.jax.get_bytes"


def read(r):
    seconds = r.span_seconds("sharing.jax.get")
    if not r._rows(COUNTER) or not seconds:
        return None
    return r.counter(COUNTER) / seconds / 1e9
