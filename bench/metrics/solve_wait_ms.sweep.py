"""Host time of the flat Eq. 4-5 solve between its copies per window
call [ms]: the ``sharing.jax.wait`` spans, from the solver's dispatch,
its inputs already on the device, to ``block_until_ready`` on its
outputs."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "sharing.jax.wait")
