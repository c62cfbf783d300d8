"""Host time of building the ``Record`` objects of a jax desync run per
window call [ms]: the ``desync.records`` spans around
``_records_from_arrays``."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "desync.records")
