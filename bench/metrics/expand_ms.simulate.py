"""Host time of expanding a noise ensemble into member programs per
window call [ms]: the ``api.compile.expand`` spans, the loop over members
that draws each member's noise and builds its rank programs."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "api.compile.expand")
