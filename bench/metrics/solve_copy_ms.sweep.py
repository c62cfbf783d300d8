"""Host time of moving the flat Eq. 4-5 solve's arrays per window call
[ms]: the ``sharing.jax.put`` spans (padding the float64 n, f and b_s to
their bucket and copying them to the device, until they arrive) and the
``sharing.jax.get`` spans
(b, alphas, util and bw back to NumPy, sliced to the batch)."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "sharing.jax.put", "sharing.jax.get")
