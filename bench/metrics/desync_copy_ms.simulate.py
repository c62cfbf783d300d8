"""Host time of moving the jax desync engine's arrays per window call
[ms]: the ``desync.jax.put`` spans (padding the encoded programs to their
bucket and copying them to the device, until they arrive) and the ``desync.jax.get`` spans (clocks
and records back to NumPy, sliced to the batch)."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "desync.jax.put", "desync.jax.get")
