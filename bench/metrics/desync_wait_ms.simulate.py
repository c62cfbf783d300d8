"""Host time of the jax desync engine's run between its copies per window
call [ms]: the ``desync.jax.wait`` spans, from the runner's dispatch, its
inputs already on the device, to ``block_until_ready`` on its outputs.
Against ``desync_us_per_step.simulate`` it shows what of the wait is not
the device loop."""

from bench.metrics.per_call import span_ms


def read(r):
    return span_ms(r, "desync.jax.wait")
