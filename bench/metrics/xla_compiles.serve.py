"""XLA executables compiled or loaded from the persistent cache inside
the window [compiles], from the program's ``backend.xla.compiles``
counter.  Set-up warms every tick shape, so it should read 0."""

from bench.metrics.per_call import xla_compiles as read  # noqa: F401
