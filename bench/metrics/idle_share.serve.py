"""Share of the traced window in which the device ran nothing [%]."""

from bench.metrics.common import idle_share as read  # noqa: F401
