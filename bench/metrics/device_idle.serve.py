"""Share of the traced slice in which no operation ran on the device, in
a serving cell."""
from bench.metrics_common import idle_percent as read  # noqa: F401
