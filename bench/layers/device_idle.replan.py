"""Device idle share of the replan window."""
from bench.layers._shared import device_idle as read  # noqa: F401
