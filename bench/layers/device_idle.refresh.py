"""Device idle share of the refresh-fleet window."""
from bench.layers._shared import device_idle as read  # noqa: F401
