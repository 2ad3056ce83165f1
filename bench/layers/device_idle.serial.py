"""Device idle share of the plan-serial window."""
from bench.layers._shared import device_idle as read  # noqa: F401
