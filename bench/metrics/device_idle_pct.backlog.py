"""Share of the traced window with no XLA op on the device, backlog cells."""
from bench.readers import device_idle_pct as read  # noqa: F401
