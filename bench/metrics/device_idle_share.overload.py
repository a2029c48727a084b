"""Device, in a cell above the knee: ``device_idle_share``'s reading. With
the queue never empty, idle between steps is time no token is made in."""
from bench.metrics.device_idle_share import read  # noqa: F401
