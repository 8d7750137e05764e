"""`device_idle_share` in a cell above the knee, where it moves the
completed tokens per second."""
from bench.metrics.device_idle_share import read  # noqa: F401
