"""`batch_rows_mean` in a cell above the knee, where it moves the
completed tokens per second."""
from bench.metrics.batch_rows_mean import read  # noqa: F401
