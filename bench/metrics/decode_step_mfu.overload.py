"""Step programs, in a cell above the knee: ``decode_step_mfu``'s reading.
With every slot busy the decode step bounds the completed tokens per
second."""
from bench.metrics.decode_step_mfu import read  # noqa: F401
