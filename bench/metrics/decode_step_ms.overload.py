"""Session, in a cell above the knee: ``decode_step_ms``'s reading. With
every slot busy the decode step bounds the completed tokens per second."""
from bench.metrics.decode_step_ms import read  # noqa: F401
