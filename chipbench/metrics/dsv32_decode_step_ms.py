"""Device milliseconds of one decode execution (``jit_decode``) in the
DeepSeek-V3.2 cell: ``decode_step_ms`` under the cell's own name."""
from chipbench.metrics.decode_step_ms import read  # noqa: F401
