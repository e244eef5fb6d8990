"""Device milliseconds of ``jit_prefill`` per 1000 prompt tokens in the
DeepSeek-V3.2 cell: ``prefill_ms_per_ktok`` under the cell's own name."""
from chipbench.metrics.prefill_ms_per_ktok import read  # noqa: F401
