"""The decode step's required FLOPs over its device time times the
peak, in the DeepSeek-V3.2 cell: ``decode_mfu`` under the cell's own name."""
from chipbench.metrics.decode_mfu import read  # noqa: F401
