"""The decode step's share of its roofline in the DeepSeek-V3.2 cell
(required work from ``chipbench/work/mla_moe.py``): ``decode_roofline``
under the cell's own name."""
from chipbench.metrics.decode_roofline import read  # noqa: F401
