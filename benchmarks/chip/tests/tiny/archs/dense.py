"""The tiny cells' block: the benchmark's own dense block
(``benchmarks/chip/archs/dense.py``)."""
from benchmarks.chip import cells

_dense = cells.load_arch("dense")
shapes = _dense.shapes
logits = _dense.logits
matmul_params = _dense.matmul_params
attention_flops = _dense.attention_flops
kv_bytes_per_position = _dense.kv_bytes_per_position
