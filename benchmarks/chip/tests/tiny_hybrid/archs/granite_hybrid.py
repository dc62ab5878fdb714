"""The tiny hybrid cell's block: the benchmark's own granite-4.0-h block
(``benchmarks/chip/archs/granite_hybrid.py``)."""
from benchmarks.chip import cells

_hybrid = cells.load_arch("granite_hybrid")
shapes = _hybrid.shapes
logits = _hybrid.logits
matmul_params = _hybrid.matmul_params
attention_flops = _hybrid.attention_flops
kv_bytes_per_position = _hybrid.kv_bytes_per_position
state_bytes_per_slot = _hybrid.state_bytes_per_slot
