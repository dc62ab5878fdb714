"""The dense block with its unembedding tied to the token embedding
(``tie_embeddings``): no ``unembed`` weight, and the reference reads the
logits off the embedding table.  No file of the harness names this
module; a configuration does, by ``"arch": "tied"``."""
from benchmarks.chip import cells

_dense = cells.load_arch("dense")
matmul_params = _dense.matmul_params
attention_flops = _dense.attention_flops
kv_bytes_per_position = _dense.kv_bytes_per_position


def shapes(m: dict) -> dict:
    tree = _dense.shapes(m)
    del tree[("unembed",)]
    return tree


def logits(m: dict, params: dict, tokens, precision: str = "float32"):
    return _dense.logits(m, {**params, "unembed": params["embed"]["tok"]},
                         tokens, precision)
