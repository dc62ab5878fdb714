"""The interleaved family's configuration and cache layout
(granite-4.0-h-micro): a mixer kind per layer, muP only where it is
served, the pattern's period, and a cache that pages the attention
layers and keeps per-slot recurrent state for the Mamba layers."""
import dataclasses

import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import model


def test_layer_types_name_every_layer():
    cfg = get_arch("granite-4.0-h-micro")
    assert cfg.layer_period == 10 and cfg.n_attn_layers == 4
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, layer_types=cfg.layer_types[:-1])
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, layer_types=("moe",) * 40)


@pytest.mark.parametrize("field,value", [
    ("residual_multiplier", 0.22), ("attention_multiplier", 0.015625),
    ("layer_types", ("attention",) * 40)])
def test_mup_and_layer_types_refused_outside_the_interleaved_family(
        field, value):
    with pytest.raises(ValueError, match="interleaved"):
        dataclasses.replace(get_arch("granite-3-2b"), **{field: value})


def test_cache_pages_attention_layers_and_keeps_state_per_slot():
    cfg = get_arch("granite-4.0-h-micro")
    cache = model.init_cache(cfg, 16, 4096, abstract_only=True,
                             layout=model.PagedLayout(16, 4096))
    assert cache["k"].shape == cache["v"].shape == (4, 4096, 16, 8, 64)
    assert cache["state"].shape == (36, 16, 64, 64, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (36, 16, 3, 4352)
    assert cache["block_tables"].shape == (16, 256)
    assert cache["state"].size * 4 // 16 == 36 * 64 * 64 * 128 * 4
