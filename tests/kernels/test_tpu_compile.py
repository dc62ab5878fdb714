"""Compile the served attention kernels for a TPU v5e that is described,
not attached, at granite-3-2b widths (H 32, Hkv 8, D 64, block 16, bf16),
and the paged decode kernel also at the D 128 widths of granite-8b,
starcoder2-3b and moonshot-v1-16b-a3b.

Interpret mode runs a kernel's math but not Mosaic's rules: tiling
(a block's last two dimensions must be (8, 128)-divisible or whole),
VMEM limits and partitioning are checked only by the TPU compiler.  Each
test compiles one kernel entry with ``interpret=False`` and asserts that
the compiled program calls it as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside a module fixture (never at import
time): only one process at a time may load the TPU compiler library, and
every test worker imports this file.  Where it cannot be described, the
fixture skips.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tpu_kernel_names

H, HKV, D = 32, 8, 64                 # configs/granite_3_2b.py
GRANITE = (H, HKV, D)
SLOTS, MAX_SEQ, BS = 8, 2048, 16      # chip_smoke.py's serving shape
PAGES = SLOTS * MAX_SEQ // BS
NB = MAX_SEQ // BS
WIDE, NARROW = 128, 5                 # solo-prefill width, spec_k + 1
SMOKE = (SLOTS, MAX_SEQ, PAGES)
BENCH = (16, 4096, 768)               # the on-chip benchmark's deployment


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _args(sharding, c, paged, shape=SMOKE, widths=GRANITE):
    slots, max_seq, pages = shape
    h, hkv, d = widths
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    kv = (pages, BS, hkv, d) if paged else (slots, max_seq, hkv, d)
    q = (slots, h, d) if c is None else (slots, c, h, d)
    args = [s(q), s(kv), s(kv)]
    if paged:
        args.append(s((slots, max_seq // BS), jnp.int32))
    args.append(s((slots,) if c is None else (slots, c), jnp.int32))
    return args


@pytest.mark.parametrize("name,c,paged,shape,widths", [
    pytest.param("paged_attention", None, True, SMOKE, GRANITE,
                 id="paged_attention-None-True"),
    pytest.param("paged_attention", None, True, BENCH, GRANITE,
                 id="paged_attention-None-True-16x4096"),
    pytest.param("paged_attention", None, True, SMOKE, (32, 8, 128),
                 id="paged_attention-None-True-granite-8b"),
    pytest.param("paged_attention", None, True, SMOKE, (24, 2, 128),
                 id="paged_attention-None-True-starcoder2-3b"),
    pytest.param("paged_attention", None, True, SMOKE, (16, 16, 128),
                 id="paged_attention-None-True-moonshot"),
    pytest.param("chunk_attention_wide", WIDE, False, SMOKE, GRANITE,
                 id="chunk_attention_wide-128-False"),
    pytest.param("chunk_attention_narrow", NARROW, False, SMOKE, GRANITE,
                 id="chunk_attention_narrow-5-False"),
    pytest.param("paged_chunk_attention_wide", WIDE, True, SMOKE, GRANITE,
                 id="paged_chunk_attention_wide-128-True"),
    pytest.param("paged_chunk_attention_narrow", NARROW, True, SMOKE,
                 GRANITE, id="paged_chunk_attention_narrow-5-True"),
])
def test_kernel_compiles_for_v5e(topo, name, c, paged, shape, widths):
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.chunk_attention import kernel as chunk_kernel
    from repro.kernels.paged_attention import kernel as paged_kernel
    module = paged_kernel if name == "paged_attention" else chunk_kernel
    call = getattr(module, f"{name}_call")
    args = _args(SingleDeviceSharding(topo.devices[0]), c, paged, shape,
                 widths)
    compiled = jax.jit(
        lambda *a: call(*a, interpret=False)).lower(*args).compile()
    assert tpu_kernel_names(compiled.as_text()) == {name}


@pytest.mark.parametrize("entry,c,paged,kernel", [
    ("paged_attention_sharded", None, True, "paged_attention"),
    ("chunk_attention_kernel_sharded", WIDE, False, "chunk_attention_wide"),
    ("paged_chunk_attention_kernel_sharded", NARROW, True,
     "paged_chunk_attention_narrow"),
])
def test_sharded_twin_compiles_on_4_chips(topo, monkeypatch, entry, c, paged,
                                          kernel):
    """Head-sharded over a model=4 mesh of the described chips: each
    shard runs the kernel on its 2 KV heads, and no collective is needed
    (heads never mix in attention)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import chunk_attention, paged_attention
    from repro.kernels.chunk_attention import ops as chunk_ops
    from repro.kernels.paged_attention import ops as paged_ops
    # the dispatchers ask jax.default_backend(), which is the CPU here
    monkeypatch.setattr(chunk_ops, "_interpret", lambda: False)
    monkeypatch.setattr(paged_ops, "_interpret", lambda: False)
    jax.clear_caches()
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    args = _args(None, c, paged)
    head_axis = 1 if c is None else 2
    specs = []
    for a in args:
        spec = [None] * len(a.shape)
        if a.dtype == jnp.bfloat16:
            spec[head_axis if a is args[0] else 2] = "model"
        specs.append(NamedSharding(mesh, P(*spec)))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a, s in zip(args, specs)]
    fn = getattr(paged_attention if entry == "paged_attention_sharded"
                 else chunk_attention, entry)
    try:
        compiled = jax.jit(lambda *a: fn(*a, mesh=mesh)).lower(*args) \
            .compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    assert tpu_kernel_names(text) == {kernel}
    assert "all-gather" not in text and "all-reduce" not in text
    assert compiled.output_shardings.spec == specs[0].spec
