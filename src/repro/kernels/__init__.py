# EMPA-adapted TPU kernels (Pallas).  Each subpackage:
#   kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
#   ops.py    — jit'd public wrapper (interpret=True off-TPU)
#   ref.py    — pure-jnp oracle used by the allclose tests
#
#   sumup           — SUMUP mass mode: streaming reduction, partials never
#                     leave VMEM (no read/write-back of the running sum)
#   massmap         — FOR mass mode: the grid owns loop control/addressing,
#                     the body is pure payload
#   flash_attention — SUMUP applied to softmax: online (m, l, acc) stream
#   ssd_scan        — Mamba2 SSD: chunk children + sequential-grid parent
#                     state carry (the latched parent-child chain)
#   paged_attention — SUMUP decode attention over the paged KV cache:
#                     scalar-prefetched block tables aim each KV DMA at
#                     the supervisor-rented physical block
#   chunk_attention — span-clamped fragment attention for the serving
#                     tick (contiguous and paged variants)

import re

# Oracle/test pairing manifest: every kernel package must name the
# interpret-mode test file (under tests/kernels/) that asserts it
# allclose against its ref.py.  `python -m repro.analysis.lint`
# cross-checks this map against the package tree — an unlisted package,
# a missing ref.py, or a dead test path fails CI.
KERNEL_TESTS = {
    "sumup": "test_kernels.py",
    "massmap": "test_kernels.py",
    "flash_attention": "test_kernels.py",
    "ssd_scan": "test_kernels.py",
    "paged_attention": "test_paged_attention.py",
    "chunk_attention": "test_chunk_attention.py",
}


def tpu_kernel_names(compiled_text: str) -> set:
    """Names of the Pallas kernels a compiled TPU program calls: the
    ``tpu_custom_call`` instructions of ``Compiled.as_text()``, each
    named after its ``pallas_call(name=...)`` (``paged_attention.3`` ->
    ``paged_attention``)."""
    return set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", compiled_text))
