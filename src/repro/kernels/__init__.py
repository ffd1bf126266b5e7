"""Pallas TPU kernels for the compute hot-spots of the AULID read path and
the learned-paged-KV serving path.

Each kernel directory holds:
  <name>.py — the pl.pallas_call with explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper (plane packing, level composition)
  ref.py    — the pure-jnp oracle the tests assert against

TPU adaptation of the paper's I/O model (DESIGN.md §2): a 4 KB disk block
becomes a 4 KB HBM tile; "fetch a block" becomes a scalar-prefetched
HBM->VMEM DMA selected by a BlockSpec index_map; the per-block binary search
becomes a whole-block compare-and-reduce on the VPU.

Keys are uint64 in the host index; TPUs have no native 64-bit lanes, so the
kernels operate on two 32-bit planes (hi, lo) with lexicographic compares.

The serving kernels (``fused_lookup``, ``overlay_merge``) compile for a TPU
v5e (``tests/test_tpu_compile.py`` compiles them for a described chip;
``chip_smoke.py`` runs them on one against the jnp path).  On the CPU they
run only when interpret mode is asked for explicitly (``interpret=True``,
backend ``"fused_interpret"``), which is how the tier-1 tests check them.
"""
from .leaf_search.ops import leaf_search
from .inner_probe.ops import inner_probe_lookup
from .overlay_probe.ops import overlay_probe
from .overlay_merge.ops import (overlay_merge_pack,
                                overlay_merge_pack_stacked,
                                overlay_merge_pack_stacked_mesh)
from .paged_attention.ops import paged_attention
from .fused_lookup.ops import (fused_lookup_batch, fused_lookup_batch_overlay,
                               fused_lookup_batch_sharded,
                               fused_lookup_batch_sharded_overlay)

__all__ = ["leaf_search", "inner_probe_lookup", "overlay_probe",
           "overlay_merge_pack", "overlay_merge_pack_stacked",
           "overlay_merge_pack_stacked_mesh",
           "paged_attention", "fused_lookup_batch",
           "fused_lookup_batch_overlay", "fused_lookup_batch_sharded",
           "fused_lookup_batch_sharded_overlay"]
