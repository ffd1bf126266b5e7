"""Pallas kernel: device-resident sorted-merge upsert of the delta overlay
(DESIGN.md §14).

The serving write path ships one small sorted batch per step (the writes
drained from ``DeltaOverlay.take_batch``) and folds it into the
device-resident overlay pack in a single launch — two-pointer-merge
semantics (sorted union, batch wins on key collisions, tombstones replayed
as entries) realized without any device-side sort:

* a one-time *rank pass* per shard (grid step 0, persisted in VMEM scratch)
  computes each survivor's output position by rank arithmetic.  Both packs
  hold their live entries as a sorted prefix (u64-max padding sorts last),
  so ranks need no prefix sum: overlay entry ``i`` survives unless the batch
  overwrites it and lands at ``i`` plus the number of *new* batch keys below
  it; live batch entry ``j`` lands at ``j`` plus the number of surviving
  overlay keys below it.  Overwritten overlay keys and padding get a -1
  sentinel.  Positions of survivors and batch entries interleave into one
  dense sorted run by construction.
* each subsequent grid step emits one output tile by one-hot matching the
  position arrays against its slot indices and compare-and-reducing the
  value planes (the ``overlay_probe`` extraction idiom); unmatched slots
  become u64-max padding.

The rank pass builds (chunk, Cb) compare matrices over chunks of the
overlay, so the batch side must stay small — which it is by construction:
Cb is the power-of-two bucket of one step's writes, while reseed-sized
transfers take the host path.  VMEM working set: 10 resident (1, C) planes +
2 scratch rows + one (OB, Ca) match matrix per tile (~4 MB at Ca=4096,
OB=256).

Layout for the TPU compiler (Mosaic): u64 keys travel as (hi, lo) int32
planes with the sign bit flipped ("biased": a signed compare orders them as
unsigned), payloads as raw int32 bits; u64-max padding is ``PAD`` in both
key planes.  Each (S, C) plane enters as (S, 1, C) so a shard row is a
block whose last two dimensions equal the array's.  Trace and lower it with
x64 off (``fused_lookup.run_x32``).  The stacked (S, ·) form merges every
shard of a sharded engine in one launch; grid order is row-major so the rank
scratch is recomputed exactly once per shard row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fused_lookup.fused_lookup import VMEM_LIMIT_BYTES

# python int (not a jnp scalar): kernel bodies must not capture traced
# constants, and an int folds into the trace as a literal
PAD = 2 ** 31 - 1         # biased plane of u64-max padding (raw 0xFFFFFFFF)

# overlay rows per rank-pass chunk: bounds the (chunk, Cb) compare matrices
RANK_CHUNK = 512


def _lt(ah, al, bh, bl):
    """(ah,al) < (bh,bl) on biased planes: exact unsigned u64 compare."""
    return (ah < bh) | ((ah == bh) & (al < bl))


def _kernel(akh_ref, akl_ref, aph_ref, apl_ref, atb_ref,   # (1, Ca) overlay
            bkh_ref, bkl_ref, bph_ref, bpl_ref, btb_ref,   # (1, Cb) batch
            okh_ref, okl_ref, oph_ref, opl_ref, otb_ref,   # (1, OB) out tile
            pa_ref, pb_ref,                                # scratch positions
            *, ob: int):
    t = pl.program_id(1)
    Ca = akh_ref.shape[1]
    Cb = bkh_ref.shape[1]

    @pl.when(t == 0)
    def _rank_pass():
        bh = bkh_ref[...]
        bl = bkl_ref[...]
        lb = ~((bh == PAD) & (bl == PAD))                       # (1, Cb)
        ch = min(Ca, RANK_CHUNK)

        def chunk(c):
            """Overlay rows [c, c+ch) as (ch, 1) columns + their compares
            against the batch row: eq (raw key match) and b < a."""
            ah = akh_ref[:, c:c + ch].reshape(ch, 1)
            al = akl_ref[:, c:c + ch].reshape(ch, 1)
            eq = (ah == bh) & (al == bl)
            return ah, al, eq, _lt(bh, bl, ah, al)

        # pass 1: batch keys that overwrite a live overlay key
        in_a = jnp.zeros((1, Cb), jnp.int32)
        for c in range(0, Ca, ch):
            _, _, eq, _ = chunk(c)
            in_a = in_a + jnp.sum((eq & lb).astype(jnp.int32), axis=0,
                                  keepdims=True, dtype=jnp.int32)
        new_b = lb & (in_a == 0)
        # pass 2: overlay positions; surviving overlay keys below each batch
        # key for the batch positions
        na_lt = jnp.zeros((1, Cb), jnp.int32)
        for c in range(0, Ca, ch):
            ah, al, eq, blt = chunk(c)
            la = ~((ah == PAD) & (al == PAD))                   # (ch, 1)
            in_b = jnp.sum((eq & lb).astype(jnp.int32), axis=1,
                           keepdims=True, dtype=jnp.int32) > 0
            surv = la & ~in_b
            row = c + jax.lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
            pos = row + jnp.sum((blt & new_b).astype(jnp.int32), axis=1,
                                keepdims=True, dtype=jnp.int32)
            pa_ref[:, c:c + ch] = jnp.where(surv, pos, -1).reshape(1, ch)
            below = ~blt & ~eq & surv                           # a < b
            na_lt = na_lt + jnp.sum(below.astype(jnp.int32), axis=0,
                                    keepdims=True, dtype=jnp.int32)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, Cb), 1)
        pb_ref[...] = jnp.where(lb, col + na_lt, -1)

    # one-hot match this tile's slots against the position arrays; the -1
    # sentinel (dropped entries) never matches a slot index >= 0
    slot = t * ob + jax.lax.broadcasted_iota(jnp.int32, (ob, 1), 0)
    sel_a = pa_ref[...] == slot                    # (OB, Ca)
    sel_b = pb_ref[...] == slot                    # (OB, Cb)
    got = (jnp.sum(sel_a.astype(jnp.int32), axis=1, keepdims=True,
                   dtype=jnp.int32)
           + jnp.sum(sel_b.astype(jnp.int32), axis=1, keepdims=True,
                     dtype=jnp.int32)) > 0

    def red(a_ref, b_ref):
        def one(sel, ref):
            return jnp.sum(jnp.where(sel, ref[...], 0), axis=1,
                           keepdims=True, dtype=jnp.int32)
        return one(sel_a, a_ref) + one(sel_b, b_ref)   # (OB, 1)

    def put(ref, col):
        ref[...] = col.reshape(1, ob)

    put(okh_ref, jnp.where(got, red(akh_ref, bkh_ref), PAD))
    put(okl_ref, jnp.where(got, red(akl_ref, bkl_ref), PAD))
    put(oph_ref, red(aph_ref, bph_ref))
    put(opl_ref, red(apl_ref, bpl_ref))
    put(otb_ref, red(atb_ref, btb_ref))


@functools.partial(jax.jit, static_argnames=("cap_out", "interpret"))
def overlay_merge_planes(akh: jnp.ndarray, akl: jnp.ndarray,
                         aph: jnp.ndarray, apl: jnp.ndarray,
                         atb: jnp.ndarray,
                         bkh: jnp.ndarray, bkl: jnp.ndarray,
                         bph: jnp.ndarray, bpl: jnp.ndarray,
                         btb: jnp.ndarray, *,
                         cap_out: int, interpret: bool):
    """Stacked plane merge: overlay int32 planes (S, Ca) (keys biased,
    payload bits, tombstone 0/1) updated by batch planes (S, Cb); returns
    five (S, cap_out) int32 planes in the same layout.  ``cap_out`` must be
    a power of two covering each shard's merged live count; Ca a multiple of
    ``RANK_CHUNK`` when larger than it."""
    S, Ca = akh.shape
    Cb = bkh.shape[1]
    ob = min(cap_out, 256)
    grid = (S, cap_out // ob)
    aspec = pl.BlockSpec((None, 1, Ca), lambda s, t: (s, 0, 0))
    bspec = pl.BlockSpec((None, 1, Cb), lambda s, t: (s, 0, 0))
    ospec = pl.BlockSpec((None, 1, ob), lambda s, t: (s, 0, t))
    rows = [p.reshape(S, 1, -1) for p in (akh, akl, aph, apl, atb,
                                           bkh, bkl, bph, bpl, btb)]
    with jax.enable_x64(False):      # literals stay 32-bit (run_x32)
        out = pl.pallas_call(
            functools.partial(_kernel, ob=ob),
            grid=grid,
            in_specs=[aspec] * 5 + [bspec] * 5,
            out_specs=[ospec] * 5,
            out_shape=[jax.ShapeDtypeStruct((S, 1, cap_out), jnp.int32)] * 5,
            scratch_shapes=[pltpu.VMEM((1, Ca), jnp.int32),
                            pltpu.VMEM((1, Cb), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="aulid_overlay_merge",
        )(*rows)
    return tuple(o.reshape(S, cap_out) for o in out)
