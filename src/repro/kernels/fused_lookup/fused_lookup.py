"""Fused Pallas lookup kernel: route → inner probe → leaf search → overlay
merge in ONE launch (DESIGN.md §10).

The sibling kernels (``inner_probe``, ``leaf_search``, ``overlay_probe``)
each cover one traversal stage and need a separate launch per stage — with
the inner probe re-launched once per level because its scalar-prefetched
BlockSpec row indices must be known *before* the launch.  Fusing the stages
moves the row computation into the kernel, so the whole batched read pipeline
of ``core.lookup`` runs as one grid over query tiles:

* **resident pools** — the slot/node/PA/BT pools (AULID's "inner part cached
  in RAM", paper §5.1) and the packed delta overlay ride constant-index-map
  BlockSpecs: they stream HBM→VMEM once and stay resident across the grid.
* **route** — the shard id is one plane-split compare against the boundary
  table (the in-kernel twin of ``lookup_batch_sharded``'s searchsorted);
  every pool gather then offsets by ``sid * pool_len``, replicating the
  vmapped per-shard ``mode="clip"`` semantics exactly.  Monolithic mirrors
  are the S=1 special case of the same kernel.
* **inner probe** — the unrolled ``height``-round traversal of
  ``lookup_batch``: FMCD slot prediction (f32, see below), a successor-chain
  walk of deterministic plane-split max-key compares, tag dispatch with
  whole-block PA/BT searches.
* **leaf search** — per the tuning layer either *persistent* (leaf pool also
  VMEM-resident) or *looped* (leaf pool stays in HBM via ``pl.ANY``; an
  in-kernel ``make_async_copy`` DMAs exactly ONE ``(4, C)`` leaf row per
  query — the paper's one-block-per-probe fetch, executed literally).
* **overlay merge** — ``_overlay_probe``'s sorted-pack consultation happens
  in-register on the resident overlay planes; an overlay hit wins, a
  tombstone hides the key.

Layout for the TPU compiler (Mosaic): every operand is a 32-bit plane.  u64
keys travel as (hi, lo) int32 planes with the sign bit flipped ("biased"), so
a signed compare orders them as unsigned; payloads travel as raw int32 bits.
Per-query state is a ``(qb, 1)`` column (query tiles are ``(qb, 1)`` blocks of
a ``(T*qb, 1)`` array, aligned to the (8, 128) tiling), pools are ``(1, X)``
rows, so a one-hot gather is one broadcast compare and a lane reduction.  The
kernel is traced and lowered with x64 off (:func:`run_x32`): Mosaic has no
64-bit types.

The FMCD prediction needs no 64-bit type: the host re-anchors each node's
model as ``v = slope * (q - K) + c`` with an integer key offset ``K``
(``ops.node_anchor_models``), the kernel forms ``q - K`` exactly in u32
planes and evaluates ``v`` in f32.  The host checks per mirror that this ``v``
is within one slot of the jnp path's f64 one; the kernel then starts its
successor-chain walk ``PRED_MARGIN`` slots left of ``floor(v)`` (the jnp path
starts one slot left) and walks ``KERNEL_STALE_STEPS`` entries (one more than
``STALE_STEPS``), so it lands on the same responsible entry.  Every other
step mirrors ``core.lookup.lookup_batch`` / ``lookup_batch_overlay`` /
``lookup_batch_sharded`` operation for operation (same clips, same ``% cap``
wraps, same merge order), which is what the bit-identical parity suite
``tests/test_fused_lookup.py`` asserts.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.lookup import (STALE_STEPS, TAG_BT, TAG_DATA, TAG_MIXED,
                            TAG_PA)

# python ints (not jnp scalars): kernel bodies must not capture traced
# constants, and an int folds into the trace as a literal
SIGN = -2 ** 31           # int32 sign bit: biased plane <-> raw u32 bits
PRED_MARGIN = 2           # start slot = floor(v) - PRED_MARGIN (jnp: 1)
KERNEL_STALE_STEPS = STALE_STEPS + 1

# scoped VMEM the compiled kernel may use: the tuner's resident-pool budget
# plus the (qb, X) one-hot temporaries (v5e cores hold 128 MiB of VMEM)
VMEM_LIMIT_BYTES = 64 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Static kernel shape: pool geometry + resolved tile strategy.

    Hashable — it keys the jit cache of :func:`fused_lookup_planes`."""
    num_shards: int
    slot_pool: int          # Sm — slots per shard
    node_pool: int          # Nm
    pa_pool: int
    pa_cap: int
    bt_pool: int
    bt_cap: int
    leaf_pool: int          # Lm — leaf rows per shard
    leaf_cap: int           # C
    bounds_len: int         # padded boundary-table length
    overlay_cap: int        # K (>= 1 even when unused)
    qb: int                 # queries per grid step
    height: int
    stale_steps: int
    leaf_resident: bool     # persistent (True) vs looped leaf stage
    gather: str             # "take" (interpret only) | "onehot"
    sharded: bool           # route against bounds (False -> sid = 0)
    has_overlay: bool


def run_x32(fn, *args, **kw):
    """Call a jitted kernel entry with x64 off, so that it is traced AND
    lowered with 32-bit literals (``core.lookup`` turns x64 on globally)."""
    with jax.enable_x64(False):
        return fn(*args, **kw)


def _lt(ah, al, bh, bl):
    """(ah,al) < (bh,bl) on biased planes: exact unsigned u64 compare."""
    return (ah < bh) | ((ah == bh) & (al < bl))


def _u32_f32(x):
    """The unsigned value of int32 bits ``x`` as f32, rounded once: both
    16-bit halves convert exactly, the sum rounds."""
    hi = jax.lax.shift_right_logical(x, 16)
    return hi.astype(jnp.float32) * 65536.0 + (x & 0xFFFF).astype(jnp.float32)


def _diff_f32(ah, al, bh, bl, a_lt_b):
    """|a - b| of two u64 values given as biased planes, as f32 (relative
    error < 3 * 2^-24): the u64 difference in the right order, exactly in
    raw u32 planes with a borrow, then one f32 conversion per half."""
    xh = jnp.where(a_lt_b, bh, ah)
    xl = jnp.where(a_lt_b, bl, al)
    yh = jnp.where(a_lt_b, ah, bh)
    yl = jnp.where(a_lt_b, al, bl)
    borrow = (xl < yl).astype(jnp.int32)          # biased: unsigned compare
    dl = (xl ^ SIGN) - (yl ^ SIGN)                # raw bits, wraps mod 2^32
    dh = (xh ^ SIGN) - (yh ^ SIGN) - borrow
    return _u32_f32(dh) * 4294967296.0 + _u32_f32(dl)


def _make_kernel(cfg: KernelConfig):
    QB = cfg.qb
    Sm, Nm, Pm, Bm, Lm = (cfg.slot_pool, cfg.node_pool, cfg.pa_pool,
                          cfg.bt_pool, cfg.leaf_pool)
    pc, bc, lc = cfg.pa_cap, cfg.bt_cap, cfg.leaf_cap
    take = cfg.gather == "take"

    def lanes(n):
        return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def gv(row, idx):
        """row (1, X), idx (QB, 1) pre-clipped -> row[idx] (QB, 1)."""
        if take:
            return jnp.take(row[0], idx[:, 0], mode="clip")[:, None]
        return jnp.sum(jnp.where(lanes(row.shape[1]) == idx, row,
                                 jnp.zeros((), row.dtype)),
                       axis=1, keepdims=True, dtype=row.dtype)

    def at_lane(row, pos):
        """row (1, C), pos (1, 1) -> row[0, pos] as (1, 1)."""
        return jnp.sum(jnp.where(lanes(row.shape[1]) == pos, row, 0),
                       axis=1, keepdims=True, dtype=jnp.int32)

    def count_lt(kh, kl, qh, ql):
        """Keys of each row below its query: (R, C) planes vs (R, 1)."""
        return jnp.sum(_lt(kh, kl, qh, ql).astype(jnp.int32), axis=1,
                       keepdims=True, dtype=jnp.int32)

    def wrap(pos, cap):
        """``pos % cap`` for pos in [0, cap]."""
        return jnp.where(pos == cap, 0, pos)

    def per_query(body, init, qh, ql):
        """Run ``body(j, sel, qhj, qlj, carry)`` for every query j of the
        tile; ``sel`` is the (QB, 1) one-hot of j, ``qhj``/``qlj`` its key
        planes as (1, 1).  The compiled path's row gathers go through here:
        a scalar row index drives one dynamic row load (or DMA) per query."""
        iq = jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)

        def step(j, carry):
            sel = iq == j
            qhj = jnp.sum(jnp.where(sel, qh, 0), axis=0, keepdims=True,
                          dtype=jnp.int32)
            qlj = jnp.sum(jnp.where(sel, ql, 0), axis=0, keepdims=True,
                          dtype=jnp.int32)
            return body(j, sel, qhj, qlj, carry)

        return jax.lax.fori_loop(0, QB, step, init)

    def scalar_at(col, sel):
        return jnp.sum(jnp.where(sel, col, 0), dtype=jnp.int32)

    def kernel(ts_ref,                                   # scalar prefetch (T,)
               qh_ref, ql_ref,                           # (QB, 1) query planes
               slots_ref, skey_ref,                      # slot pools
               node_i_ref, node_f_ref,                   # node tables
               pak_ref, pap_ref, btk_ref, btp_ref,       # PA / BT pools
               leaf_ref,                                 # (S*Lm, 4, lc)
               meta_ref, llm_ref, bounds_ref,            # per-shard meta
               ovk_ref, ovt_ref,                         # overlay planes
               ph_ref, pl_ref, fnd_ref, lf_ref, sid_ref,  # (QB, 1) outputs
               *scratch):
        del ts_ref
        qh = qh_ref[...]
        ql = ql_ref[...]
        zero = jnp.zeros((QB, 1), jnp.int32)

        # ---- route: sid = count(bounds < q), the searchsorted-left twin
        if cfg.sharded:
            sid = count_lt(bounds_ref[0:1, :], bounds_ref[1:2, :], qh, ql)
        else:
            sid = zero

        root = gv(meta_ref[0:1, :], sid)
        last_row = gv(meta_ref[1:2, :], sid)
        # metanode shortcut: q >= last_leaf_min goes straight to the last leaf
        in_last = ~_lt(qh, ql, gv(llm_ref[0:1, :], sid),
                       gv(llm_ref[1:2, :], sid))

        node = jnp.maximum(root, 0)
        done = in_last | (root < 0)
        leaf = jnp.where(done, last_row, -1)

        tags = slots_ref[0:1, :]
        ptrs = slots_ref[1:2, :]
        nocc = slots_ref[2:3, :]
        succ = slots_ref[3:4, :]
        skh = skey_ref[0:1, :]
        skl = skey_ref[1:2, :]

        for _ in range(cfg.height):
            nidx = sid * Nm + jnp.clip(node, 0, Nm - 1)
            base = gv(node_i_ref[0:1, :], nidx)
            fanout = gv(node_i_ref[1:2, :], nidx)
            overflow = gv(node_i_ref[2:3, :], nidx)
            kh = gv(node_i_ref[3:4, :], nidx)
            kl = gv(node_i_ref[4:5, :], nidx)
            slope = gv(node_f_ref[0:1, :], nidx)
            c = gv(node_f_ref[1:2, :], nidx)
            below = _lt(qh, ql, kh, kl)
            d = _diff_f32(qh, ql, kh, kl, below)
            v = slope * jnp.where(below, -d, d) + c
            pred = jnp.clip(jnp.floor(v) - float(PRED_MARGIN), 0.0,
                            (fanout - 1).astype(jnp.float32)
                            ).astype(jnp.int32)
            s = gv(nocc, sid * Sm + jnp.clip(base + pred, 0, Sm - 1))
            s = jnp.where(s < 0, overflow, s)
            # stale-skip walk along the successor chain (max key < q)
            for _ in range(cfg.stale_steps):
                scl = sid * Sm + jnp.clip(s, 0, Sm - 1)
                stale = (s >= 0) & _lt(gv(skh, scl), gv(skl, scl), qh, ql)
                s = jnp.where(stale, gv(succ, scl), s)
            ended = s < 0
            scl = sid * Sm + jnp.clip(s, 0, Sm - 1)
            tag = gv(tags, scl)
            ptr = gv(ptrs, scl)

            # PA / BT: one whole-block plane-split search per level
            parow = sid * Pm + jnp.clip(jnp.maximum(ptr, 0), 0, Pm - 1)
            btrow = sid * Bm + jnp.clip(jnp.maximum(ptr, 0), 0, Bm - 1)
            if take:
                def block_hit(keys_ref, ptrs_ref, rows, cap):
                    r = rows[:, 0]
                    kh_m = jnp.take(keys_ref[0], r, axis=0, mode="clip")
                    kl_m = jnp.take(keys_ref[1], r, axis=0, mode="clip")
                    pos = wrap(count_lt(kh_m, kl_m, qh, ql), cap)
                    p_m = jnp.take(ptrs_ref[...], r, axis=0, mode="clip")
                    return jnp.take_along_axis(p_m, pos, axis=1)

                pa_hit = block_hit(pak_ref, pap_ref, parow, pc)
                bt_hit = block_hit(btk_ref, btp_ref, btrow, bc)
            else:
                def block_body(j, sel, qhj, qlj, carry):
                    pa_acc, bt_acc = carry
                    out = []
                    for keys_ref, ptrs_ref, rows, cap, acc in (
                            (pak_ref, pap_ref, parow, pc, pa_acc),
                            (btk_ref, btp_ref, btrow, bc, bt_acc)):
                        r = scalar_at(rows, sel)
                        kh_r = keys_ref[0, pl.ds(r, 1), :]
                        kl_r = keys_ref[1, pl.ds(r, 1), :]
                        pos = wrap(count_lt(kh_r, kl_r, qhj, qlj), cap)
                        hit = at_lane(ptrs_ref[pl.ds(r, 1), :], pos)
                        out.append(jnp.where(sel, hit, acc))
                    return tuple(out)

                pa_hit, bt_hit = per_query(block_body, (zero, zero), qh, ql)

            is_mixed = (tag == TAG_MIXED) & ~ended
            step_leaf = jnp.where(ended, last_row,
                        jnp.where(tag == TAG_DATA, ptr,
                        jnp.where(tag == TAG_PA, pa_hit,
                        jnp.where(tag == TAG_BT, bt_hit, -1))))
            newly = ~done & ~is_mixed
            leaf = jnp.where(newly, step_leaf, leaf)
            done = done | newly
            node = jnp.where(~done & is_mixed, ptr, node)

        # ---- leaf stage
        leaf = jnp.maximum(leaf, 0)
        lrow = sid * Lm + jnp.clip(leaf, 0, Lm - 1)
        if cfg.leaf_resident and take:
            rows = jnp.take(leaf_ref[...], lrow[:, 0], axis=0, mode="clip")
            kh_m, kl_m = rows[:, 0, :], rows[:, 1, :]
            pos = count_lt(kh_m, kl_m, qh, ql)
            posm = wrap(pos, lc)

            def col(m):
                return jnp.take_along_axis(m, posm, axis=1)

            fnd = (pos < lc) & (col(kh_m) == qh) & (col(kl_m) == ql)
            pay_h = col(rows[:, 2, :])
            pay_l = col(rows[:, 3, :])
        else:
            def leaf_body(j, sel, qhj, qlj, carry):
                ph_a, pl_a, f_a = carry
                r = scalar_at(lrow, sel)
                if cfg.leaf_resident:
                    row = leaf_ref[r]
                else:
                    vscr, sem = scratch
                    cp = pltpu.make_async_copy(leaf_ref.at[r], vscr, sem)
                    cp.start()
                    cp.wait()
                    row = vscr[...]
                rkh, rkl = row[0:1, :], row[1:2, :]
                pos = count_lt(rkh, rkl, qhj, qlj)
                posm = wrap(pos, lc)
                fj = (pos < lc) & (at_lane(rkh, posm) == qhj) \
                    & (at_lane(rkl, posm) == qlj)
                return (jnp.where(sel, at_lane(row[2:3, :], posm), ph_a),
                        jnp.where(sel, at_lane(row[3:4, :], posm), pl_a),
                        jnp.where(sel, fj.astype(jnp.int32), f_a))

            pay_h, pay_l, f_i = per_query(leaf_body, (zero, zero, zero),
                                          qh, ql)
            fnd = f_i != 0

        pay_h = jnp.where(fnd, pay_h, 0)
        pay_l = jnp.where(fnd, pay_l, 0)

        # ---- overlay merge, in-register on the resident pack
        if cfg.has_overlay:
            okh, okl = ovk_ref[0:1, :], ovk_ref[1:2, :]
            K = okh.shape[1]
            opos = count_lt(okh, okl, qh, ql)
            oposc = jnp.clip(opos, 0, K - 1)
            hit = (opos < K) & (gv(okh, oposc) == qh) & (gv(okl, oposc) == ql)
            tomb = hit & (gv(ovt_ref[0:1, :], oposc) != 0)
            win = hit & ~tomb
            pay_h = jnp.where(win, gv(ovk_ref[2:3, :], oposc), pay_h)
            pay_l = jnp.where(win, gv(ovk_ref[3:4, :], oposc), pay_l)
            fnd = win | (~hit & fnd)       # no select of i1 values in Mosaic
            pay_h = jnp.where(fnd, pay_h, 0)
            pay_l = jnp.where(fnd, pay_l, 0)

        ph_ref[...] = pay_h
        pl_ref[...] = pay_l
        fnd_ref[...] = fnd.astype(jnp.int32)
        lf_ref[...] = leaf
        sid_ref[...] = sid

    return kernel


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def fused_lookup_planes(cfg: KernelConfig, tile_starts, qh, ql,
                        slots_i32, slot_key, node_i32, node_f32,
                        pa_keys, pa_ptrs, bt_keys, bt_ptrs, leaf_pack,
                        meta, llm, bounds, ov_planes, ov_tomb, *,
                        interpret: bool):
    """Launch the fused kernel over ``(qb, 1)`` query-plane tiles.

    ``qh``/``ql`` are ``(T*qb, 1)`` biased int32 planes; ``tile_starts``
    (T,) i32 is the scalar-prefetched grid→tile map driving the query/output
    BlockSpec index maps (identity today; the indirection is the hook for
    tile reordering).  Returns five ``(T*qb, 1)`` int32 columns: payload
    hi/lo bits, found, local leaf row, shard id.  Call it through
    :func:`run_x32`."""
    QB = cfg.qb
    T = qh.shape[0] // QB
    S = cfg.num_shards

    tile = pl.BlockSpec((QB, 1), lambda i, ts: (ts[i], 0))

    def res2(r, c):          # VMEM-resident across the grid: constant map
        return pl.BlockSpec((r, c), lambda i, ts: (0, 0))

    def res3(a, b, c):
        return pl.BlockSpec((a, b, c), lambda i, ts: (0, 0, 0))

    if cfg.leaf_resident:
        leaf_spec = res3(S * cfg.leaf_pool, 4, cfg.leaf_cap)
        scratch = []
    else:
        leaf_spec = pl.BlockSpec(memory_space=pl.ANY)  # stays in HBM
        scratch = [pltpu.VMEM((4, cfg.leaf_cap), jnp.int32),
                   pltpu.SemaphoreType.DMA]

    in_specs = [
        tile, tile,                                        # qh, ql
        res2(4, S * cfg.slot_pool), res2(2, S * cfg.slot_pool),
        res2(5, S * cfg.node_pool), res2(2, S * cfg.node_pool),
        res3(2, S * cfg.pa_pool, cfg.pa_cap),
        res2(S * cfg.pa_pool, cfg.pa_cap),
        res3(2, S * cfg.bt_pool, cfg.bt_cap),
        res2(S * cfg.bt_pool, cfg.bt_cap),
        leaf_spec,
        res2(2, S), res2(2, S), res2(2, cfg.bounds_len),
        res2(4, cfg.overlay_cap), res2(1, cfg.overlay_cap),
    ]
    with jax.enable_x64(False):      # literals stay 32-bit (run_x32)
        return pl.pallas_call(
            _make_kernel(cfg),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(T,),
                in_specs=in_specs,
                out_specs=[tile] * 5,
                scratch_shapes=scratch,
            ),
            out_shape=[jax.ShapeDtypeStruct((T * QB, 1), jnp.int32)] * 5,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="aulid_fused_lookup",
        )(tile_starts, qh, ql, slots_i32, slot_key, node_i32, node_f32,
          pa_keys, pa_ptrs, bt_keys, bt_ptrs, leaf_pack, meta, llm, bounds,
          ov_planes, ov_tomb)
