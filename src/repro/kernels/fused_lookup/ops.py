"""Driver for the fused lookup kernel: operand packing, caching, strategy
resolution, and entry points mirroring the ``core.lookup`` signatures.

The kernel consumes u32-plane-packed pools (``fused_lookup.py`` module doc);
packing a mirror costs one pass over every pool, so prepared operands are
cached per snapshot.  The cache key is the snapshot's monotonic token
(``snap_token`` / ``ov_token``, stamped by every mutation path in
``core.lookup``): tokens are process-unique and never recycled, so — unlike
the ``id(dict)`` keying this replaced — a garbage-collected snapshot's key
can never be reissued to a new one and silently serve a stale pack
(DESIGN.md §10 caveat).  Unstamped dicts (hand-built test operands) fall
back to identity keying with the dict pinned so its id cannot be recycled
while the entry lives.  The cache is a small bounded LRU.

Entry points (drop-in for the jnp read path, same return conventions):

* :func:`fused_lookup_batch`            == ``lookup_batch``
* :func:`fused_lookup_batch_overlay`    == ``lookup_batch_overlay``
* :func:`fused_lookup_batch_sharded`    == ``lookup_batch_sharded``
* :func:`fused_lookup_batch_sharded_overlay`
                                        == ``lookup_batch_sharded_overlay``

``interpret`` is always the caller's explicit choice (default: compiled);
nothing here falls back to interpret mode.  Strategy defaults to
:func:`tuning.choose_strategy`; pass one explicitly (or via
:func:`autotune_strategy`) to override.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict

import jax
import numpy as np

from ...core.lookup import _DEVICE_FIELDS, psum_disjoint

import jax.numpy as jnp  # noqa: E402  (x64 enabled by the lookup import)
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from . import tuning  # noqa: E402
from .fused_lookup import (KERNEL_STALE_STEPS, KernelConfig,  # noqa: E402
                           fused_lookup_planes, run_x32)

UMAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_MIN_BOUNDS = 8  # boundary-table pad floor (u64-max filled, never counted)


# ----------------------------------------------------------------- capability
def compiled_backend_available() -> tuple[bool, str]:
    """Whether a real (non-interpret) kernel launch is available, plus a
    human-readable reason when it is not."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True, "tpu"
    return False, (f"no Pallas-capable backend (jax default_backend="
                   f"{backend!r}); the compiled kernel needs a TPU")


# ------------------------------------------------------------ operand packing
def _planes(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    a = np.ascontiguousarray(a, dtype=np.uint64)
    return ((a >> np.uint64(32)).astype(np.uint32),
            (a & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _bits(a: np.ndarray) -> np.ndarray:
    """u32 plane -> the same bits as int32 (payload planes)."""
    return np.ascontiguousarray(a, dtype=np.uint32).view(np.int32)


def _key_planes(a: np.ndarray) -> np.ndarray:
    """u64 keys -> (2, ...) biased int32 (hi, lo) planes: the sign bit is
    flipped, so a signed int32 compare orders them as unsigned (the
    kernel's plane layout, ``fused_lookup`` module doc)."""
    sign = np.uint32(0x80000000)
    return np.stack([_bits(p ^ sign) for p in _planes(a)])


# the FMCD re-anchoring may move the kernel's slot estimate by less than one
# slot from the jnp path's f64 one (module doc of ``fused_lookup``)
MAX_PRED_ERROR = 1.0


def node_anchor_models(slope: np.ndarray, intercept: np.ndarray,
                       fanout: np.ndarray):
    """Re-anchor FMCD node models for the kernel's f32 prediction:
    ``slope*q + intercept == slope*(q - K) + c`` with ``K`` the integer key
    nearest the model's zero and ``c`` evaluated in extended precision.

    Returns (K u64, slope f32, c f32, bound) where ``bound`` is a worst-case
    bound, in slots, on the distance between the kernel's f32 estimate and
    the jnp path's f64 ``slope*q + intercept`` over the queries whose slot
    is not clipped: the f32 evaluation (7 roundings of 2^-24 relative on
    values up to ``fanout + |c| + 2``), the f64 evaluation it must agree
    with (its query conversion, product and sum, each 2^-53 relative on up
    to ``|intercept| + fanout + |c| + 2``) and the extended-precision ``c``.
    """
    ld = np.longdouble
    s = np.asarray(slope, dtype=np.float64)
    b = np.asarray(intercept, dtype=np.float64)
    f = np.asarray(fanout, dtype=np.float64)
    k = np.zeros(s.shape, dtype=ld)
    nz = s != 0
    k[nz] = np.rint(-b[nz].astype(ld) / s[nz].astype(ld))
    K = np.clip(k, 0, ld(2.0 ** 64 - 2.0 ** 11)).astype(np.uint64)
    c = (s.astype(ld) * K.astype(ld) + b.astype(ld)).astype(np.float64)
    ac = np.abs(c)
    bound = (2.0 ** -21 * (f + ac + 2)
             + 2.0 ** -52 * (np.abs(b) + f + ac + 2) + 2.0 ** -53 * (f + 2)
             + float(np.finfo(ld).eps) * (np.abs(s) * K.astype(np.float64)
                                          + np.abs(b)))
    return (K, s.astype(np.float32), c.astype(np.float32),
            float(bound.max()) if bound.size else 0.0)


class FusedOperands:
    """Plane-packed device operands for one mirror snapshot."""

    def __init__(self, arrs: dict):
        stacked = arrs["leaf_keys"].ndim == 3

        def flat(name):
            v = np.asarray(arrs[name])
            return v.reshape(-1, *v.shape[2:]) if stacked else v

        self.sharded = stacked
        self.slots_i32 = jnp.asarray(np.stack([
            flat("slot_tag").astype(np.int32),
            flat("slot_ptr").astype(np.int32),
            flat("next_occ").astype(np.int32),
            flat("succ_slot").astype(np.int32)]))
        self.slot_key = jnp.asarray(_key_planes(flat("slot_key")))
        fanout = flat("node_fanout").astype(np.int32)
        K, slope, c, self.pred_error = node_anchor_models(
            flat("node_slope"), flat("node_intercept"), fanout)
        if not self.pred_error < MAX_PRED_ERROR:
            raise ValueError(
                f"fused kernel: the f32 slot prediction of this mirror may "
                f"be {self.pred_error:.3g} slots off the f64 one (limit "
                f"{MAX_PRED_ERROR}); its FMCD models need the jnp path")
        self.node_i32 = jnp.asarray(np.concatenate([np.stack([
            flat("node_base").astype(np.int32), fanout,
            flat("node_overflow_slot").astype(np.int32)]), _key_planes(K)]))
        self.node_f32 = jnp.asarray(np.stack([slope, c]))
        self.pa_keys = jnp.asarray(_key_planes(flat("pa_keys")))
        self.pa_ptrs = jnp.asarray(flat("pa_ptrs").astype(np.int32))
        self.bt_keys = jnp.asarray(_key_planes(flat("bt_keys")))
        self.bt_ptrs = jnp.asarray(flat("bt_ptrs").astype(np.int32))
        lkh, lkl = _key_planes(flat("leaf_keys"))
        lph, lpl = (_bits(p) for p in _planes(flat("leaf_pay")))
        self.leaf_pack = jnp.asarray(
            np.stack([lkh, lkl, lph, lpl], axis=1))       # (R, 4, C)

        if stacked:
            meta = np.asarray(arrs["meta"]).T.astype(np.int32)    # (2, S)
            llm = _key_planes(np.asarray(arrs["last_leaf_min"]))
            raw = np.asarray(arrs["bounds"])
            nb = max(_MIN_BOUNDS, int(raw.shape[0]))
            pad = np.full(nb, UMAX, dtype=np.uint64)
            pad[: raw.shape[0]] = raw
            bounds = _key_planes(pad)
        else:
            meta = np.asarray(arrs["meta"]).reshape(2, 1).astype(np.int32)
            llm = _key_planes(np.asarray(arrs["last_leaf_min"]).reshape(1))
            bounds = _key_planes(np.full(1, UMAX, dtype=np.uint64))
        self.meta = jnp.asarray(meta)
        self.llm = jnp.asarray(llm)
        self.bounds = jnp.asarray(bounds)
        self.geom = tuning.PoolGeometry.from_device_arrays(arrs)

    def pool_args(self) -> tuple:
        return (self.slots_i32, self.slot_key, self.node_i32, self.node_f32,
                self.pa_keys, self.pa_ptrs, self.bt_keys, self.bt_ptrs,
                self.leaf_pack, self.meta, self.llm, self.bounds)


def u64_planes(a, bias: bool):
    """u64 -> (hi, lo) int32 planes on device; keys are biased (sign bit
    flipped).  (Scalar constants are built inside the traced call, after
    core.lookup's import enabled x64 — a module-level jnp.uint64 would
    silently truncate to u32.)"""
    sign = jnp.uint32(0x80000000 if bias else 0)
    return tuple(jax.lax.bitcast_convert_type(p ^ sign, jnp.int32) for p in (
        (a >> jnp.uint64(32)).astype(jnp.uint32),
        (a & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)))


def u64_join(hi, lo, bias: bool):
    """Inverse of :func:`u64_planes`."""
    sign = jnp.uint32(0x80000000 if bias else 0)

    def u64(p):
        return (jax.lax.bitcast_convert_type(p, jnp.uint32) ^ sign) \
            .astype(jnp.uint64)

    return (u64(hi) << jnp.uint64(32)) | u64(lo)


@jax.jit
def _overlay_planes_jit(pack: jnp.ndarray):
    """(3, cap) u64 pack -> ((4, cap) int32 key/payload planes, (1, cap)
    int32 tombstones), entirely on device: overlay packs produced by the
    device-resident merge kernel (DESIGN.md §14) are re-planed with zero
    D2H/H2D traffic — one tiny shift/mask dispatch per fresh ov_token."""
    return (jnp.stack([*u64_planes(pack[0], True),
                       *u64_planes(pack[1], False)]),
            (pack[2] != 0).astype(jnp.int32).reshape(1, -1))


class OverlayOperands:
    def __init__(self, ovr: dict):
        pack = ovr["ov_pack"]
        if not isinstance(pack, jnp.ndarray):
            pack = jnp.asarray(np.asarray(pack, dtype=np.uint64))
        self.ov_u32, self.ov_tomb = _overlay_planes_jit(pack)
        self.cap = int(pack.shape[1])


_EMPTY_OVERLAY = None  # lazily built (4,1)/(1,1) placeholder operands


def _empty_overlay_args() -> tuple:
    global _EMPTY_OVERLAY
    if _EMPTY_OVERLAY is None:
        _EMPTY_OVERLAY = (jnp.zeros((4, 1), jnp.int32),
                          jnp.zeros((1, 1), jnp.int32))
    return _EMPTY_OVERLAY


# snapshot token (or pinned dict id for unstamped dicts) -> prepared
# operands; bounded LRU (module doc)
_FP_FIELDS = _DEVICE_FIELDS + ["meta", "last_leaf_min", "bounds"]
_OPERANDS: "OrderedDict[tuple, tuple]" = OrderedDict()
_OV_OPERANDS: "OrderedDict[tuple, tuple]" = OrderedDict()
_CACHE_LIMIT = 16


def clear_operand_cache() -> None:
    _OPERANDS.clear()
    _OV_OPERANDS.clear()
    _MESH_OPERANDS.clear()   # defined in the mesh section below


def _cached(cache: OrderedDict, src: dict, fingerprint: tuple, build,
            token=None):
    """Prepared-operand lookup.  Token-stamped snapshots key by the token
    (never recycled -> no pinning needed); unstamped dicts key by identity
    and pin the dict.  The member-array fingerprint guards both against
    in-place mutation of a cached dict — a mismatch rebuilds."""
    if token is not None:
        key, pin = ("tok", int(token)), None
    else:
        key, pin = ("id", id(src)), src
    ent = cache.get(key)
    if ent is not None and (pin is None or ent[0] is src) \
            and ent[1] == fingerprint:
        cache.move_to_end(key)
        return ent[2]
    ops = build(src)
    cache[key] = (pin, fingerprint, ops)
    cache.move_to_end(key)
    while len(cache) > _CACHE_LIMIT:
        cache.popitem(last=False)
    return ops


def _operands(arrs: dict) -> FusedOperands:
    # member-array identities + the boundary-table version BY VALUE
    # (DESIGN.md §12): every split/merge builds a fresh pack with a fresh
    # snap_token, but the version term also hard-invalidates unstamped
    # (identity-keyed) dicts whose bounds were swapped under them — the
    # in-kernel route must only ever read the pinned version's bounds
    fp = tuple(id(arrs[f]) for f in _FP_FIELDS if f in arrs) \
        + (arrs.get("bounds_version", 0),)
    return _cached(_OPERANDS, arrs, fp, FusedOperands,
                   token=arrs.get("snap_token"))


def _overlay_operands(ovr: dict) -> OverlayOperands:
    return _cached(_OV_OPERANDS, ovr, (id(ovr["ov_pack"]),), OverlayOperands,
                   token=ovr.get("ov_token"))


# ------------------------------------------------------------------ execution
def _pad_tiles(q, qb: int):
    """u64 queries -> two (T*qb, 1) biased int32 planes, u64-max padded to a
    tile multiple (the same never-matching sentinel the engines'
    ``pad_queries`` uses)."""
    q = np.asarray(q).astype(np.uint64)
    Q = q.shape[0]
    T = max(-(-Q // qb), 1)
    qp = np.full(T * qb, UMAX, dtype=np.uint64)
    qp[:Q] = q
    hi, lo = _key_planes(qp)
    return (jnp.asarray(hi.reshape(-1, 1)), jnp.asarray(lo.reshape(-1, 1)),
            Q, T)


def _check_mode(st: tuning.TileStrategy, interpret: bool) -> None:
    if st.gather == "take" and not interpret:
        raise ValueError("gather='take' has no TPU lowering; it runs in "
                         "interpret mode only")


def kernel_config(g: tuning.PoolGeometry, st: tuning.TileStrategy,
                  height: int, *, bounds_len: int, overlay_cap: int,
                  sharded: bool, has_overlay: bool) -> KernelConfig:
    """The static kernel shape for pools of geometry ``g`` (per device)
    under strategy ``st``."""
    return KernelConfig(
        num_shards=g.num_shards, slot_pool=g.slot_pool,
        node_pool=g.node_pool, pa_pool=g.pa_pool, pa_cap=g.pa_cap,
        bt_pool=g.bt_pool, bt_cap=g.bt_cap, leaf_pool=g.leaf_pool,
        leaf_cap=g.leaf_cap, bounds_len=bounds_len,
        overlay_cap=overlay_cap, qb=st.qb, height=int(height),
        stale_steps=KERNEL_STALE_STEPS,
        leaf_resident=(st.leaf == "persistent"),
        gather=st.gather, sharded=sharded, has_overlay=has_overlay)


def _run(arrs: dict, ovr: dict | None, q, height: int,
         interpret: bool, strategy: tuning.TileStrategy | None):
    ops = _operands(arrs)
    if ovr is not None:
        ovo = _overlay_operands(ovr)
        ov_args, ov_cap, has_ov = (ovo.ov_u32, ovo.ov_tomb), ovo.cap, True
    else:
        ov_args, ov_cap, has_ov = _empty_overlay_args(), 1, False
    geom = (ops.geom if not has_ov else
            tuning.PoolGeometry.from_device_arrays(arrs, ovr))
    st = strategy or tuning.choose_strategy(geom, interpret=interpret)
    _check_mode(st, interpret)
    g = ops.geom
    cfg = kernel_config(g, st, height, bounds_len=int(ops.bounds.shape[1]),
                        overlay_cap=ov_cap, sharded=ops.sharded,
                        has_overlay=has_ov)
    qh, ql, Q, T = _pad_tiles(q, st.qb)
    tile_starts = jnp.asarray(np.arange(T, dtype=np.int32))
    ph, plo, fnd, leaf, sid = run_x32(
        fused_lookup_planes, cfg, tile_starts, qh, ql, *ops.pool_args(),
        *ov_args, interpret=interpret)
    pay = u64_join(ph.reshape(-1)[:Q], plo.reshape(-1)[:Q], False)
    found = fnd.reshape(-1)[:Q].astype(bool)
    leaf = leaf.reshape(-1)[:Q]
    sid = sid.reshape(-1)[:Q]
    return pay, found, leaf, sid, g


# --------------------------------------------------------------- entry points
def fused_lookup_batch(arrs: dict, q, height: int = 3, *,
                       interpret=False, strategy=None):
    """Fused-kernel twin of ``lookup_batch`` (pay, found, leaf_row)."""
    pay, found, leaf, _, _ = _run(arrs, None, q, height, interpret, strategy)
    return pay, found, leaf


def fused_lookup_batch_overlay(arrs: dict, ovr: dict, q, height: int = 3, *,
                               interpret=False, strategy=None):
    """Fused-kernel twin of ``lookup_batch_overlay``."""
    pay, found, leaf, _, _ = _run(arrs, ovr, q, height, interpret, strategy)
    return pay, found, leaf


def fused_lookup_batch_sharded(stk: dict, q, height: int = 3, *,
                               qcap=None, interpret=False, strategy=None):
    """Fused-kernel twin of ``lookup_batch_sharded`` (pay, found, global
    leaf row, shard id).  ``qcap`` is accepted for signature compatibility;
    lane packing is a vmap artifact the fused route does not need."""
    del qcap
    pay, found, leaf, sid, g = _run(stk, None, q, height, interpret, strategy)
    return pay, found, sid * g.leaf_pool + leaf, sid


def fused_lookup_batch_sharded_overlay(stk: dict, ovr: dict, q,
                                       height: int = 3, *, qcap=None,
                                       interpret=False, strategy=None):
    """Fused-kernel twin of ``lookup_batch_sharded_overlay``."""
    del qcap
    pay, found, leaf, sid, g = _run(stk, ovr, q, height, interpret, strategy)
    return pay, found, sid * g.leaf_pool + leaf


# ----------------------------------------------------------------------- mesh
# Mesh-placed fused read path (DESIGN.md §13): the plane-packed pools shard
# along their row axis — every pool's row count is S * <per-shard pool>, and
# the engine pads S to a device multiple, so the leading-axis split lands
# exactly on shard boundaries.  Each device runs the SAME kernel body over
# its local S/D-shard slice with a shifted boundary-table window, masks the
# queries it does not own to the u64-max sentinel, and the (B,)-sized result
# planes ``psum`` together — disjoint ownership means each slot is written by
# exactly one device.  Overlay merge happens outside ``shard_map`` on the
# replicated packed overlay, identical to the jnp mesh path in
# ``core.lookup``.

_POOL_SPECS = (
    PartitionSpec(None, "shards"),          # slots_i32   (4, S*slot)
    PartitionSpec(None, "shards"),          # slot_key    (2, S*slot)
    PartitionSpec(None, "shards"),          # node_i32    (5, S*node)
    PartitionSpec(None, "shards"),          # node_f32    (2, S*node)
    PartitionSpec(None, "shards", None),    # pa_keys     (2, S*pa, cap)
    PartitionSpec("shards", None),          # pa_ptrs     (S*pa, cap)
    PartitionSpec(None, "shards", None),    # bt_keys     (2, S*bt, cap)
    PartitionSpec("shards", None),          # bt_ptrs     (S*bt, cap)
    PartitionSpec("shards", None, None),    # leaf_pack   (S*leaf, 4, C)
    PartitionSpec(None, "shards"),          # meta        (2, S)
    PartitionSpec(None, "shards"),          # llm         (2, S)
)


class MeshFusedOperands:
    """Mesh placement of one :class:`FusedOperands` pack.

    Pools go on the devices row-sharded (``_POOL_SPECS``); the boundary
    planes are rebuilt replicated and padded to ``(D-1)*S_local +
    bounds_len`` u64-max entries so every device can ``dynamic_slice`` its
    own ``bounds_len``-wide window at offset ``d * S_local`` — the in-kernel
    route count over that window IS the local shard id for owned queries
    (bounds are sorted; entries left of the window are all < q)."""

    def __init__(self, ops: FusedOperands, mesh, bounds_u64: np.ndarray):
        S = ops.geom.num_shards
        D = int(mesh.shape["shards"])
        if S % D:
            raise ValueError(
                f"mesh fused lookup: {S} shard slots not divisible by "
                f"{D} mesh devices")
        self.S, self.D = S, D
        self.Sl = S // D
        self.nbl = max(_MIN_BOUNDS, self.Sl)
        plen = (D - 1) * self.Sl + self.nbl
        pad = np.full(plen, UMAX, dtype=np.uint64)
        raw = np.asarray(bounds_u64, dtype=np.uint64)
        pad[: raw.shape[0]] = raw
        self.bounds_planes = jax.device_put(
            jnp.asarray(_key_planes(pad)),
            NamedSharding(mesh, PartitionSpec()))
        self.bounds_u64 = jax.device_put(
            jnp.asarray(raw), NamedSharding(mesh, PartitionSpec()))
        pools = ops.pool_args()[:-1]        # all but the single-device bounds
        self.pools = tuple(
            jax.device_put(a, NamedSharding(mesh, spec))
            for a, spec in zip(pools, _POOL_SPECS))
        self.geom = ops.geom


_MESH_OPERANDS: "OrderedDict[tuple, tuple]" = OrderedDict()
_MESH_CACHE_LIMIT = 8


def _mesh_operands(ops: FusedOperands, mesh, bounds_u64) -> MeshFusedOperands:
    # keyed by pack identity + mesh; the pack is pinned so its id cannot be
    # recycled while the entry lives (same discipline as ``_cached``)
    key = (id(ops), mesh)
    ent = _MESH_OPERANDS.get(key)
    if ent is not None and ent[0] is ops:
        _MESH_OPERANDS.move_to_end(key)
        return ent[1]
    mops = MeshFusedOperands(ops, mesh, bounds_u64)
    _MESH_OPERANDS[key] = (ops, mops)
    _MESH_OPERANDS.move_to_end(key)
    while len(_MESH_OPERANDS) > _MESH_CACHE_LIMIT:
        _MESH_OPERANDS.popitem(last=False)
    return mops


# The mesh call runs in three jitted stages so that the kernel stage is
# traced and lowered with x64 off (``run_x32``) while routing and the result
# gather keep their u64 arithmetic: route (per device: owned queries,
# compacted owned-first into the qcap launch window) -> kernel -> unroute
# (scatter back to request order, psum of the disjoint contributions).

@functools.partial(jax.jit, static_argnames=("mesh", "Sl", "qcap", "qb"))
def _mesh_route(mesh, bounds, q, *, Sl: int, qcap: int, qb: int):
    T = max(-(-qcap // qb), 1)

    def body(bounds, qq):
        d = jax.lax.axis_index("shards").astype(jnp.int32)
        sid = jnp.searchsorted(bounds, qq, side="left").astype(jnp.int32)
        local = sid - d * Sl
        owned = (local >= 0) & (local < Sl) & (qq != jnp.uint64(UMAX))
        n_owned = jnp.sum(owned.astype(jnp.int32))
        # owned-first compaction into the qcap launch window; slots past
        # n_owned (and any non-owned spill when qcap == Q) masked to the
        # never-matching sentinel
        order = jnp.argsort(~owned, stable=True)
        qsel = jnp.take(qq, order)[:qcap]
        qsel = jnp.where(jnp.arange(qcap) < n_owned, qsel, jnp.uint64(UMAX))
        qpad = jnp.full((T * qb,), jnp.uint64(UMAX)).at[:qcap].set(qsel)
        qh, ql = u64_planes(qpad, True)
        return (qh.reshape(-1, 1), ql.reshape(-1, 1), order[None],
                owned[None], sid[None])

    rows = PartitionSpec("shards", None)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec(), PartitionSpec()),
                         out_specs=(rows,) * 5, check_vma=False)(bounds, q)


@functools.partial(jax.jit, static_argnames=("mesh", "cfg", "interpret"))
def _mesh_kernel(mesh, cfg, interpret, pools, bpad, qh, ql):
    Sl = cfg.num_shards

    def body(pools, bpad, qh, ql):
        d = jax.lax.axis_index("shards").astype(jnp.int32)
        lb = jax.lax.dynamic_slice(
            bpad, (jnp.int32(0), d * Sl), (2, cfg.bounds_len))
        ts = jnp.arange(qh.shape[0] // cfg.qb, dtype=jnp.int32)
        # fresh placeholder overlay operands: the module-level
        # _empty_overlay_args cache must not capture tracers
        ovk = jnp.zeros((4, 1), jnp.int32)
        ovt = jnp.zeros((1, 1), jnp.int32)
        return fused_lookup_planes(cfg, ts, qh, ql, *pools, lb, ovk, ovt,
                                   interpret=interpret)

    rows = PartitionSpec("shards", None)
    # check_vma=False: pallas_call has no replication rule
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_POOL_SPECS, PartitionSpec(), rows, rows),
        out_specs=(rows,) * 5, check_vma=False)(pools, bpad, qh, ql)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "Sl", "qcap", "leaf_pool"))
def _mesh_unroute(mesh, outs, order, owned, sid, *, Sl: int, qcap: int,
                  leaf_pool: int):
    def body(ph, plo, fnd, leaf, lsid, order, owned, sid):
        d = jax.lax.axis_index("shards").astype(jnp.int32)
        order, owned, sid = order[0], owned[0], sid[0]
        Q = order.shape[0]
        pay = u64_join(ph.reshape(-1)[:qcap], plo.reshape(-1)[:qcap],
                       False)
        fnd = fnd.reshape(-1)[:qcap]
        lsid = lsid.reshape(-1)[:qcap]
        leaf = leaf.reshape(-1)[:qcap]
        gleaf = (d * Sl + jnp.clip(lsid, 0, Sl - 1)) * leaf_pool + leaf
        sel = order[:qcap]
        payq = jnp.zeros((Q,), jnp.uint64).at[sel].set(pay)
        fndq = jnp.zeros((Q,), jnp.int32).at[sel].set(fnd)
        glq = jnp.zeros((Q,), jnp.int32).at[sel].set(gleaf)
        outs = (jnp.where(owned, payq, jnp.uint64(0)),
                jnp.where(owned, fndq, jnp.int32(0)),
                jnp.where(owned, glq, jnp.int32(0)),
                jnp.where(owned, sid, jnp.int32(0)))
        return tuple(psum_disjoint(o, "shards") for o in outs)

    rows = PartitionSpec("shards", None)
    return jax.shard_map(body, mesh=mesh, in_specs=(rows,) * 8,
                         out_specs=(PartitionSpec(),) * 4,
                         check_vma=False)(*outs, order, owned, sid)


def _run_mesh(mesh, stk: dict, q, height: int, qcap, interpret: bool,
              strategy):
    ops = _operands(stk)
    mops = _mesh_operands(ops, mesh, np.asarray(stk["bounds"]))
    q64 = jnp.asarray(q).astype(jnp.uint64)
    Q = int(q64.shape[0])
    # qcap is the PER-SHARD routing bound (the jnp lane-pack contract); a
    # device owns S_local shards, so its launch width is S_local * qcap
    qcap = Q if qcap is None else max(1, min(int(qcap) * mops.Sl, Q))
    lgeom = dataclasses.replace(ops.geom, num_shards=mops.Sl)
    st = strategy or tuning.choose_strategy(lgeom, interpret=interpret)
    _check_mode(st, interpret)
    g = ops.geom
    cfg = kernel_config(lgeom, st, height, bounds_len=mops.nbl,
                        overlay_cap=1, sharded=True, has_overlay=False)
    qh, ql, order, owned, sid = _mesh_route(
        mesh, mops.bounds_u64, q64, Sl=mops.Sl, qcap=qcap, qb=st.qb)
    outs = run_x32(_mesh_kernel, mesh, cfg, interpret, mops.pools,
                   mops.bounds_planes, qh, ql)
    pay, fnd, gleaf, sid = _mesh_unroute(
        mesh, outs, order, owned, sid, Sl=mops.Sl, qcap=qcap,
        leaf_pool=g.leaf_pool)
    return pay, fnd.astype(bool), gleaf, sid


def fused_lookup_batch_sharded_mesh(mesh, stk: dict, q, height: int = 3, *,
                                    qcap=None, interpret=False,
                                    strategy=None):
    """Fused-kernel twin of ``lookup_batch_sharded_mesh`` (pay, found,
    global leaf row, shard id); per-device local kernel launches under
    ``shard_map``."""
    return _run_mesh(mesh, stk, q, height, qcap, interpret, strategy)


def fused_lookup_batch_sharded_overlay_mesh(mesh, stk: dict, ovr: dict, q,
                                            height: int = 3, *, qcap=None,
                                            interpret=False, strategy=None):
    """Fused-kernel twin of ``lookup_batch_sharded_overlay_mesh``.  The
    overlay is replicated, so the merge runs once outside ``shard_map`` on
    the gathered snapshot results (bit-identical to the in-kernel merge:
    kernel pay planes are already zeroed where not found)."""
    from ...core.lookup import _overlay_probe
    pay, found, gleaf, _ = _run_mesh(mesh, stk, q, height, qcap, interpret,
                                     strategy)
    q64 = jnp.asarray(q).astype(jnp.uint64)
    hit, tomb, opay = _overlay_probe(ovr, q64)
    pay = jnp.where(hit & ~tomb, opay, pay)
    found = jnp.where(hit, ~tomb, found)
    return jnp.where(found, pay, jnp.uint64(0)), found, gleaf


# ------------------------------------------------------------------- autotune
def autotune_strategy(arrs: dict, q, *, ovr: dict | None = None,
                      height: int = 3, interpret: bool = False,
                      reps: int = 3) -> tuning.TileStrategy:
    """Measured tile-size sweep for this mirror's geometry (cached per
    geometry in :mod:`tuning`)."""
    geom = tuning.PoolGeometry.from_device_arrays(arrs, ovr)

    def bench(st: tuning.TileStrategy) -> float:
        def once():
            jax.block_until_ready(
                _run(arrs, ovr, q, height, interpret, st)[0])
        once()                       # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            once()
        return (time.perf_counter() - t0) / reps

    return tuning.autotune(geom, bench, interpret=interpret)
