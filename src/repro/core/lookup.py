"""Batched JAX lookup/scan over a :class:`DeviceIndex` mirror.

This is the TPU-native read path of AULID (DESIGN.md §2): the bounded inner
height (paper §4.4) lets us fully unroll the root-to-leaf traversal, so a
batch of Q queries becomes ``height`` rounds of dense gathers + one leaf-block
search — no per-query control flow, VPU-friendly, and directly mappable to
the Pallas kernels in ``repro.kernels``.

Uses 64-bit types (uint64 keys, float64 models) — enabled module-locally via
``jax.config``; the LM-framework model code never imports this module and uses
explicit 32/16-bit dtypes throughout, so the global x64 flag is safe there.
On a real TPU, XLA emulates 64-bit integers with u32 pairs; the two-plane
comparison variant is implemented natively in ``repro.kernels.leaf_search``.
"""
from __future__ import annotations

import functools
import itertools

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from .delta_overlay import DeltaOverlay, UINT64_MAX, next_pow2  # noqa: E402
from .device_index import _STACK_2D, _STACK_3D, DeviceIndex  # noqa: E402

# the mirror pools every read path gathers from — one list, derived from the
# stacking tables so a new DeviceIndex pool can't silently miss a consumer
_DEVICE_FIELDS = [f for f, _ in _STACK_2D + _STACK_3D]

# Monotonic snapshot tokens (DESIGN.md §10 caveat, §11): every operand dict a
# mutation path returns carries a fresh process-unique token under
# "snap_token" / "ov_token".  Unlike ``id()``, a token is never recycled
# after garbage collection, so downstream caches (the fused kernel's operand
# packs) key on it safely.  The token rides the dict as a plain int leaf —
# jitted consumers treat it as one scalar operand and never recompile on it.
_SNAP_TOKENS = itertools.count(1)


def new_snap_token() -> int:
    """Issue a process-unique snapshot token (see module comment above)."""
    return next(_SNAP_TOKENS)


def device_arrays(di: DeviceIndex) -> dict[str, jnp.ndarray]:
    """Move the mirror pools to device (jnp) arrays."""
    d = {f: jnp.asarray(getattr(di, f)) for f in _DEVICE_FIELDS}
    d["meta"] = jnp.array([di.root_node, di.last_leaf_row], dtype=jnp.int32)
    d["last_leaf_min"] = jnp.asarray(di.last_leaf_min)
    d["snap_token"] = new_snap_token()
    return d


TAG_NULL, TAG_DATA, TAG_PA, TAG_BT, TAG_MIXED = 0, 1, 2, 3, 4


def _row_search(pool_keys: jnp.ndarray, rows: jnp.ndarray, q: jnp.ndarray):
    """Vectorized intra-block search: for each query q[i], the position of the
    first key >= q within pool row rows[i] (the paper's per-block binary
    search becomes one whole-block compare — DESIGN.md §2)."""
    blk = jnp.take(pool_keys, rows, axis=0, mode="clip")      # (Q, C)
    pos = jnp.sum(blk < q[:, None], axis=1).astype(jnp.int32)  # (Q,)
    return blk, pos


STALE_STEPS = 4  # max successor-chain steps per level (>= 3 suffices, see mirror)


@functools.partial(jax.jit, static_argnames=("height",))
def lookup_batch(arrs: dict, q: jnp.ndarray, height: int = 3):
    """Batched point lookup. Returns (payload u64, found bool, leaf_row i32).

    Per level: predict a starting slot (with a one-slot safety margin against
    fp skew), walk the precomputed successor-entry chain until the first entry
    whose max key >= q (deterministic integer compares), then resolve it by
    tag — DATA -> leaf, PA/BT -> one whole-block vectorized search, MIXED ->
    descend. Chain exhaustion (-1) means "no entry >= q": the metanode's last
    leaf is the global successor sentinel (paper §4.2.1)."""
    q = q.astype(jnp.uint64)
    Q = q.shape[0]
    root = arrs["meta"][0]
    last_row = arrs["meta"][1]

    # Metanode shortcut (paper §4.2.1): keys >= last leaf's min go straight
    # to the last leaf; likewise when there is no inner part at all.
    in_last = q >= arrs["last_leaf_min"]
    no_root = root < 0

    node = jnp.full((Q,), jnp.maximum(root, 0), dtype=jnp.int32)
    leaf = jnp.full((Q,), -1, dtype=jnp.int32)
    done = in_last | no_root
    leaf = jnp.where(done, last_row, leaf)

    qf = q.astype(jnp.float64)
    S = arrs["slot_tag"].shape[0]
    for _ in range(height):
        base = jnp.take(arrs["node_base"], node, mode="clip")
        fanout = jnp.take(arrs["node_fanout"], node, mode="clip")
        slope = jnp.take(arrs["node_slope"], node, mode="clip")
        inter = jnp.take(arrs["node_intercept"], node, mode="clip")
        overflow = jnp.take(arrs["node_overflow_slot"], node, mode="clip")
        pred = jnp.clip(jnp.floor(slope * qf + inter) - 1, 0, fanout - 1).astype(jnp.int32)
        s = jnp.take(arrs["next_occ"], base + pred, mode="clip")
        s = jnp.where(s < 0, overflow, s)
        # skip stale entries (max key < q) along the successor chain
        for _ in range(STALE_STEPS):
            key_s = jnp.take(arrs["slot_key"], jnp.clip(s, 0, S - 1), mode="clip")
            stale = (s >= 0) & (key_s < q)
            nxt = jnp.take(arrs["succ_slot"], jnp.clip(s, 0, S - 1), mode="clip")
            s = jnp.where(stale, nxt, s)
        ended = s < 0
        sc = jnp.clip(s, 0, S - 1)
        tag = jnp.take(arrs["slot_tag"], sc, mode="clip")
        ptr = jnp.take(arrs["slot_ptr"], sc, mode="clip")

        # PA / BT: one whole-block search (entry max >= q guarantees a hit)
        _, pa_pos = _row_search(arrs["pa_keys"], jnp.maximum(ptr, 0), q)
        pa_hit = jnp.take_along_axis(
            jnp.take(arrs["pa_ptrs"], jnp.maximum(ptr, 0), axis=0, mode="clip"),
            pa_pos[:, None] % arrs["pa_ptrs"].shape[1], axis=1)[:, 0]
        _, bt_pos = _row_search(arrs["bt_keys"], jnp.maximum(ptr, 0), q)
        bt_hit = jnp.take_along_axis(
            jnp.take(arrs["bt_ptrs"], jnp.maximum(ptr, 0), axis=0, mode="clip"),
            bt_pos[:, None] % arrs["bt_ptrs"].shape[1], axis=1)[:, 0]

        is_mixed = (tag == TAG_MIXED) & ~ended
        step_leaf = jnp.where(ended, last_row,
                    jnp.where(tag == TAG_DATA, ptr,
                    jnp.where(tag == TAG_PA, pa_hit,
                    jnp.where(tag == TAG_BT, bt_hit, -1))))
        newly = ~done & ~is_mixed
        leaf = jnp.where(newly, step_leaf, leaf)
        done = done | newly
        node = jnp.where(~done & is_mixed, ptr, node)

    # Final leaf search (the paper's one-block binary search, vectorized).
    leaf = jnp.maximum(leaf, 0)
    blk, pos = _row_search(arrs["leaf_keys"], leaf, q)
    cap = blk.shape[1]
    hit_key = jnp.take_along_axis(blk, pos[:, None] % cap, axis=1)[:, 0]
    pay = jnp.take_along_axis(
        jnp.take(arrs["leaf_pay"], leaf, axis=0, mode="clip"),
        pos[:, None] % cap, axis=1)[:, 0]
    found = (pos < cap) & (hit_key == q)
    return jnp.where(found, pay, 0), found, leaf


def _scan_leaf_walk(leaf_keys, leaf_pay, leaf_count, leaf_next,
                    leaf0, q, count: int, max_blocks: int):
    """Shared leaf-chain walk of the batched scans: gather ``max_blocks``
    blocks along ``leaf_next`` from ``leaf0`` and compact the in-range
    entries.  ``leaf_next`` may be the monolithic sibling links or the
    stacked mirror's cross-shard successor chain (same walk either way)."""
    cap = leaf_keys.shape[1]
    Q = q.shape[0]
    out_k = jnp.zeros((Q, max_blocks * cap), dtype=jnp.uint64)
    out_p = jnp.zeros((Q, max_blocks * cap), dtype=jnp.uint64)
    out_v = jnp.zeros((Q, max_blocks * cap), dtype=bool)
    leaf = leaf0
    for b in range(max_blocks):
        ks = jnp.take(leaf_keys, leaf, axis=0, mode="clip")
        ps = jnp.take(leaf_pay, leaf, axis=0, mode="clip")
        cnt = jnp.take(leaf_count, leaf, mode="clip")
        valid = (jnp.arange(cap)[None, :] < cnt[:, None]) & (ks >= q[:, None]) \
            & (leaf >= 0)[:, None]
        out_k = out_k.at[:, b * cap : (b + 1) * cap].set(ks)
        out_p = out_p.at[:, b * cap : (b + 1) * cap].set(ps)
        out_v = out_v.at[:, b * cap : (b + 1) * cap].set(valid)
        leaf = jnp.where(leaf >= 0, jnp.take(leaf_next, leaf, mode="clip"), -1)
    return _scan_compact(out_k, out_p, out_v, count)


def _scan_compact(out_k, out_p, out_v, count: int):
    """Compact a gathered (Q, blocks*cap) scan window: order valid entries
    first (keys within+across blocks are sorted, so the stable sort keeps
    key order) and slice to ``count`` (shared with the mesh scan, whose walk
    gathers the same window via per-device contributions + psum)."""
    order = jnp.argsort(~out_v, axis=1, stable=True)[:, :count]
    keys = jnp.take_along_axis(out_k, order, axis=1)
    pays = jnp.take_along_axis(out_p, order, axis=1)
    vmask = jnp.take_along_axis(out_v, order, axis=1)
    return keys, pays, vmask


@functools.partial(jax.jit, static_argnames=("height", "count", "max_blocks"))
def scan_batch(arrs: dict, q: jnp.ndarray, count: int = 100, height: int = 3,
               max_blocks: int | None = None):
    """Batched range scan: ``count`` pairs with key >= q[i] per query.

    Walks ``leaf_next`` sibling links (paper §4.2.2); the number of fetched
    blocks per query is ceil(count/leaf_fill)+1 — the locality the B+-tree
    styled leaves buy (P5). Returns (keys (Q,count), payloads, valid mask)."""
    _, _, leaf0 = lookup_batch(arrs, q, height=height)
    q = q.astype(jnp.uint64)
    cap = arrs["leaf_keys"].shape[1]
    if max_blocks is None:
        max_blocks = count // max(cap // 2, 1) + 2
    return _scan_leaf_walk(arrs["leaf_keys"], arrs["leaf_pay"],
                           arrs["leaf_count"], arrs["leaf_next"],
                           leaf0, q, count, max_blocks)


# --------------------------------------------------------------------- overlay
# Merge-consultation of a DeltaOverlay (DESIGN.md §3): the snapshot mirror
# stays frozen; writes since the snapshot live in a small sorted overlay that
# the batched read path consults with one whole-array compare (the same
# "one block fetch + whole-block search" idiom as the leaf step — the Pallas
# twin is repro.kernels.overlay_probe).


def overlay_arrays(ov: DeltaOverlay) -> dict[str, jnp.ndarray]:
    """Move the overlay pools to device as ONE packed (3, cap) u64 transfer
    (keys, payloads, tombstones) — the pack layout the engine's overlay pack
    shares (``ShardedIndexEngine._rebuild_overlay_pack``)."""
    a = ov.arrays()
    pack = np.empty((3, a["ov_keys"].shape[0]), dtype=np.uint64)
    pack[0] = a["ov_keys"]
    pack[1] = a["ov_pay"]
    pack[2] = a["ov_tomb"]
    return {"ov_pack": jnp.asarray(pack), "ov_token": new_snap_token()}


@functools.partial(jax.jit, static_argnames=("cap_out",))
def merge_overlay_pack_jnp(pack: jnp.ndarray, batch: jnp.ndarray,
                           cap_out: int) -> jnp.ndarray:
    """Device-resident sorted-merge upsert: ``pack`` (3, Ca) updated by the
    step's sorted write ``batch`` (3, Cb), producing a (3, cap_out) pack —
    the jnp reference semantics of the overlay-merge kernel and the engine's
    default write path (DESIGN.md §14).

    Both inputs are u64 packs in overlay layout (keys/payloads/tombstones,
    UINT64_MAX key padding doubling as the occupancy mask) with unique sorted
    live keys.  The batch wins on key collisions (last-writer-wins upsert)
    and tombstones are retained as entries — exactly the dict-union semantics
    of the host oracle, so the merged pack is bit-identical to a full host
    repack at the same capacity.  The caller guarantees ``cap_out`` covers
    the merged live count (it knows both host-side fill counts exactly);
    output positions are computed by rank arithmetic from the batch side,
    so no sort runs on device and no search runs per overlay slot: one
    search of the batch into the overlay, then O(Ca + Cb) scatter work.
    Overlay slot ``i`` lands at ``i`` plus the number of *new* batch keys
    below it, counted by one fused compare-and-sum over the batch; a slot
    the batch overwrites lands where the overwriting entry does, and the
    batch is scattered last, so it wins without a mask.
    """
    ak, ap, at = pack[0], pack[1], pack[2]
    bk, bp, bt = batch[0], batch[1], batch[2]
    ca = ak.shape[0]
    um = jnp.uint64(UINT64_MAX)
    live_a = ak != um
    live_b = bk != um
    # insertion point of each batch key among the overlay's keys, and the
    # batch keys that overwrite an overlay key
    posa = jnp.searchsorted(ak, bk, side="left").astype(jnp.int32)
    in_a = (posa < ca) & (jnp.take(ak, jnp.clip(posa, 0, ca - 1)) == bk)
    common = live_b & in_a
    # a new batch key bk[j] lies below overlay slot i iff posa[j] <= i
    # (live keys are a sorted prefix), so slot i lands at i + shift[i].
    # Counted as one compare-and-sum over the batch axis: a binary search
    # per slot, or a prefix sum over the overlay, costs work or compile
    # time that grows with the pack.
    newpos = jnp.where(live_b & ~in_a, posa, ca)
    slot = jnp.arange(ca, dtype=jnp.int32)
    shift = jnp.sum(newpos[:, None] <= slot[None, :], axis=0,
                    dtype=jnp.int32)
    pos_a = slot + shift
    # dead[j] == how many overwriting batch keys lie among the first j
    # batch entries (prefix sum over the batch)
    dead = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(common.astype(jnp.int32))])
    # rank of each live batch key among batch entries
    live_bi = live_b.astype(jnp.int32)
    rank_b = jnp.cumsum(live_bi) - live_bi
    # surviving overlay keys strictly below bk[j]: all overlay keys below it
    # minus the overwritten ones below it (= batch∩overlay keys before j)
    pos_b = rank_b + posa - dead[:-1]
    # survivors and batch entries interleave into one sorted run; an
    # overwritten overlay slot and its batch entry share a position, and
    # the batch's scatter runs second; padding scatters to the
    # (out-of-range) sentinel
    idx_a = jnp.where(live_a, pos_a, cap_out)
    idx_b = jnp.where(live_b, pos_b, cap_out)
    out_k = jnp.full((cap_out,), um, dtype=jnp.uint64)
    out_p = jnp.zeros((cap_out,), dtype=jnp.uint64)
    out_t = jnp.zeros((cap_out,), dtype=jnp.uint64)
    out_k = out_k.at[idx_a].set(ak, mode="drop").at[idx_b].set(bk, mode="drop")
    out_p = out_p.at[idx_a].set(ap, mode="drop").at[idx_b].set(bp, mode="drop")
    out_t = out_t.at[idx_a].set(at, mode="drop").at[idx_b].set(bt, mode="drop")
    return jnp.stack([out_k, out_p, out_t])


@functools.partial(jax.jit, static_argnames=("cap",))
def empty_overlay_pack(cap: int) -> jnp.ndarray:
    """All-padding (3, cap) overlay pack built ON DEVICE — the zero-H2D
    reseed after a compaction cleared the overlay."""
    um = jnp.full((1, cap), jnp.uint64(UINT64_MAX), dtype=jnp.uint64)
    z = jnp.zeros((2, cap), dtype=jnp.uint64)
    return jnp.concatenate([um, z], axis=0)


def merge_window(fill_bound: int, bcap: int, cap: int) -> int:
    """Slots ``[0, w)`` of a (3, cap) pack that a delta merge reads and
    writes (DESIGN.md §14): the power-of-two bucket of the post-merge fill
    bound, at least the batch bucket, at most the capacity.  Between
    reseeds the fill only grows, so every slot at or past the bound is
    padding both before and after the merge."""
    return min(cap, next_pow2(max(fill_bound, bcap)))


# The windowed merge runs as three programs around the bound merge function
# (the Pallas kernel traces with x64 off, so it is not nested in another
# jit); each program's name holds "overlay_merge" or "merge_overlay_pack",
# which the benchmark's merge reader matches, so the device time it reads
# covers the copies too.
@functools.partial(jax.jit, static_argnames=("w",))
def overlay_merge_head(pack: jnp.ndarray, w: int) -> jnp.ndarray:
    return pack[:, :w]


@jax.jit
def overlay_merge_write_back(pack: jnp.ndarray,
                             head: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(pack, head, (0, 0))


def merge_overlay_pack(ovr: dict, batch, fill_bound: int,
                       merge_fn=None) -> tuple[dict, int, int]:
    """Absorb a drained host write batch (``DeltaOverlay.take_batch``) into
    the device-resident overlay pack — the O(batch) H2D write path.

    Pads the sorted batch to a power-of-two bucket (few jit shapes), ships
    ONLY that (3, bcap) pack, and merges on device via ``merge_fn`` (default:
    the jnp reference; the serving engine binds the Pallas kernel through
    ``overlay_merge_backend_fn``).  ``fill_bound`` is the caller's upper
    bound on the pack's live count after the merge: the merge runs over the
    ``merge_window`` prefix and keeps the pack's shape, and only a bound
    past the capacity merges the whole pack into a larger one.  Returns
    (new overlay dict stamped with a fresh ``ov_token``, H2D bytes
    uploaded, slots merged)."""
    bk, bp, bt = batch
    n = int(bk.shape[0])
    bcap = next_pow2(max(n, 8))
    bpack = np.zeros((3, bcap), dtype=np.uint64)
    bpack[0] = UINT64_MAX
    bpack[0, :n] = bk
    bpack[1, :n] = bp
    bpack[2, :n] = bt
    fn = merge_fn if merge_fn is not None else merge_overlay_pack_jnp
    pack, bdev = ovr["ov_pack"], jnp.asarray(bpack)
    cap = int(pack.shape[1])
    w = merge_window(fill_bound, bcap, cap)
    if w == cap:           # fill at capacity, or growth past it
        w = max(cap, next_pow2(fill_bound))
        pack = fn(pack, bdev, w)
    else:
        pack = overlay_merge_write_back(
            pack, fn(overlay_merge_head(pack, w), bdev, w))
    return ({"ov_pack": pack, "ov_token": new_snap_token()},
            int(bpack.nbytes), w)


def overlay_merge_backend_fn(backend: str = "auto"):
    """The overlay-merge entry for a read backend, callable as
    ``fn(pack, batch_pack, cap_out) -> new_pack`` — the engine's write-path
    twin of ``lookup_backend_fns``: "jnp" merges with the reference above,
    "fused" through the compiled Pallas overlay-merge kernel and
    "fused_interpret" through the same kernel in interpret mode."""
    b = resolve_read_backend(backend)
    if b == "jnp":
        return merge_overlay_pack_jnp
    from ..kernels.overlay_merge.ops import overlay_merge_pack
    return functools.partial(overlay_merge_pack,
                             interpret=(b == "fused_interpret"))


def update_leaf_rows(arrs: dict, di: DeviceIndex) -> dict:
    """Patch device copies of the leaf pools after a fast-path refresh.

    ``refresh_device_index`` records the re-mirrored rows in
    ``di.last_touched_rows``; uploading just those (plus the metanode's
    ``last_leaf_min``) keeps compaction's device cost O(touched) instead of
    re-transferring every pool.  Falls back to a full ``device_arrays`` when
    the last refresh was a full build (``last_touched_rows is None``).
    """
    rows = di.last_touched_rows
    if rows is None:
        return device_arrays(di)
    if len(rows):
        r = jnp.asarray(rows)
        arrs = dict(arrs)
        arrs["leaf_keys"] = arrs["leaf_keys"].at[r].set(
            jnp.asarray(di.leaf_keys[rows]))
        arrs["leaf_pay"] = arrs["leaf_pay"].at[r].set(
            jnp.asarray(di.leaf_pay[rows]))
        arrs["leaf_count"] = arrs["leaf_count"].at[r].set(
            jnp.asarray(di.leaf_count[rows]))
        arrs["last_leaf_min"] = jnp.asarray(di.last_leaf_min)
        arrs["snap_token"] = new_snap_token()
    return arrs


def _overlay_unpack(ovr: dict):
    pack = ovr["ov_pack"]
    return pack[0], pack[1], pack[2] != 0   # keys, payloads, tombstones


def _overlay_probe(ovr: dict, q: jnp.ndarray):
    """For each query: (hit, tombstone, payload) from the sorted overlay.
    Padding keys are u64-max so they never match a (valid) query key.
    searchsorted keeps temporaries O(Q) — a (Q, cap) broadcast compare
    thrashes the CPU backend's allocator hard enough to tax the *next*
    host-side step (measured 5x on the serving loop)."""
    keys, pays, tombs = _overlay_unpack(ovr)
    cap = keys.shape[0]
    pos = jnp.searchsorted(keys, q, side="left").astype(jnp.int32)
    posc = jnp.clip(pos, 0, cap - 1)
    hit = (pos < cap) & (jnp.take(keys, posc) == q)
    tomb = hit & jnp.take(tombs, posc)
    pay = jnp.take(pays, posc)
    return hit, tomb, pay


# jitted form for hosts that merge the overlay outside a jitted read path
# (the engine's host-routed mesh lookup): same function, compiled once per
# pack/batch shape instead of ~6 eager dispatches per read batch
overlay_probe_jit = jax.jit(_overlay_probe)


@functools.partial(jax.jit, static_argnames=("height",))
def lookup_batch_overlay(arrs: dict, ovr: dict, q: jnp.ndarray, height: int = 3):
    """Batched point lookup over snapshot + overlay. Overlay hit wins; a
    tombstone hides the key even when the snapshot still stores it.
    Returns (payload u64, found bool, leaf_row i32) like ``lookup_batch``."""
    q = q.astype(jnp.uint64)
    pay, found, leaf = lookup_batch(arrs, q, height=height)
    hit, tomb, opay = _overlay_probe(ovr, q)
    pay = jnp.where(hit & ~tomb, opay, pay)
    found = jnp.where(hit, ~tomb, found)
    return jnp.where(found, pay, 0), found, leaf


@functools.partial(jax.jit,
                   static_argnames=("height", "count", "max_blocks",
                                    "ov_bound"))
def scan_batch_overlay(arrs: dict, ovr: dict, q: jnp.ndarray, count: int = 100,
                       height: int = 3, max_blocks: int | None = None,
                       ov_bound: int | None = None):
    """Batched range scan over snapshot + overlay (two-way sorted merge).

    Fetches ``count + ov_bound`` snapshot candidates (the overlay can hide at
    most one snapshot key per entry it holds via tombstones/upserts), drops
    snapshot keys the overlay overrides, unions in the overlay's live
    in-range entries, and re-sorts — the device twin of the host's leaf-chain
    + overlay merge.

    ``ov_bound`` (static) must be >= the number of LIVE overlay entries;
    callers that track occupancy host-side (the serving engine) pass its
    next power of two, which keeps the unrolled leaf walk proportional to the
    overlay's actual fill. The default is the padded capacity — always safe,
    but an overlay sized for a large compaction threshold then unrolls a
    pathologically deep walk, so pass the bound whenever you know it.
    Returns (keys (Q,count), payloads, valid mask)."""
    q = q.astype(jnp.uint64)
    keys, pays, tombs = _overlay_unpack(ovr)
    cap = keys.shape[0]
    hide = cap if ov_bound is None else min(int(ov_bound), cap)
    base = count + hide
    if max_blocks is not None:
        # the caller sized max_blocks for `count`; widen it for the extra
        # `hide` snapshot candidates this merge needs or tombstones could
        # silently starve the window
        leaf_cap = arrs["leaf_keys"].shape[1]
        max_blocks = max_blocks + hide // max(leaf_cap // 2, 1) + 1
    ks, ps, vs = scan_batch(arrs, q, count=base, height=height,
                            max_blocks=max_blocks)
    return _overlay_scan_merge(ks, ps, vs, keys, pays, tombs, q, count, hide)


def _overlay_scan_merge(ks, ps, vs, keys, pays, tombs, q, count: int,
                        live_bound: int):
    """Merge snapshot scan candidates with the overlay range (shared by the
    monolithic and sharded scans): snapshot keys the overlay owns lose, live
    overlay entries in range union in, and the result re-sorts.

    The pack is sorted with its live entries first, and ``live_bound``
    (static) bounds their number, so only that prefix is merged: a pack sized
    for a large compaction threshold (gamma·n entries) would otherwise be
    broadcast and sorted whole for every query."""
    keys, pays, tombs = (keys[:live_bound], pays[:live_bound],
                         tombs[:live_bound])
    cap = keys.shape[0]
    pos = jnp.searchsorted(keys, ks, side="left").astype(jnp.int32)
    owned = (pos < cap) & (jnp.take(keys, jnp.clip(pos, 0, cap - 1)) == ks)
    vs = vs & ~owned
    # overlay live entries in range, broadcast per query (u64-max padding
    # doubles as the occupancy mask)
    Q = q.shape[0]
    in_ov = keys[None, :] != jnp.uint64(0xFFFFFFFFFFFFFFFF)
    ov_v = in_ov & ~tombs[None, :] & (keys[None, :] >= q[:, None])
    comb_k = jnp.concatenate([ks, jnp.broadcast_to(keys[None, :], (Q, cap))], axis=1)
    comb_p = jnp.concatenate(
        [ps, jnp.broadcast_to(pays[None, :], (Q, cap))], axis=1)
    comb_v = jnp.concatenate([vs, ov_v], axis=1)
    sort_k = jnp.where(comb_v, comb_k, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    order = jnp.argsort(sort_k, axis=1, stable=True)[:, :count]
    return (jnp.take_along_axis(comb_k, order, axis=1),
            jnp.take_along_axis(comb_p, order, axis=1),
            jnp.take_along_axis(comb_v, order, axis=1))


# --------------------------------------------------------------------- sharded
# Range-sharded read path (DESIGN.md §9): the stacked mirror pools of
# ``device_index.stack_device_indexes`` carry a leading shard axis, and the
# batched entry points below route each query with ONE searchsorted over the
# boundary table, scatter queries into per-shard lanes, ``jax.vmap`` the
# monolithic unrolled traversal over the shard axis, and gather results back
# into request order.  Scans then leave the vmap: they walk the flattened
# (S*L,) leaf pools through the precomputed shard-successor chain, so a range
# crossing a shard boundary keeps streaming blocks with no host round-trip.

def stacked_host_arrays(sdi, bounds_version: int = 0) -> dict:
    """The operand dict of a :class:`StackedDeviceIndex` with its pools
    still host numpy arrays — what a placement (``jnp.asarray`` for one
    device, ``place_stacked`` for a mesh) moves to the device.

    ``bounds_version`` records which boundary-table version the pack's
    ``bounds`` array belongs to (DESIGN.md §12) — informational for
    stats/tests; operand-pack caches are invalidated by the fresh
    ``snap_token`` every build stamps, so a split/merge (which always builds
    a new pack) can never serve reads through stale cached route operands."""
    d = {f: getattr(sdi, f) for f in _DEVICE_FIELDS}
    d["meta"] = sdi.meta
    d["last_leaf_min"] = sdi.last_leaf_min
    d["bounds"] = sdi.bounds
    d["leaf_next_chain"] = sdi.leaf_next_chain
    d["snap_token"] = new_snap_token()
    d["bounds_version"] = int(bounds_version)
    return d


def stacked_device_arrays(sdi, bounds_version: int = 0
                          ) -> dict[str, jnp.ndarray]:
    """Move a :class:`StackedDeviceIndex`'s pools to the default device
    (:func:`stacked_host_arrays` placed with ``jnp.asarray``)."""
    return {k: jnp.asarray(v) if isinstance(v, np.ndarray) else v
            for k, v in stacked_host_arrays(sdi, bounds_version).items()}


@functools.partial(jax.jit, donate_argnames=("pools",))
def _install_shard_rows(pools: dict, s: jnp.ndarray, rows: dict) -> dict:
    """Write one shard's mirror slices into the stacked pools in place: the
    pools are donated, so XLA reuses their buffers instead of copying them
    (O(slice) per install, not O(pool)).  ``s`` is traced — one compile
    serves every shard index."""
    return {f: pools[f].at[s].set(rows[f]) for f in pools}


def update_stacked_shard(stk: dict, sdi, shards: list[int],
                         dev_slices: dict | None = None) -> dict:
    """Patch the device copy of the stacked pools after ``restack_shard``
    refreshed the given shards: only those shards' slices are re-uploaded
    (plus the small per-shard metadata vectors and the successor chain) —
    cold shards' device slices are untouched, keeping the device cost of a
    shard-local compaction proportional to the hot shard.

    ``dev_slices`` maps shard id -> per-field device arrays already shaped to
    the stacked slice (``pad_shard_slices`` output, ``jax.device_put`` by a
    background build — DESIGN.md §11).  Shards present there skip the host
    transfer entirely: the epoch swap pays only the on-device scatter."""
    assert shards, "update_stacked_shard needs at least one changed shard"
    stk = dict(stk)
    # one donated jit call per shard writes that shard's slices into the
    # pools IN PLACE: cost O(slice), not O(pool) — an eager .at[].set would
    # materialize a fresh copy of every pool per call, and a batched scatter
    # would recompile for every distinct count of simultaneously-swapped
    # shards (epoch installs must stay compile-free and cheap, DESIGN.md
    # §11).  The shard id is a traced scalar, so one compile covers every
    # shard; donating the pools retires the previous epoch's buffers, which
    # no read path touches again (reads rebuild operands off the fresh
    # snap_token below).
    pools = {f: stk[f] for f in _DEVICE_FIELDS}
    for s in shards:
        dev = dev_slices.get(s) if dev_slices is not None else None
        rows = {f: dev[f] if dev is not None and f in dev
                else jnp.asarray(getattr(sdi, f)[s]) for f in _DEVICE_FIELDS}
        pools = _install_shard_rows(pools, jnp.int32(s), rows)
    stk.update(pools)
    stk["meta"] = jnp.asarray(sdi.meta)
    stk["last_leaf_min"] = jnp.asarray(sdi.last_leaf_min)
    stk["leaf_next_chain"] = jnp.asarray(sdi.leaf_next_chain)
    stk["snap_token"] = new_snap_token()
    return stk


@functools.partial(jax.jit, static_argnames=("height", "qcap"))
def lookup_batch_sharded(stk: dict, q: jnp.ndarray, height: int = 3,
                         qcap: int | None = None):
    """Batched point lookup over stacked shard mirrors.

    Route (one searchsorted over the boundary table) -> scatter-by-shard into
    an (S, qcap) lane matrix -> ``jax.vmap`` of :func:`lookup_batch` over the
    shard axis -> gather-back permutation into request order.

    ``qcap`` (static) is the per-shard lane capacity; it must be >= the
    largest per-shard query count or lanes would clobber (callers that know
    the routing host-side — the serving engine — pass the next power of two
    of the max shard load; the default Q is always safe).
    Returns (payload u64, found bool, global leaf row i32, shard id i32);
    the leaf row indexes the flattened (S*L,) leaf pools.
    """
    q = q.astype(jnp.uint64)
    Q = q.shape[0]
    S = stk["meta"].shape[0]
    L = stk["leaf_keys"].shape[1]
    qcap = Q if qcap is None else min(int(qcap), Q)
    sid = jnp.searchsorted(stk["bounds"], q, side="left").astype(jnp.int32)
    order = jnp.argsort(sid, stable=True)
    sid_s = jnp.take(sid, order)
    q_s = jnp.take(q, order)
    counts = jnp.bincount(sid_s, length=S)
    offs = jnp.concatenate([jnp.zeros(1, counts.dtype),
                            jnp.cumsum(counts)[:-1]])
    lane = jnp.arange(Q) - jnp.take(offs, sid_s)   # position within shard
    flat = sid_s * qcap + lane
    pad = jnp.uint64(0xFFFFFFFFFFFFFFFF)           # never matches a real key
    q_mat = jnp.full((S * qcap,), pad, dtype=jnp.uint64) \
        .at[flat].set(q_s).reshape(S, qcap)
    per_shard = {f: stk[f] for f in _DEVICE_FIELDS + ["meta", "last_leaf_min"]}
    pay_m, found_m, leaf_m = jax.vmap(
        lambda a, qq: lookup_batch(a, qq, height=height))(per_shard, q_mat)

    def gather_back(m):
        v = m.reshape(S * qcap)[flat]
        return jnp.zeros((Q,), v.dtype).at[order].set(v)

    pay = gather_back(pay_m)
    found = gather_back(found_m)
    leaf = gather_back(leaf_m)
    return pay, found, sid * L + leaf, sid


@functools.partial(jax.jit,
                   static_argnames=("height", "count", "max_blocks", "qcap"))
def scan_batch_sharded(stk: dict, q: jnp.ndarray, count: int = 100,
                       height: int = 3, max_blocks: int | None = None,
                       qcap: int | None = None):
    """Batched range scan over stacked shard mirrors.

    The start leaf comes from the vmapped sharded lookup; the walk itself
    runs on the flattened (S*L, cap) leaf pools through the precomputed
    shard-successor chain, so a scan that exhausts its shard continues in
    the next shard's first leaf with no extra dispatch (cross-shard scans,
    DESIGN.md §9).  Returns (keys (Q,count), payloads, valid mask)."""
    q = q.astype(jnp.uint64)
    S = stk["meta"].shape[0]
    cap = stk["leaf_keys"].shape[2]
    if max_blocks is None:
        # + S: each shard boundary crossed can add one underfull chain leaf
        max_blocks = count // max(cap // 2, 1) + 2 + S
    _, _, gleaf, _ = lookup_batch_sharded(stk, q, height=height, qcap=qcap)
    return _scan_leaf_walk(stk["leaf_keys"].reshape(-1, cap),
                           stk["leaf_pay"].reshape(-1, cap),
                           stk["leaf_count"].reshape(-1),
                           stk["leaf_next_chain"],
                           gleaf, q, count, max_blocks)


@functools.partial(jax.jit, static_argnames=("height", "qcap"))
def lookup_batch_sharded_overlay(stk: dict, ovr: dict, q: jnp.ndarray,
                                 height: int = 3, qcap: int | None = None):
    """Sharded point lookup merged with the (globally sorted) overlay pack.

    Per-shard overlays concatenate into one globally sorted pack (shards
    partition the key space in order), so overlay consultation stays the
    monolithic single probe.  Returns (payload, found, global leaf row)."""
    q = q.astype(jnp.uint64)
    pay, found, gleaf, _ = lookup_batch_sharded(stk, q, height=height,
                                                qcap=qcap)
    hit, tomb, opay = _overlay_probe(ovr, q)
    pay = jnp.where(hit & ~tomb, opay, pay)
    found = jnp.where(hit, ~tomb, found)
    return jnp.where(found, pay, 0), found, gleaf


# --------------------------------------------------------------------- backend
# Read-backend dispatch (DESIGN.md §10): the serving engine binds its point-
# lookup entry through here, so the fused Pallas kernel and the jnp gather
# path are interchangeable behind one switch.  "auto" is the jnp path on every
# platform: it is the path that runs at deployment size, and the correctness
# oracle.  "fused" is the compiled kernel and needs a TPU — it never degrades
# to interpret mode; "fused_interpret" asks for interpret mode explicitly.
# Scans always run the jnp path — the fused kernel covers point lookups.

READ_BACKENDS = ("auto", "jnp", "fused", "fused_interpret")


def resolve_read_backend(backend: str = "auto") -> str:
    """Resolve a read-backend name: "auto" is "jnp"; "fused" raises unless
    the default JAX backend is a TPU (the kernel's only compiled target)."""
    if backend not in READ_BACKENDS:
        raise ValueError(f"backend must be one of {READ_BACKENDS}, "
                         f"got {backend!r}")
    if backend == "auto":
        return "jnp"
    if backend == "fused" and jax.default_backend() != "tpu":
        raise ValueError(
            f"backend 'fused' compiles the Pallas kernels for a TPU, but "
            f"the default JAX backend is {jax.default_backend()!r}; use "
            f"'fused_interpret' for interpret mode")
    return backend


def lookup_backend_fns(backend: str = "auto"):
    """The overlay-merged point-lookup entry over a stacked snapshot for a
    read backend, callable as ``fn(stk, ovr, q, height=...)`` — the engine's
    ``self._lookup`` shape.  "fused_interpret" runs the fused kernel in
    interpret mode (what tier-1 CI exercises)."""
    b = resolve_read_backend(backend)
    if b == "jnp":
        return lookup_batch_sharded_overlay
    from ..kernels.fused_lookup.ops import fused_lookup_batch_sharded_overlay
    return functools.partial(fused_lookup_batch_sharded_overlay,
                             interpret=(b == "fused_interpret"))


@functools.partial(jax.jit,
                   static_argnames=("height", "count", "max_blocks", "qcap",
                                    "ov_bound"))
def scan_batch_sharded_overlay(stk: dict, ovr: dict, q: jnp.ndarray,
                               count: int = 100, height: int = 3,
                               max_blocks: int | None = None,
                               qcap: int | None = None,
                               ov_bound: int | None = None):
    """Sharded range scan merged with the global overlay pack (the same
    two-way sorted merge as :func:`scan_batch_overlay`, over the cross-shard
    leaf chain; ``ov_bound`` bounds live overlay entries exactly as there)."""
    q = q.astype(jnp.uint64)
    keys, pays, tombs = _overlay_unpack(ovr)
    cap = keys.shape[0]
    hide = cap if ov_bound is None else min(int(ov_bound), cap)
    base = count + hide
    if max_blocks is not None:
        leaf_cap = stk["leaf_keys"].shape[2]
        max_blocks = max_blocks + hide // max(leaf_cap // 2, 1) + 1
    ks, ps, vs = scan_batch_sharded(stk, q, count=base, height=height,
                                    max_blocks=max_blocks, qcap=qcap)
    return _overlay_scan_merge(ks, ps, vs, keys, pays, tombs, q, count, hide)


# ------------------------------------------------------------------------ mesh
# Multi-device mesh read path (DESIGN.md §13): the stacked pools shard their
# leading (S, ...) axis across the 1-D index mesh of
# ``repro.parallel.index_mesh`` (placement in ``parallel/index_placement.py``)
# and the entry points below run the SAME traversal as the vmapped sharded
# path, but per device under ``shard_map``: every device routes the
# (replicated) query batch over the (replicated) boundary table, keeps only
# the queries whose shard it owns, lane-packs them into a TIGHT
# (S_local, qcap) matrix, vmaps the monolithic traversal over its local
# pools, and contributes its owned results to an all-gather (psum of
# disjoint contributions) of only the (B,)-shaped outputs — pools never move.
#
# Two consequences the benchmarks measure: (1) on a real multi-device
# backend each device touches only its own shards' memory; (2) even
# single-core (forced host devices) the per-device lane matrix is
# S_local*qcap instead of the monolithic S*Q, so total traversal work drops
# by ~S/max_shard_load when the engine passes a tight qcap — the CPU-visible
# half of the speedup ``benchmarks/multi_device_serving.py`` gates on.
#
# Sentinel (u64-max padded) queries are owned by NO device and return zeroed
# results (found=False) — callers slice to the real count, exactly as with
# the vmapped path.

MESH_AXIS = "shards"


def mesh_local_shards(S: int, mesh) -> int:
    """Shards per device; the stack's padded slot count must divide the mesh
    (the engine pads ``_shard_slots`` to a device multiple — refuse loudly
    instead of serving from a silently replicated layout)."""
    D = int(mesh.shape[MESH_AXIS])
    if S % D:
        raise ValueError(
            f"stacked shard slots S={S} not divisible by the index mesh's "
            f"{D} devices — pad shard slots to a device multiple")
    return S // D


def psum_disjoint(x, axis: str = MESH_AXIS):
    """``psum`` of contributions that are zero on all but one device.  TPU
    all-reduces have no 64-bit form, so a u64 operand reduces as its two
    32-bit halves (exact: one nonzero term per element, no carries)."""
    if x.dtype != jnp.uint64:
        return jax.lax.psum(x, axis)
    hi, lo = jax.lax.psum(((x >> jnp.uint64(32)).astype(jnp.uint32),
                           (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)),
                          axis)
    return (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)


def _mesh_pool_specs(stk: dict) -> dict:
    """shard_map in_specs of the per-device pool operands: leading shard
    axis on the mesh, trailing axes replicated."""
    return {f: PartitionSpec(MESH_AXIS, *(None,) * (stk[f].ndim - 1))
            for f in _DEVICE_FIELDS + ["meta", "last_leaf_min"]}


def _mesh_lane_pack(q, local_sid, owned, S_local: int, qcap: int):
    """Per-device lane packing: scatter this device's owned queries into an
    (S_local, qcap) matrix (u64-max padded), with one trailing trash slot
    absorbing non-owned queries and overflow.  Returns (q_mat, flat, order)
    for the inverse gather."""
    Q = q.shape[0]
    lsid = jnp.where(owned, local_sid, S_local).astype(jnp.int32)
    order = jnp.argsort(lsid, stable=True)
    lsid_s = jnp.take(lsid, order)
    q_s = jnp.take(q, order)
    counts = jnp.bincount(lsid_s, length=S_local + 1)
    offs = jnp.concatenate([jnp.zeros(1, counts.dtype),
                            jnp.cumsum(counts)[:-1]])
    lane = jnp.arange(Q) - jnp.take(offs, lsid_s)
    ok = (lsid_s < S_local) & (lane < qcap)
    trash = S_local * qcap
    flat = jnp.where(ok, lsid_s * qcap + lane, trash)
    pad = jnp.uint64(UINT64_MAX)
    q_mat = jnp.full((trash + 1,), pad, dtype=jnp.uint64) \
        .at[flat].set(jnp.where(ok, q_s, pad))[:trash] \
        .reshape(S_local, qcap)
    return q_mat, flat, order


def _mesh_gather_back(m, flat, order, Q: int):
    """Inverse of :func:`_mesh_lane_pack` for one per-lane result matrix."""
    v = jnp.concatenate([m.reshape(-1), jnp.zeros((1,), m.dtype)])[flat]
    return jnp.zeros((Q,), v.dtype).at[order].set(v)


@functools.partial(jax.jit, static_argnames=("mesh", "height", "qcap"))
def lookup_batch_sharded_mesh(mesh, stk: dict, q: jnp.ndarray,
                              height: int = 3, qcap: int | None = None):
    """Mesh twin of :func:`lookup_batch_sharded`: per-device local traversal
    + result all-gather (module comment above).  Same returns
    (payload u64, found bool, global leaf row i32, shard id i32), except
    sentinel queries return zeros for leaf/sid (they have no owner).

    ``qcap`` (static) bounds the per-shard lane count exactly as in the
    vmapped path — but here a tight value is the point: each device's
    traversal costs S_local*qcap lanes, so the engine's host-side routing
    bound turns shard locality into proportionally less work per device."""
    q = q.astype(jnp.uint64)
    Q = q.shape[0]
    S = int(stk["meta"].shape[0])
    L = int(stk["leaf_keys"].shape[1])
    S_local = mesh_local_shards(S, mesh)
    qcap = Q if qcap is None else min(int(qcap), Q)
    pools = {f: stk[f] for f in _DEVICE_FIELDS + ["meta", "last_leaf_min"]}

    def body(pools, bounds, qq):
        d = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32)
        sid = jnp.searchsorted(bounds, qq, side="left").astype(jnp.int32)
        local = sid - d * S_local
        owned = (local >= 0) & (local < S_local) \
            & (qq != jnp.uint64(UINT64_MAX))
        q_mat, flat, order = _mesh_lane_pack(qq, local, owned, S_local, qcap)
        pay_m, found_m, leaf_m = jax.vmap(
            lambda a, qv: lookup_batch(a, qv, height=height))(pools, q_mat)
        pay = _mesh_gather_back(pay_m, flat, order, Q)
        found = _mesh_gather_back(found_m.astype(jnp.int32), flat, order, Q)
        leaf = _mesh_gather_back(leaf_m, flat, order, Q)
        gleaf = sid * L + leaf
        zero = jnp.int32(0)
        outs = (jnp.where(owned, pay, jnp.uint64(0)),
                jnp.where(owned, found, zero),
                jnp.where(owned, gleaf, zero),
                jnp.where(owned, sid, zero))
        return tuple(psum_disjoint(o) for o in outs)

    pay, found, gleaf, sid = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_mesh_pool_specs(stk), PartitionSpec(), PartitionSpec()),
        out_specs=(PartitionSpec(),) * 4,
        check_vma=False,   # scan/while bodies lack replication rules
    )(pools, stk["bounds"], q)
    return pay, found.astype(bool), gleaf, sid


@functools.partial(jax.jit, static_argnames=("mesh", "height"))
def lookup_batch_sharded_mesh_packed(mesh, stk: dict, q_mat: jnp.ndarray,
                                     height: int = 3):
    """Host-routed mesh lookup: the caller has already scattered queries by
    owning shard into an (S, qcap) lane matrix (u64-max padded), so each
    device receives ONLY its (S_local, qcap) slice as a sharded input and
    runs pure traversal — no per-device replicated routing/packing work,
    which on time-sliced host devices (and on real chips, as wasted flops)
    costs more than the traversal itself for large batches.  Returns the
    per-lane (S, qcap) result mats (payload u64, found i32, global leaf
    row i32), sharded the same way; the caller inverts its own permutation.
    """
    S = int(stk["meta"].shape[0])
    L = int(stk["leaf_keys"].shape[1])
    S_local = mesh_local_shards(S, mesh)
    pools = {f: stk[f] for f in _DEVICE_FIELDS + ["meta", "last_leaf_min"]}

    def body(pools, qm):
        d = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32)
        pay_m, found_m, leaf_m = jax.vmap(
            lambda a, qv: lookup_batch(a, qv, height=height))(pools, qm)
        row = d * S_local + jnp.arange(S_local, dtype=jnp.int32)
        gleaf_m = row[:, None] * L + leaf_m
        return pay_m, found_m.astype(jnp.int32), gleaf_m

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_mesh_pool_specs(stk), PartitionSpec(MESH_AXIS, None)),
        out_specs=(PartitionSpec(MESH_AXIS, None),) * 3,
        check_vma=False,   # scan/while bodies lack replication rules
    )(pools, q_mat.astype(jnp.uint64))


@functools.partial(jax.jit, static_argnames=("mesh", "height", "qcap"))
def lookup_batch_sharded_overlay_mesh(mesh, stk: dict, ovr: dict,
                                      q: jnp.ndarray, height: int = 3,
                                      qcap: int | None = None):
    """Mesh twin of :func:`lookup_batch_sharded_overlay`: the overlay pack is
    replicated, so the (cheap, (Q,)-shaped) merge happens outside the
    shard_map on the all-gathered results."""
    q = q.astype(jnp.uint64)
    pay, found, gleaf, _ = lookup_batch_sharded_mesh(mesh, stk, q,
                                                     height=height, qcap=qcap)
    hit, tomb, opay = _overlay_probe(ovr, q)
    pay = jnp.where(hit & ~tomb, opay, pay)
    found = jnp.where(hit, ~tomb, found)
    return jnp.where(found, pay, 0), found, gleaf


@functools.partial(jax.jit,
                   static_argnames=("mesh", "height", "count", "max_blocks",
                                    "qcap"))
def scan_batch_sharded_mesh(mesh, stk: dict, q: jnp.ndarray, count: int = 100,
                            height: int = 3, max_blocks: int | None = None,
                            qcap: int | None = None):
    """Mesh twin of :func:`scan_batch_sharded`: start leaves come from the
    mesh lookup (replicated after its all-gather); the chain walk runs under
    shard_map with the successor chain replicated — each device follows the
    walk but contributes key/payload/valid entries only for leaves in its
    local row range, and the disjoint (Q, blocks*cap) windows psum before
    the shared compaction."""
    q = q.astype(jnp.uint64)
    S = int(stk["meta"].shape[0])
    L = int(stk["leaf_keys"].shape[1])
    cap = int(stk["leaf_keys"].shape[2])
    S_local = mesh_local_shards(S, mesh)
    if max_blocks is None:
        # + S: each shard boundary crossed can add one underfull chain leaf
        max_blocks = count // max(cap // 2, 1) + 2 + S
    _, _, gleaf, _ = lookup_batch_sharded_mesh(mesh, stk, q, height=height,
                                               qcap=qcap)

    def body(lk, lp, lc, chain, leaf0, qq):
        d = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32)
        base = d * (S_local * L)
        lk = lk.reshape(-1, cap)
        lp = lp.reshape(-1, cap)
        lc = lc.reshape(-1)
        Q = qq.shape[0]
        out_k = jnp.zeros((Q, max_blocks * cap), dtype=jnp.uint64)
        out_p = jnp.zeros((Q, max_blocks * cap), dtype=jnp.uint64)
        out_v = jnp.zeros((Q, max_blocks * cap), dtype=jnp.int32)
        leaf = leaf0
        for b in range(max_blocks):
            mine = (leaf >= base) & (leaf < base + S_local * L)
            lrow = leaf - base
            ks = jnp.take(lk, lrow, axis=0, mode="clip")
            ps = jnp.take(lp, lrow, axis=0, mode="clip")
            cnt = jnp.take(lc, lrow, mode="clip")
            valid = mine[:, None] & (jnp.arange(cap)[None, :] < cnt[:, None]) \
                & (ks >= qq[:, None])
            out_k = out_k.at[:, b * cap:(b + 1) * cap].set(
                jnp.where(valid, ks, jnp.uint64(0)))
            out_p = out_p.at[:, b * cap:(b + 1) * cap].set(
                jnp.where(valid, ps, jnp.uint64(0)))
            out_v = out_v.at[:, b * cap:(b + 1) * cap].set(
                valid.astype(jnp.int32))
            leaf = jnp.where(leaf >= 0,
                             jnp.take(chain, leaf, mode="clip"), -1)
        return (psum_disjoint(out_k), psum_disjoint(out_p),
                psum_disjoint(out_v))

    leaf_specs = tuple(
        PartitionSpec(MESH_AXIS, *(None,) * (stk[f].ndim - 1))
        for f in ("leaf_keys", "leaf_pay", "leaf_count"))
    out_k, out_p, out_v = jax.shard_map(
        body, mesh=mesh,
        in_specs=leaf_specs + (PartitionSpec(), PartitionSpec(),
                               PartitionSpec()),
        out_specs=(PartitionSpec(),) * 3,
        check_vma=False,   # scan/while bodies lack replication rules
    )(stk["leaf_keys"], stk["leaf_pay"], stk["leaf_count"],
      stk["leaf_next_chain"], gleaf, q)
    return _scan_compact(out_k, out_p, out_v.astype(bool), count)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "height", "count", "max_blocks",
                                    "qcap", "ov_bound"))
def scan_batch_sharded_overlay_mesh(mesh, stk: dict, ovr: dict,
                                    q: jnp.ndarray, count: int = 100,
                                    height: int = 3,
                                    max_blocks: int | None = None,
                                    qcap: int | None = None,
                                    ov_bound: int | None = None):
    """Mesh twin of :func:`scan_batch_sharded_overlay` (same overlay-window
    widening and two-way sorted merge, over the mesh scan)."""
    q = q.astype(jnp.uint64)
    keys, pays, tombs = _overlay_unpack(ovr)
    cap = keys.shape[0]
    hide = cap if ov_bound is None else min(int(ov_bound), cap)
    base = count + hide
    if max_blocks is not None:
        leaf_cap = stk["leaf_keys"].shape[2]
        max_blocks = max_blocks + hide // max(leaf_cap // 2, 1) + 1
    ks, ps, vs = scan_batch_sharded_mesh(mesh, stk, q, count=base,
                                         height=height, max_blocks=max_blocks,
                                         qcap=qcap)
    return _overlay_scan_merge(ks, ps, vs, keys, pays, tombs, q, count, hide)


@functools.lru_cache(maxsize=8)
def _mesh_install_fn(mesh, ndims: tuple):
    """Jitted donated single-device shard install for one mesh (DESIGN.md
    §13): under shard_map only the device owning the target shard rewrites
    its pool slices; every other device's slices pass through untouched —
    the stacked-pool upload of an async compaction or repartition swap
    touches exactly one device.  ``ndims`` = per-field stacked ranks (the
    spec layout), so one compile serves every shard/stack of that layout."""
    specs = {f: PartitionSpec(MESH_AXIS, *(None,) * (nd - 1))
             for f, nd in zip(_DEVICE_FIELDS, ndims)}

    def body(pools, s, rows):
        d = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32)
        S_local = next(iter(pools.values())).shape[0]
        local = s - d * S_local
        own = (local >= 0) & (local < S_local)
        lc = jnp.clip(local, 0, S_local - 1).astype(jnp.int32)
        out = {}
        for f, a in pools.items():
            row = jnp.where(own, rows[f], a[lc])
            idx = (lc,) + (jnp.int32(0),) * (a.ndim - 1)
            out[f] = jax.lax.dynamic_update_slice(a, row[None], idx)
        return out

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, PartitionSpec(), {f: PartitionSpec()
                                           for f in _DEVICE_FIELDS}),
        out_specs=specs,
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def update_stacked_shard_mesh(mesh, stk: dict, sdi, shards: list[int],
                              dev_slices: dict | None = None) -> dict:
    """Mesh twin of :func:`update_stacked_shard`: same per-shard donated
    in-place installs (O(slice), one compile per mesh+layout), executed as
    single-device writes on the device owning each shard; the small
    replicated/per-shard metadata re-places through the index placement
    rules."""
    from ..parallel.index_placement import place_stacked
    assert shards, "update_stacked_shard_mesh needs at least one shard"
    stk = dict(stk)
    pools = {f: stk[f] for f in _DEVICE_FIELDS}
    install = _mesh_install_fn(
        mesh, tuple(stk[f].ndim for f in _DEVICE_FIELDS))
    for s in shards:
        dev = dev_slices.get(s) if dev_slices is not None else None
        rows = {f: dev[f] if dev is not None and f in dev
                else jnp.asarray(getattr(sdi, f)[s]) for f in _DEVICE_FIELDS}
        pools = install(pools, jnp.int32(s), rows)
    stk.update(pools)
    stk.update(place_stacked(
        {"meta": jnp.asarray(sdi.meta),
         "last_leaf_min": jnp.asarray(sdi.last_leaf_min),
         "leaf_next_chain": jnp.asarray(sdi.leaf_next_chain)}, mesh))
    stk["snap_token"] = new_snap_token()
    return stk


def mesh_lookup_backend_fns(backend: str, mesh):
    """Mesh twin of :func:`lookup_backend_fns`: the overlay-merged
    point-lookup entry bound to an index mesh, callable as
    ``fn(snap, ovr, q, height=..., qcap=...)``.  "fused" keeps the Pallas
    kernel per-device-local under shard_map; "jnp" is the bit-exact oracle,
    as everywhere else."""
    b = resolve_read_backend(backend)
    if b == "jnp":
        return functools.partial(lookup_batch_sharded_overlay_mesh, mesh)
    from ..kernels.fused_lookup.ops import (
        fused_lookup_batch_sharded_overlay_mesh)
    return functools.partial(fused_lookup_batch_sharded_overlay_mesh, mesh,
                             interpret=(b == "fused_interpret"))
