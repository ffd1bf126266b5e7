"""Tiling-strategy layer for the fused lookup kernel (DESIGN.md §10).

The fused kernel has two degrees of freedom that depend only on *pool
geometry* — not on the queries — so they are decided here, once per mirror
shape, instead of being hardcoded in the kernel:

* **query tile size** ``qb``: how many queries one grid step resolves.  Small
  tiles waste VPU lanes; huge tiles blow the per-step register/VMEM working
  set (the (qb, C) block-search temporaries).
* **leaf residency** — the helion-style persistent-vs-looped choice:

  - ``"persistent"``: the leaf pool rides a constant-index-map BlockSpec, so
    it loads into VMEM once and stays resident across the whole grid; the
    leaf step is a vectorized row gather (fastest when the pool fits the
    VMEM budget).
  - ``"looped"``: the leaf pool stays in HBM (``pltpu.ANY``); the kernel
    walks the query tile with an in-kernel async copy that DMAs exactly ONE
    ``(4, C)`` leaf row per query — the paper's "fetch one block per probe"
    executed literally, and the only option once the leaf pool outgrows
    VMEM.

The gather implementation is tied to the execution mode: interpret mode
(CPU) uses ``jnp.take`` directly, while a compiled TPU lowering needs the
one-hot compare-and-reduce idiom (``"onehot"``) for per-query values and a
per-query dynamic row load (or DMA) for whole rows.  A one-hot gather
materializes a (qb, X) mask over the gathered pool, so the compiled kernel
only takes pools up to ``ONEHOT_LANE_CAP`` entries wide, and its resident
pools must fit the VMEM budget: :func:`choose_strategy` raises for any
geometry the compiled kernel cannot take.

``autotune`` runs a cached sweep over candidate tile sizes with a
caller-supplied measurement function; the cache is keyed by geometry so the
sweep happens once per distinct pool shape per process.
"""
from __future__ import annotations

import dataclasses

# Per-core VMEM is ~16 MB on current TPUs; leave half for the pipeline,
# outputs, and the (qb, C) search temporaries.
DEFAULT_VMEM_BUDGET = 8 * 2 ** 20

# one-hot gathers materialize a (qb, X) int32 mask per gather: cap X so a
# few such temporaries fit the kernel's scoped VMEM next to the pools
ONEHOT_LANE_CAP = 8192

QB_CANDIDATES = (64, 128, 256)
DEFAULT_QB = 128  # one VPU lane row per u32 plane


@dataclasses.dataclass(frozen=True)
class PoolGeometry:
    """Static shape summary of one device mirror (stacked or monolithic).

    All counts are per shard except ``overlay_bucket`` (the overlay pack is
    global).  Hashable, so it keys the autotune cache and the jit caches of
    the kernel entry points.
    """
    num_shards: int
    slot_pool: int          # Smax — slots per shard
    node_pool: int          # Nmax
    pa_pool: int            # Pmax rows
    pa_cap: int             # keys per PA row
    bt_pool: int            # Bmax rows
    bt_cap: int
    leaf_pool: int          # Lmax rows
    leaf_cap: int           # C — keys per leaf block
    overlay_bucket: int     # padded overlay capacity (0 = no overlay operand)

    # ------------------------------------------------------------- VMEM sizing
    @property
    def inner_bytes(self) -> int:
        """Resident bytes of the non-leaf pools as the kernel packs them:
        32-bit planes for keys/payloads, links/tags and the f32 models."""
        s = self.num_shards
        slots = s * self.slot_pool * (4 * 4 + 2 * 4)      # 4 i32 rows + 2 keys
        nodes = s * self.node_pool * (5 * 4 + 2 * 4)      # 5 i32 rows + 2 f32
        pa = s * self.pa_pool * self.pa_cap * (2 * 4 + 4)  # key planes + ptrs
        bt = s * self.bt_pool * self.bt_cap * (2 * 4 + 4)
        return slots + nodes + pa + bt

    @property
    def leaf_bytes(self) -> int:
        # 4 u32 planes per row: key hi/lo + payload hi/lo
        return self.num_shards * self.leaf_pool * self.leaf_cap * 4 * 4

    @property
    def overlay_bytes(self) -> int:
        return self.overlay_bucket * (4 * 4 + 4)          # 4 u32 planes + tomb

    @property
    def leaf_rows(self) -> int:
        return self.num_shards * self.leaf_pool

    @property
    def onehot_widths(self) -> dict:
        """Widths of the pools the compiled kernel gathers from one-hot."""
        s = self.num_shards
        return {"slots": s * self.slot_pool, "nodes": s * self.node_pool,
                "overlay": self.overlay_bucket}

    # ---------------------------------------------------------- constructors
    @classmethod
    def from_device_arrays(cls, arrs: dict, ovr: dict | None = None
                           ) -> "PoolGeometry":
        """Geometry of a ``device_arrays`` / ``stacked_device_arrays`` dict
        (stacked pools carry the leading shard axis)."""
        stacked = arrs["leaf_keys"].ndim == 3
        lead = (lambda a: a.shape[1]) if stacked else (lambda a: a.shape[0])
        return cls(
            num_shards=arrs["meta"].shape[0] if stacked else 1,
            slot_pool=lead(arrs["slot_tag"]),
            node_pool=lead(arrs["node_base"]),
            pa_pool=lead(arrs["pa_keys"]),
            pa_cap=arrs["pa_keys"].shape[-1],
            bt_pool=lead(arrs["bt_keys"]),
            bt_cap=arrs["bt_keys"].shape[-1],
            leaf_pool=lead(arrs["leaf_keys"]),
            leaf_cap=arrs["leaf_keys"].shape[-1],
            overlay_bucket=(int(ovr["ov_pack"].shape[1]) if ovr else 0),
        )

    @classmethod
    def from_pools(cls, pools: dict, overlay_bucket: int = 0
                   ) -> "PoolGeometry":
        """From ``DeviceIndex.pool_geometry()`` metadata (core layer stays
        free of kernel imports; this adapter owns the field mapping)."""
        return cls(overlay_bucket=overlay_bucket, **pools)


@dataclasses.dataclass(frozen=True)
class TileStrategy:
    """One resolved kernel configuration for a geometry."""
    qb: int                 # queries per grid step
    leaf: str               # "persistent" | "looped"
    gather: str             # "take" (interpret) | "onehot" (compiled)
    autotuned: bool = False

    def describe(self) -> str:
        tag = "autotuned" if self.autotuned else "heuristic"
        return f"qb={self.qb} leaf={self.leaf} gather={self.gather} ({tag})"


def choose_strategy(geom: PoolGeometry, *, interpret: bool,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TileStrategy:
    """Heuristic strategy table (DESIGN.md §10):

    ==========================  =============  ==========================
    geometry                    leaf strategy  rationale
    ==========================  =============  ==========================
    inner+leaf+overlay <= VMEM  persistent     one load, zero per-query DMA
    leaf pool > VMEM budget     looped         1 row DMA/query, exact fetch
    ==========================  =============  ==========================

    For the compiled kernel (``interpret=False``) it raises ``ValueError``
    when no strategy can compile: the inner pools and overlay alone over the
    VMEM budget, or a one-hot gathered pool wider than ``ONEHOT_LANE_CAP``.
    """
    gather = "take" if interpret else "onehot"
    resident = geom.inner_bytes + geom.leaf_bytes + geom.overlay_bytes
    leaf = "persistent" if resident <= vmem_budget else "looped"
    if not interpret:
        fixed = geom.inner_bytes + geom.overlay_bytes
        if fixed > vmem_budget:
            raise ValueError(
                f"compiled fused kernel: resident inner pools + overlay "
                f"need {fixed} B of VMEM, over the {vmem_budget} B budget")
        wide = {k: v for k, v in geom.onehot_widths.items()
                if v > ONEHOT_LANE_CAP}
        if wide:
            raise ValueError(
                f"compiled fused kernel: one-hot gathers over {wide} "
                f"exceed {ONEHOT_LANE_CAP} lanes")
    qb = DEFAULT_QB
    # a tiny mirror does not fill a 128-lane tile with useful work
    if geom.leaf_rows * geom.leaf_cap < DEFAULT_QB:
        qb = min(QB_CANDIDATES)
    return TileStrategy(qb=qb, leaf=leaf, gather=gather)


# autotune cache: geometry (+ mode) -> TileStrategy picked by measurement
_AUTOTUNE_CACHE: dict[tuple, TileStrategy] = {}


def clear_autotune_cache() -> None:
    _AUTOTUNE_CACHE.clear()


def autotune(geom: PoolGeometry, bench, *, interpret: bool,
             candidates: tuple[int, ...] = QB_CANDIDATES,
             vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TileStrategy:
    """Sweep candidate query-tile sizes with the caller's measurement
    function ``bench(strategy) -> seconds`` and cache the winner per
    geometry.  The leaf/gather choice comes from :func:`choose_strategy`
    (residency is a capacity constraint, not a taste to measure)."""
    key = (geom, interpret, tuple(candidates), vmem_budget)
    hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    base = choose_strategy(geom, interpret=interpret,
                           vmem_budget=vmem_budget)
    timings = []
    for qb in candidates:
        st = dataclasses.replace(base, qb=qb)
        timings.append((bench(st), qb))
    best_qb = min(timings)[1]
    won = dataclasses.replace(base, qb=best_qb, autotuned=True)
    _AUTOTUNE_CACHE[key] = won
    return won


def rows_dma_per_query(geom: PoolGeometry, strategy: TileStrategy,
                       batch: int) -> float:
    """HBM→VMEM *rows* moved per query for one launch at ``batch`` queries —
    the benchmark's I/O metric next to ``kernel_block_rounds``.

    Resident pools amortize over the batch (they load once per launch);
    the looped leaf strategy adds exactly one leaf-row DMA per query — the
    paper's per-probe block fetch."""
    batch = max(int(batch), 1)
    resident_rows = (
        geom.num_shards * (geom.slot_pool / geom.leaf_cap  # flat pools in
                           + geom.node_pool / geom.leaf_cap)  # row units
        + geom.num_shards * geom.pa_pool
        + geom.num_shards * geom.bt_pool
        + (geom.overlay_bucket / geom.leaf_cap if geom.overlay_bucket else 0))
    per_query = 0.0
    if strategy.leaf == "persistent":
        resident_rows += geom.leaf_rows
    else:
        per_query = 1.0
    return resident_rows / batch + per_query
