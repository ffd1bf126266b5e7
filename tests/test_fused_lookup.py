"""Fused Pallas lookup kernel == the jnp read path, bit for bit.

Tier-1 runs the kernel in interpret mode (DESIGN.md §10): every parity test
here compares the fused route→inner-probe→leaf-search→overlay-merge launch
against the jnp oracle (`lookup_batch` & friends) on the SAME operands —
payloads, found flags, leaf rows, and shard ids must be identical, not just
equivalent.  Both leaf strategies (persistent / looped DMA) and both gather
implementations (take / onehot) are exercised; the tiling layer and the
engines' backend switch get their own unit tests.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (Aulid, AulidConfig, BlockDevice, DeltaOverlay,
                        partition_bulkload)
from repro.core.device_index import build_device_index, stack_device_indexes
from repro.core.lookup import (READ_BACKENDS, device_arrays, lookup_batch,
                               lookup_batch_overlay, lookup_batch_sharded,
                               lookup_batch_sharded_overlay,
                               lookup_backend_fns, overlay_arrays,
                               resolve_read_backend, stacked_device_arrays)
from repro.core.workloads import make_dataset, payloads_for
from repro.kernels.fused_lookup import (PoolGeometry, TileStrategy,
                                        choose_strategy, fused_lookup_batch,
                                        fused_lookup_batch_overlay,
                                        fused_lookup_batch_sharded,
                                        fused_lookup_batch_sharded_overlay)
from repro.kernels.fused_lookup import tuning
from repro.kernels.fused_lookup import ops as ops_mod
from repro.serving import IndexEngine, ShardedIndexEngine

import jax.numpy as jnp

SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)

# the full strategy grid: leaf residency x gather implementation (qb=64
# keeps the interpret-mode grids small); all run with interpret=True here
STRATEGIES = [
    TileStrategy(qb=64, leaf="persistent", gather="take"),
    TileStrategy(qb=64, leaf="looped", gather="take"),
    TileStrategy(qb=64, leaf="persistent", gather="onehot"),
    TileStrategy(qb=64, leaf="looped", gather="onehot"),
]
_IDS = [f"{s.leaf}-{s.gather}" for s in STRATEGIES]


def _same(got, exp):
    for g, e in zip(got, exp):
        assert np.asarray(g).shape == np.asarray(e).shape
        assert (np.asarray(g) == np.asarray(e)).all()


# Pristine mirrors shared across tests: parity tests never mutate them,
# so each distinct kernel config traces once for the whole module.
_CACHE: dict = {}


def _mono(name="planet", n=2_500):
    if ("mono", name) not in _CACHE:
        keys = make_dataset(name, n, seed=1)
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, payloads_for(keys))
        di = build_device_index(idx)
        _CACHE[("mono", name)] = (keys, idx, di, device_arrays(di),
                                  max(di.max_inner_height, 3))
    return _CACHE[("mono", name)]


def _stack(name="covid", n=3_000, num_shards=4):
    if ("stack", name) not in _CACHE:
        keys = make_dataset(name, n, seed=1)
        part = partition_bulkload(keys, payloads_for(keys), num_shards,
                                  cfg=AulidConfig(**SMALL_GEOM))
        dis = [build_device_index(sh) for sh in part.shards]
        sdi = stack_device_indexes(dis, part.bounds)
        _CACHE[("stack", name)] = (keys, part, sdi,
                                   stacked_device_arrays(sdi),
                                   max(sdi.max_inner_height, 3))
    return _CACHE[("stack", name)]


def _queries(keys, rng, n_hits=160, n_miss=64):
    hits = rng.choice(keys, n_hits).astype(np.uint64)
    misses = rng.integers(0, 2**62, n_miss).astype(np.uint64)
    return jnp.asarray(np.concatenate([hits, misses]))


class TestMonolithicParity:
    @pytest.mark.parametrize("strategy", STRATEGIES, ids=_IDS)
    def test_hits_and_misses(self, strategy):
        keys, idx, di, arrs, h = _mono()
        q = _queries(keys, np.random.default_rng(0))
        exp = lookup_batch(arrs, q, height=h)
        got = fused_lookup_batch(arrs, q, height=h, interpret=True,
                                 strategy=strategy)
        _same(got, exp)
        assert bool(np.asarray(got[1])[:160].all())      # hits found
        assert not bool(np.asarray(got[1])[160:].any())  # misses not

    def test_ragged_batch_padding(self):
        """Q not a multiple of qb: the u64-max tile padding must not leak
        into results (same sentinel discipline as the engines')."""
        keys, idx, di, arrs, h = _mono()
        q = _queries(keys, np.random.default_rng(3))[:77]
        _same(fused_lookup_batch(arrs, q, height=h, interpret=True,
                                 strategy=STRATEGIES[0]),
              lookup_batch(arrs, q, height=h))

    def test_empty_mirror(self):
        """Never-bulkloaded mirror (TestEmptyMirror edge): all-padding
        leaves, root_node == -1 — the fused kernel serves nothing too."""
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        di = build_device_index(idx)
        assert di.root_node == -1
        arrs = device_arrays(di)
        q = jnp.asarray(np.array([0, 5, 2**50], dtype=np.uint64))
        exp = lookup_batch(arrs, q, height=3)
        got = fused_lookup_batch(arrs, q, height=3, interpret=True,
                                 strategy=STRATEGIES[0])
        _same(got, exp)
        assert not bool(np.asarray(got[1]).any())

    def test_stale_chain_walk(self):
        """Force the STALE_STEPS successor-chain walk (the stale-high MIXED
        slot-key patch of test_device_lookup): queries routed past a child's
        last entry must resolve through the succ/overflow threading in the
        fused kernel exactly as in the jnp path."""
        rng = np.random.default_rng(2)
        keys = np.unique(rng.integers(0, 2**60, 12_000).astype(np.uint64))
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, keys + np.uint64(1))
        hot = np.unique(rng.integers(10**9, 10**9 + 10**6, 3_000)
                        ).astype(np.uint64)
        for k in hot:
            idx.insert(int(k), int(k) + 1)
        di = build_device_index(idx)
        assert di.inner_height >= 2, "need nested mixed nodes for this test"
        TAG_MIXED = 4
        target = -1
        for g in np.nonzero(di.slot_tag == TAG_MIXED)[0]:
            if int(di.succ_slot[int(g)]) >= 0:
                child = int(di.slot_ptr[int(g)])
                if int(di.node_overflow_slot[child]) >= 0 \
                        and di.slot_key[int(di.succ_slot[int(g)])] \
                        > di.slot_key[int(g)] + np.uint64(4):
                    target = int(g)
                    break
        assert target >= 0, "no patchable nested mixed entry found"
        child_max = int(di.slot_key[target])
        succ_key = int(di.slot_key[int(di.succ_slot[target])])
        di.slot_key[target] = np.uint64(succ_key - 1)  # stale-high parent max
        arrs = device_arrays(di)
        h = max(di.max_inner_height, 3)
        qs = np.array([child_max + 1, child_max + 2, succ_key - 2],
                      dtype=np.uint64)
        q = jnp.asarray(qs[qs > child_max])
        for strategy in STRATEGIES[:2]:
            _same(fused_lookup_batch(arrs, q, height=h, interpret=True,
                                     strategy=strategy),
                  lookup_batch(arrs, q, height=h))


class TestOverlayParity:
    def _overlaid(self):
        keys, idx, di, arrs, h = _mono("covid")
        ov = DeltaOverlay()
        rng = np.random.default_rng(7)
        fresh = np.unique(rng.integers(0, 2**55, 64).astype(np.uint64))
        for k in fresh:
            ov.record_insert(int(k), int(k) + 9)
        upd = rng.choice(keys, 16).astype(np.uint64)     # shadow snapshot keys
        for k in upd:
            ov.record_insert(int(k), int(k) + 77)
        dead = rng.choice(keys, 16).astype(np.uint64)    # tombstone snapshot keys
        for k in dead:
            ov.record_delete(int(k))
        q = np.concatenate([fresh[:32], upd, dead,
                            rng.choice(keys, 64).astype(np.uint64),
                            rng.integers(0, 2**62, 32).astype(np.uint64)])
        return arrs, overlay_arrays(ov), jnp.asarray(q), h, len(fresh[:32])

    @pytest.mark.parametrize("strategy", STRATEGIES[:2], ids=_IDS[:2])
    def test_inserts_updates_tombstones(self, strategy):
        arrs, ovr, q, h, n_fresh = self._overlaid()
        exp = lookup_batch_overlay(arrs, ovr, q, height=h)
        got = fused_lookup_batch_overlay(arrs, ovr, q, height=h,
                                         interpret=True, strategy=strategy)
        _same(got, exp)
        f = np.asarray(got[1])
        assert f[:n_fresh].all()                       # overlay-only hits
        assert not f[n_fresh + 16: n_fresh + 32].any()  # tombstoned erased

    def test_empty_overlay_pack(self):
        """A live-but-empty overlay pack (all padding): merge must be a
        no-op, including on the never-matching u64-max sentinels."""
        keys, idx, di, arrs, h = _mono("covid")
        ovr = overlay_arrays(DeltaOverlay())
        q = _queries(keys, np.random.default_rng(9), 48, 16)
        _same(fused_lookup_batch_overlay(arrs, ovr, q, height=h,
                                         interpret=True,
                                         strategy=STRATEGIES[0]),
              lookup_batch_overlay(arrs, ovr, q, height=h))


class TestShardedParity:
    @pytest.mark.parametrize("strategy", STRATEGIES[:2], ids=_IDS[:2])
    def test_lookup(self, strategy):
        keys, part, sdi, stk, h = _stack()
        q = _queries(keys, np.random.default_rng(1))
        exp = lookup_batch_sharded(stk, q, height=h)       # pay,found,gleaf,sid
        got = fused_lookup_batch_sharded(stk, q, height=h, interpret=True,
                                         strategy=strategy)
        _same(got, exp)
        assert len(set(np.asarray(got[3]).tolist())) > 1   # crosses shards

    def test_boundary_routing(self):
        """Keys exactly at the shard bounds (inclusive max) and one past:
        the in-kernel route (sum of bounds < q) must agree with the jnp
        searchsorted route on both sides of every boundary."""
        keys, part, sdi, stk, h = _stack()
        edges = []
        for b in np.asarray(part.bounds, dtype=np.uint64):
            edges += [int(b), int(b) + 1]
        q = jnp.asarray(np.array(edges, dtype=np.uint64))
        _same(fused_lookup_batch_sharded(stk, q, height=h, interpret=True,
                                         strategy=STRATEGIES[0]),
              lookup_batch_sharded(stk, q, height=h))

    def test_overlay_merge(self):
        """Global overlay pack spanning several shards (shard order IS key
        order) merged inside the sharded fused launch."""
        keys, part, sdi, stk, h = _stack()
        ov = DeltaOverlay()
        rng = np.random.default_rng(4)
        fresh = np.unique(rng.integers(0, 2**55, 48).astype(np.uint64))
        for k in fresh:
            ov.record_insert(int(k), int(k) + 5)
        dead = rng.choice(keys, 12).astype(np.uint64)
        for k in dead:
            ov.record_delete(int(k))
        ovr = overlay_arrays(ov)
        q = jnp.asarray(np.concatenate(
            [fresh[:24], dead, rng.choice(keys, 48).astype(np.uint64)]))
        exp = lookup_batch_sharded_overlay(stk, ovr, q, height=h)
        got = fused_lookup_batch_sharded_overlay(stk, ovr, q, height=h,
                                                 interpret=True,
                                                 strategy=STRATEGIES[1])
        _same(got, exp)
        assert not np.asarray(got[1])[24:36].any()         # tombstones erased


class TestTuning:
    def _geom(self, **kw):
        base = dict(num_shards=1, slot_pool=512, node_pool=64, pa_pool=32,
                    pa_cap=8, bt_pool=32, bt_cap=15, leaf_pool=256,
                    leaf_cap=16, overlay_bucket=0)
        return PoolGeometry(**{**base, **kw})

    def test_choose_strategy_table(self):
        small = self._geom()
        st = choose_strategy(small, interpret=True)
        assert (st.leaf, st.gather) == ("persistent", "take")
        st = choose_strategy(small, interpret=False)
        assert (st.leaf, st.gather) == ("persistent", "onehot")
        # leaf pool past the VMEM budget -> looped
        big = self._geom(leaf_pool=2**20, leaf_cap=32)
        assert choose_strategy(big, interpret=True).leaf == "looped"
        assert choose_strategy(big, interpret=False).leaf == "looped"
        # the compiled kernel refuses what it cannot take; interpret does not
        wide = self._geom(slot_pool=tuning.ONEHOT_LANE_CAP + 1)
        with pytest.raises(ValueError, match="one-hot"):
            choose_strategy(wide, interpret=False)
        assert choose_strategy(wide, interpret=True).leaf == "persistent"
        fat = self._geom(pa_pool=2**16, pa_cap=64)
        with pytest.raises(ValueError, match="VMEM"):
            choose_strategy(fat, interpret=False)
        assert choose_strategy(fat, interpret=True).leaf == "looped"
        # tiny mirror -> smallest tile
        tiny = self._geom(leaf_pool=4, leaf_cap=8)
        assert choose_strategy(tiny, interpret=True).qb == min(
            tuning.QB_CANDIDATES)

    def test_rows_dma_per_query(self):
        g = self._geom()
        per = choose_strategy(g, interpret=True)
        assert per.leaf == "persistent"
        looped = dataclasses.replace(per, leaf="looped")
        resident = tuning.rows_dma_per_query(g, per, batch=4096)
        streamed = tuning.rows_dma_per_query(g, looped, batch=4096)
        # looped: exactly one leaf-row DMA per query on top of the shared
        # resident pools; persistent amortizes the whole leaf pool instead
        assert streamed == pytest.approx(
            resident - g.leaf_rows / 4096 + 1.0)
        assert tuning.rows_dma_per_query(g, looped, batch=1) > 1.0

    def test_pool_geometry_roundtrip(self):
        keys, idx, di, arrs, h = _mono()
        assert PoolGeometry.from_device_arrays(arrs) == \
            PoolGeometry.from_pools(di.pool_geometry())
        keys, part, sdi, stk, h = _stack()
        assert PoolGeometry.from_device_arrays(stk) == \
            PoolGeometry.from_pools(sdi.pool_geometry())
        ovr = overlay_arrays(DeltaOverlay())
        g = PoolGeometry.from_device_arrays(arrs, ovr)
        assert g.overlay_bucket == int(ovr["ov_pack"].shape[1])

    def test_autotune_sweeps_once_per_geometry(self):
        tuning.clear_autotune_cache()
        g = self._geom()
        calls = []

        def bench(st):
            calls.append(st.qb)
            return {64: 3.0, 128: 1.0, 256: 2.0}[st.qb]

        won = tuning.autotune(g, bench, interpret=True)
        assert won.qb == 128 and won.autotuned
        assert sorted(calls) == sorted(tuning.QB_CANDIDATES)
        again = tuning.autotune(g, lambda st: 1 / 0, interpret=True)
        assert again is won                      # cached: bench never called
        assert tuning.autotune(self._geom(leaf_pool=128), bench,
                               interpret=True) is not won
        tuning.clear_autotune_cache()


class TestBackendDispatch:
    def test_resolve(self):
        assert resolve_read_backend("jnp") == "jnp"
        assert resolve_read_backend("fused_interpret") == "fused_interpret"
        assert resolve_read_backend("auto") == "jnp"   # on every platform
        import jax
        if jax.default_backend() != "tpu":
            # the compiled kernel never degrades to interpret mode
            with pytest.raises(ValueError, match="fused_interpret"):
                resolve_read_backend("fused")
            with pytest.raises(ValueError):
                lookup_backend_fns("fused")
        with pytest.raises(ValueError):
            resolve_read_backend("cuda_graphs")
        with pytest.raises(ValueError):
            IndexEngine(Aulid(), backend="nope")

    def test_backend_fns_parity(self):
        keys, idx, di, arrs, h = _mono("covid")
        ovr = overlay_arrays(DeltaOverlay())
        q = _queries(keys, np.random.default_rng(5), 48, 16)
        _same(lookup_backend_fns("fused_interpret")(arrs, ovr, q, height=h),
              lookup_backend_fns("jnp")(arrs, ovr, q, height=h))

    def _drive(self, eng, keys, rng, steps=3):
        out = []
        for _ in range(steps):
            reqs = []
            for k in rng.integers(0, 2**48, 24):
                eng.insert(int(k), int(k) % 997)
            for k in rng.choice(keys, 48):
                reqs.append(eng.get(int(k)))
            eng.step()
            out += [(r.key, r.result) for r in reqs]
        return out

    def test_engine_streams_identical(self):
        keys = make_dataset("covid", 1_200, seed=3)

        def build(backend):
            idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
            idx.bulkload(keys, payloads_for(keys))
            return IndexEngine(idx, gamma=0.02, backend=backend)

        a, b = build("jnp"), build("fused_interpret")
        assert (a.read_backend, b.read_backend) == ("jnp", "fused_interpret")
        assert b.stats()["read_backend"] == "fused_interpret"
        ra = self._drive(a, keys, np.random.default_rng(11))
        rb = self._drive(b, keys, np.random.default_rng(11))
        assert ra == rb
        assert b.stats()["compactions"] >= 1    # parity held across refresh

    def test_sharded_engine_streams_identical(self):
        keys = make_dataset("osm", 1_600, seed=3)
        pays = payloads_for(keys)

        def build(backend):
            part = partition_bulkload(keys, pays, 4,
                                      cfg=AulidConfig(**SMALL_GEOM))
            return ShardedIndexEngine(part, gamma=0.02, backend=backend)

        a, b = build("jnp"), build("fused_interpret")
        assert b.stats()["read_backend"] == "fused_interpret"
        ra = self._drive(a, keys, np.random.default_rng(13))
        rb = self._drive(b, keys, np.random.default_rng(13))
        assert ra == rb


class TestOperandCacheTokens:
    """Operand-pack cache keying (DESIGN.md §10 caveat): keyed by the
    snapshot's monotonic token, never by a recyclable ``id()``."""

    def setup_method(self):
        ops_mod.clear_operand_cache()

    def teardown_method(self):
        ops_mod.clear_operand_cache()

    def test_distinct_snapshots_distinct_entries(self):
        keys, idx, di, arrs, h = _mono("covid")
        a1 = device_arrays(di)
        a2 = device_arrays(di)          # same content, NEW snapshot token
        assert a1["snap_token"] != a2["snap_token"]
        p1 = ops_mod._operands(a1)
        p2 = ops_mod._operands(a2)
        assert p1 is not p2
        assert ops_mod._operands(a1) is p1    # both stay resident
        assert ops_mod._operands(a2) is p2

    def test_id_reuse_cannot_alias(self):
        """The historical bug: a GC'd snapshot dict's id given to a new
        snapshot must NOT hit the old pack.  Token keys make the dict's id
        irrelevant — equal ids, different tokens, different packs."""
        keys, idx, di, arrs, h = _mono("covid")
        a1 = device_arrays(di)
        p1 = ops_mod._operands(a1)
        a2 = device_arrays(di)
        a2_id = id(a2)
        p2 = ops_mod._operands(a2)
        assert p2 is not p1
        del a2                               # id(a2) may now be recycled
        a3 = device_arrays(di)
        p3 = ops_mod._operands(a3)
        assert p3 is not p1                  # fresh token -> fresh entry
        del a2_id, a3

    def test_unstamped_dict_fallback_pins(self):
        """Hand-built operand dicts (no token) still cache — keyed by
        identity with the dict pinned so the id cannot be recycled while
        the entry lives."""
        keys, idx, di, arrs, h = _mono("covid")
        bare = {k: v for k, v in device_arrays(di).items()
                if k != "snap_token"}
        p1 = ops_mod._operands(bare)
        assert ops_mod._operands(bare) is p1
        ent = ops_mod._OPERANDS[("id", id(bare))]
        assert ent[0] is bare                # pinned

    def test_eviction_bound(self):
        keys, idx, di, arrs, h = _mono("covid")
        packs = [ops_mod._operands(device_arrays(di))
                 for _ in range(ops_mod._CACHE_LIMIT + 5)]
        assert len(ops_mod._OPERANDS) == ops_mod._CACHE_LIMIT
        del packs

    def test_lru_keeps_hot_entries(self):
        keys, idx, di, arrs, h = _mono("covid")
        hot = device_arrays(di)
        ops_mod._operands(hot)
        for _ in range(ops_mod._CACHE_LIMIT - 1):
            ops_mod._operands(device_arrays(di))
        hot_pack = ops_mod._operands(hot)     # touch: moves to MRU
        ops_mod._operands(device_arrays(di))  # evicts the LRU, not `hot`
        assert ops_mod._operands(hot) is hot_pack

    def test_overlay_token_keying(self):
        ov = DeltaOverlay()
        ov.record_insert(5, 50)
        o1 = overlay_arrays(ov)
        p1 = ops_mod._overlay_operands(o1)
        ov.record_insert(6, 60)
        o2 = overlay_arrays(ov)
        assert o2["ov_token"] != o1["ov_token"]
        assert ops_mod._overlay_operands(o2) is not p1
        assert ops_mod._overlay_operands(o1) is p1
