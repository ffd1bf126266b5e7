"""Device-resident overlay merge == host-dict repack, bit for bit.

The write-path acceptance oracle (DESIGN.md §14): merging a sorted write
batch into the device overlay pack must produce exactly the pack a full
host repack of ``{**overlay, **batch}`` would — same sorted union, same
last-writer-wins payloads, same retained tombstones, same padding.  That
exactness is what lets the serving engines ship O(batch) bytes per step
instead of re-uploading the whole overlay, with the host dict surviving
only as compaction input and as the oracle here.

Layers under test: the rank-arithmetic jnp merge and the Pallas kernel
(interpret mode) against a literal dict repack; ``DeltaOverlay``'s
incremental sorted mirror against a from-scratch rebuild; and the serving
engine's delta write path, over one shard and over three, against
full-repack twins across compaction swaps (hand-pumped pool) and an online
split.
"""
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from test_async_compaction import ManualExecutor

from repro.core import AulidConfig, DeltaOverlay, partition_bulkload
from repro.core.delta_overlay import next_pow2
from repro.core.lookup import (empty_overlay_pack, merge_overlay_pack,
                               merge_overlay_pack_jnp, merge_window,
                               overlay_merge_backend_fn)
from repro.core.workloads import make_dataset, payloads_for
from repro.kernels.overlay_merge import (overlay_merge_pack,
                                         overlay_merge_pack_stacked)
from repro.serving import ShardedIndexEngine
from repro.serving import index_engine as ie_mod

import jax.numpy as jnp

UM = np.uint64(2**64 - 1)
SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)

# fixed plane shapes so the whole parity suite shares one kernel compile
CAP_A, CAP_B, CAP_OUT = 32, 16, 64


def dict_pack(d: dict, cap: int) -> np.ndarray:
    """The oracle: a {key: (payload, tomb)} dict packed sorted + padded,
    exactly as ``overlay_arrays`` lays the overlay out on device."""
    assert len(d) <= cap
    pack = np.zeros((3, cap), dtype=np.uint64)
    pack[0] = UM
    for i, k in enumerate(sorted(d)):
        pack[0, i] = k
        pack[1, i] = d[k][0]
        pack[2, i] = d[k][1]
    return pack


def rand_dict(rng, n, overlap_keys=()):
    d = {}
    for k in rng.integers(0, 2**50, n):
        d[int(k)] = (int(rng.integers(0, 2**40)), bool(rng.random() < 0.25))
    for k in overlap_keys:
        if rng.random() < 0.5:
            d[int(k)] = (int(rng.integers(0, 2**40)),
                         bool(rng.random() < 0.25))
    return d


_EVENS = {2 * i + 100: (i, False) for i in range(CAP_B)}
_TOP = 2**64 - 2                      # the largest key below the padding
# (overlay dict, batch dict, cap_out): cases of the survivors' rank, each
# with distinct payloads so a misplaced entry shows
RANK_CASES = {
    "all_new": (_EVENS, {2 * i + 101: (500 + i, i % 2 == 0)
                         for i in range(CAP_B)}, CAP_OUT),
    "all_overwrite": (_EVENS, {k: (900 + p, p % 3 == 0)
                               for k, (p, _) in _EVENS.items()}, CAP_OUT),
    "batch_below": (_EVENS, {i: (700 + i, False) for i in range(CAP_B)},
                    CAP_OUT),
    "batch_above": (_EVENS, {10_000 + i: (700 + i, i == 3)
                             for i in range(CAP_B)}, CAP_OUT),
    # new key, overwrite, new key, overwrite ... slot by slot
    "alternating": (_EVENS, {k: (800 + k, k % 8 == 3)
                             for i in range(CAP_B // 2)
                             for k in (4 * i + 99, 4 * i + 100)}, CAP_OUT),
    # the window's every slot is live; one new key lands past the last
    "full_window_new_last": ({3 * i + 1: (i, i % 5 == 0)
                              for i in range(CAP_A)},
                             {0: (40, False), 50: (41, True),
                              3 * CAP_A + 7: (42, False)}, CAP_OUT),
    "extreme_keys_in_batch": ({2**40 + i: (i, False) for i in range(8)},
                              {0: (1, False), _TOP: (2, False),
                               2**40 + 3: (3, True)}, CAP_OUT),
    "extreme_keys_in_overlay": ({0: (1, False), 7: (2, True),
                                 _TOP: (3, False)},
                                {0: (4, True), 5: (5, False),
                                 _TOP - 1: (6, False), _TOP: (7, True)},
                                CAP_OUT),
    "tombstones_over_tombstones": ({k: (0, True) for k in _EVENS},
                                   {k: (0, True) for k in
                                    list(_EVENS)[::2] + [99, 1_000]},
                                   CAP_OUT),
    # capacity growth: cap_out past the two inputs' slots together
    "growth": (_EVENS, {2 * i + 101: (600 + i, i % 4 == 0)
                        for i in range(CAP_B)}, 2 * CAP_OUT),
}


def assert_all_merge_paths(a: dict, b: dict, cap_out=CAP_OUT,
                           cap_a=CAP_A, cap_b=CAP_B):
    """jnp merge, Pallas kernel (interpret), and vmapped reference all
    reproduce the dict repack bit for bit."""
    want = dict_pack({**a, **b}, cap_out)
    pa = dict_pack(a, cap_a)
    pb = dict_pack(b, cap_b)
    got_jnp = merge_overlay_pack_jnp(jnp.asarray(pa), jnp.asarray(pb),
                                     cap_out)
    np.testing.assert_array_equal(np.asarray(got_jnp), want)
    got_k = overlay_merge_pack(pa, pb, cap_out, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), want)
    got_r = overlay_merge_pack(pa, pb, cap_out, interpret=True, use_ref=True)
    np.testing.assert_array_equal(np.asarray(got_r), want)


class TestMergeParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_randomized_vs_dict_repack(self, seed):
        rng = np.random.default_rng(seed)
        a = rand_dict(rng, int(rng.integers(0, CAP_A)))
        b = rand_dict(rng, int(rng.integers(0, CAP_B // 2)),
                      overlap_keys=list(a))
        while len(b) > CAP_B:
            b.pop(next(iter(b)))
        assert_all_merge_paths(a, b)

    def test_empty_sides(self):
        rng = np.random.default_rng(9)
        full = rand_dict(rng, 10)
        assert_all_merge_paths({}, full)
        assert_all_merge_paths(full, {})
        assert_all_merge_paths({}, {})

    def test_all_overlap_batch_wins(self):
        """Every batch key collides: payloads and tombstone flips must all
        come from the batch (last-writer-wins upsert + tombstone replay)."""
        a = {k: (k + 1, False) for k in range(10, 26)}
        b = {k: (k + 500, k % 3 == 0) for k in range(10, 26)}
        assert_all_merge_paths(a, b)

    @pytest.mark.parametrize("case", sorted(RANK_CASES))
    def test_rank_arithmetic_cases(self, case):
        """Survivors land at their slot plus the new batch keys below it:
        the placements where that count is all, none, or alternates, and
        the ends of the key space, of the window and of the capacity."""
        a, b, cap_out = RANK_CASES[case]
        assert_all_merge_paths(a, b, cap_out=cap_out)

    def test_cap_growth_and_identity_cap(self):
        a = {k: (k, False) for k in range(0, 60, 2)}
        b = {k: (k, True) for k in range(1, 31, 2)}
        assert_all_merge_paths(a, b, cap_out=64)
        assert_all_merge_paths(a, b, cap_out=128, cap_a=64, cap_b=16)

    def test_stacked_rows_merge_independently(self):
        rng = np.random.default_rng(4)
        ds = [(rand_dict(rng, 12), rand_dict(rng, 6)) for _ in range(3)]
        packs = np.stack([dict_pack(a, CAP_A) for a, _ in ds])
        batches = np.stack([dict_pack(b, CAP_B) for _, b in ds])
        got = overlay_merge_pack_stacked(packs, batches, CAP_OUT,
                                         interpret=True)
        want = np.stack([dict_pack({**a, **b}, CAP_OUT) for a, b in ds])
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_empty_overlay_pack_is_all_padding(self):
        p = np.asarray(empty_overlay_pack(32))
        assert p.shape == (3, 32) and p.dtype == np.uint64
        assert (p[0] == UM).all() and (p[1] == 0).all() and (p[2] == 0).all()

    @given(a=st.lists(st.tuples(st.integers(0, 2**50),
                                st.integers(0, 2**40), st.booleans()),
                      max_size=CAP_A),
           b=st.lists(st.tuples(st.integers(0, 2**50),
                                st.integers(0, 2**40), st.booleans()),
                      max_size=CAP_B))
    @settings(max_examples=40, deadline=None)
    def test_property_merge_is_dict_union(self, a, b):
        """∀ overlay, batch: device merge == sorted repack of the dict
        union with batch-wins semantics (duplicate list entries collapse
        last-wins, exactly like repeated dict writes)."""
        da = {k: (p, t) for k, p, t in a}
        db = {k: (p, t) for k, p, t in b}
        assert_all_merge_paths(da, db)


# the windowed merge's pack: capacity well above the windows it merges into
WIN_CAP = 128


def _keys_of(base, n, step=2):
    return {base + step * i: (base + i, i % 4 == 0) for i in range(n)}


# (pack dict, batch dict, fill bound the caller passes, window it yields)
WINDOW_CASES = {
    # 10 live + 6 (2 colliding): bound 16, far below the capacity
    "well_below": (_keys_of(100, 10), {**_keys_of(101, 4),
                   100: (7, True), 104: (9, False)}, 16, 16),
    # 24 live + 8 new keys: the merged fill is exactly the window
    "edge": (_keys_of(0, 24), _keys_of(1001, 8), 32, 32),
    # every batch key collides, tombstones flip both ways
    "all_collide": (_keys_of(0, 20),
                    {2 * i: (500 + i, i % 3 == 0) for i in range(20)},
                    40, 64),
    # 30 live (window bucket 32) pushed to 40 by the batch: next bucket
    "bucket_up": (_keys_of(0, 30), _keys_of(2001, 10), 40, 64),
    # the bound reaches the capacity: the whole-pack merge
    "at_capacity": (_keys_of(0, 90), _keys_of(3001, 16), 106, WIN_CAP),
    # the bound passes the capacity: the pack grows, the whole-pack merge
    "growth": (_keys_of(0, 120), _keys_of(4001, 20), 140, 2 * WIN_CAP),
}


class TestWindowedMerge:
    """The delta merge over the live prefix ``[0, w)`` of the pack is bit
    for bit today's whole-capacity merge, and leaves ``[w, cap)`` padding."""

    @pytest.mark.parametrize("backend", ["jnp", "fused_interpret"])
    @pytest.mark.parametrize("case", sorted(WINDOW_CASES))
    def test_window_equals_full_merge(self, backend, case):
        a, b, bound, want_w = WINDOW_CASES[case]
        fn = overlay_merge_backend_fn(backend)
        pack = dict_pack(a, WIN_CAP)
        cap_out = max(WIN_CAP, next_pow2(bound))
        full = np.asarray(fn(jnp.asarray(pack),
                             jnp.asarray(dict_pack(b, next_pow2(len(b)))),
                             cap_out))
        np.testing.assert_array_equal(full, dict_pack({**a, **b}, cap_out))
        batch = tuple(np.asarray(x) for x in dict_pack(b, len(b)))
        ovr, nbytes, w = merge_overlay_pack(
            {"ov_pack": jnp.asarray(pack)}, (batch[0], batch[1],
                                             batch[2].astype(bool)),
            bound, merge_fn=fn)
        got = np.asarray(ovr["ov_pack"])
        assert w == want_w and nbytes == 3 * 8 * next_pow2(max(len(b), 8))
        np.testing.assert_array_equal(got, full)
        assert (got[0, w:] == UM).all() and not got[1:, w:].any()
        assert (got[0, :w] != UM).sum() == len({**a, **b})

    def test_merge_window_buckets(self):
        assert merge_window(0, 8, 1 << 24) == 8
        assert merge_window(5_000, 8192, 1 << 24) == 8192
        assert merge_window(40_000, 8192, 1 << 24) == 65536
        assert merge_window(65536, 8192, 1 << 24) == 65536
        assert merge_window(20_000_000, 8192, 1 << 24) == 1 << 24


class TestDeltaOverlayBatching:
    def test_take_batch_is_sorted_and_drains(self):
        ov = DeltaOverlay()
        ov.record_insert(7, 70)
        ov.record_delete(3)
        ov.record_insert(5, 50)
        ov.record_insert(7, 71)       # upsert folds in-place
        assert ov.pending_writes == 3
        bk, bp, bt = ov.take_batch()
        np.testing.assert_array_equal(bk, np.array([3, 5, 7], np.uint64))
        np.testing.assert_array_equal(bp, np.array([0, 50, 71], np.uint64))
        np.testing.assert_array_equal(bt, np.array([True, False, False]))
        assert ov.pending_writes == 0
        assert ov.take_batch()[0].size == 0

    def test_incremental_arrays_match_full_rebuild(self):
        """The searchsorted-insert mirror serves ``arrays()`` identically to
        an overlay rebuilt from scratch after every batch."""
        rng = np.random.default_rng(2)
        ov = DeltaOverlay()
        for step in range(12):
            for _ in range(rng.integers(1, 9)):
                k = int(rng.integers(0, 40))
                if rng.random() < 0.3:
                    ov.record_delete(k)
                else:
                    ov.record_insert(k, int(rng.integers(0, 1000)))
            fresh = DeltaOverlay()
            fresh.merge_under(ov)     # same map, mirror rebuilt from scratch
            got, want = ov.arrays(), fresh.arrays()
            for f in ("ov_keys", "ov_pay", "ov_tomb"):
                np.testing.assert_array_equal(got[f], want[f])

    @given(ops=st.lists(st.tuples(st.booleans(), st.integers(0, 30),
                                  st.integers(0, 999)),
                        max_size=60))
    @settings(max_examples=40, deadline=None)
    def test_property_mirror_matches_dict(self, ops):
        ov = DeltaOverlay()
        d = {}
        for ins, k, p in ops:
            if ins:
                ov.record_insert(k, p)
                d[k] = (p, 0)
            else:
                ov.record_delete(k)
                d[k] = (0, 1)
        arrs = ov.arrays()
        cap = arrs["ov_keys"].size
        assert cap >= next_pow2(max(len(d), 1))
        want = dict_pack(d, cap)
        np.testing.assert_array_equal(arrs["ov_keys"], want[0])
        np.testing.assert_array_equal(arrs["ov_pay"], want[1])
        np.testing.assert_array_equal(arrs["ov_tomb"].astype(np.uint64),
                                      want[2])

    def test_clear_is_structurally_fresh(self):
        """A cleared overlay must not look like the overlay whose entries
        are already on device — pack validity is keyed on uid."""
        ov = DeltaOverlay()
        ov.record_insert(1, 1)
        uid = ov.uid
        ov.clear()
        assert ov.uid != uid and ov.pending_writes == 0
        assert ov.arrays()["ov_keys"][0] == UM

    def test_mark_synced_discards_pending_only(self):
        ov = DeltaOverlay()
        ov.record_insert(1, 10)
        ov.mark_synced()
        ov.record_insert(2, 20)
        bk, bp, _ = ov.take_batch()
        np.testing.assert_array_equal(bk, np.array([2], np.uint64))
        # the mirror still serves the full map
        np.testing.assert_array_equal(ov.arrays()["ov_keys"][:2],
                                      np.array([1, 2], np.uint64))


def one_shard(keys, **kw):
    """The engine over a single index (a one-shard partition); it compacts
    synchronously unless ``kw`` says otherwise."""
    part = partition_bulkload(keys, payloads_for(keys), 1,
                              cfg=AulidConfig(**SMALL_GEOM))
    return ShardedIndexEngine(part, **{"async_compact": False, **kw})


def twin_engines(n=1_200, backend="jnp", **kw):
    keys = make_dataset("covid", n, seed=1)
    eng = one_shard(keys, backend=backend, overlay_merge=True, **kw)
    base = one_shard(keys, backend=backend, overlay_merge=False, **kw)
    return keys, eng, base


class TestEngineWritePath:
    @pytest.mark.parametrize("backend", ["jnp", "fused_interpret"])
    def test_mixed_stream_equivalence(self, backend):
        """Delta-merge engine == full-repack twin == dict oracle on a mixed
        write/read stream, and it ships strictly fewer H2D bytes."""
        keys, eng, base = twin_engines(backend=backend, gamma=0.05)
        oracle = {int(k): int(k) + 1 for k in keys}
        rng = np.random.default_rng(5)
        for step in range(10):
            checks = []
            for i in range(10):
                k = (int(rng.integers(0, 2**50)) if rng.random() < 0.7
                     else int(rng.choice(keys)))
                eng.insert(k, step * 100 + i)
                base.insert(k, step * 100 + i)
                oracle[k] = step * 100 + i
            for _ in range(3):
                k = int(rng.choice(sorted(oracle)))
                eng.delete(k)
                base.delete(k)
                oracle.pop(k, None)
            for _ in range(12):
                k = (int(rng.choice(sorted(oracle))) if rng.random() < 0.6
                     else int(rng.integers(0, 2**50)))
                checks.append((k, eng.get(k), base.get(k)))
            eng.step()
            base.step()
            for k, a, b in checks:
                assert a.result == b.result == oracle.get(k), (step, k)
        s, sb = eng.stats(), base.stats()
        assert s["overlay_merges"] > 0
        assert sb["overlay_merges"] == 0 and sb["overlay_reseeds"] > 0
        assert s["write_h2d_bytes"] < sb["write_h2d_bytes"]
        eng.shards[0].idx.check_invariants()

    def test_mid_stream_swap_parity(self, monkeypatch):
        """The delta path hands off across freeze -> build -> swap: while a
        compaction is parked in the hand-pumped pool the pack serves
        frozen ∪ live, and the post-swap reseed starts a new delta run."""
        pool = ManualExecutor()
        monkeypatch.setattr(ie_mod, "_COMPACT_POOL", pool)
        keys, eng, base = twin_engines(gamma=0.01,   # freeze early
                                       async_compact=True)
        oracle = {int(k): int(k) + 1 for k in keys}
        rng = np.random.default_rng(8)
        for step in range(8):
            checks = []
            for i in range(12):
                k = int(rng.integers(0, 2**50))
                eng.insert(k, step * 50 + i)
                base.insert(k, step * 50 + i)
                oracle[k] = step * 50 + i
            for _ in range(12):
                k = (int(rng.choice(sorted(oracle))) if rng.random() < 0.5
                     else int(rng.integers(0, 2**50)))
                checks.append((k, eng.get(k), base.get(k)))
            eng.step()
            base.step()
            if step % 2 == 1:        # swap lands two steps after the freeze
                pool.pump()
            for k, a, b in checks:
                assert a.result == b.result == oracle.get(k), (step, k)
        assert eng.stats()["compactions"] >= 1
        assert eng.stats()["overlay_merges"] > 0

    @given(backend=st.sampled_from(["jnp", "fused_interpret"]),
           ops=st.lists(st.tuples(st.sampled_from("iidg"),
                                  st.integers(0, 2**50 - 1),
                                  st.integers(0, 999)),
                        min_size=12, max_size=48))
    @settings(max_examples=5, deadline=None)
    def test_property_stream_vs_dict_oracle(self, backend, ops):
        """∀ interleavings of insert/delete/get (duplicates, upserts,
        deletes of absent keys): the device-merged overlay read path
        answers exactly like the host dict, across the compaction swaps a
        tiny gamma forces mid-stream."""
        keys = make_dataset("covid", 600, seed=1)
        eng = one_shard(keys, backend=backend, gamma=0.02,
                        overlay_merge=True)
        oracle = {int(k): int(k) + 1 for k in keys}
        checks, step_gets = [], []
        for j, (op, k, p) in enumerate(ops):
            if op == "i":
                eng.insert(k, p)
                oracle[k] = p
            elif op == "d":
                eng.delete(k)
                oracle.pop(k, None)
            else:
                step_gets.append((k, eng.get(k)))
            if (j + 1) % 8 == 0 or j == len(ops) - 1:
                eng.step()
                # a get sees every write of its own step, none of later ones
                checks += [(k, r, oracle.get(k)) for k, r in step_gets]
                step_gets = []
        for k, r, want in checks:
            assert r.done and r.result == want, k
        eng.shards[0].idx.check_invariants()


class TestShardedWritePath:
    def _twins(self, n=1_200, **kw):
        keys = make_dataset("covid", n, seed=1)
        pay = payloads_for(keys)

        def one(merge):
            part = partition_bulkload(keys, pay, 3,
                                      cfg=AulidConfig(**SMALL_GEOM))
            return ShardedIndexEngine(part, gamma=0.05, backend="jnp",
                                      overlay_merge=merge, **kw)
        return keys, one(True), one(False)

    def test_mixed_stream_equivalence(self):
        keys, eng, base = self._twins()
        rng = np.random.default_rng(3)
        for step in range(8):
            pairs = []
            for i in range(12):
                k = (int(rng.integers(0, 2**50)) if rng.random() < 0.7
                     else int(rng.choice(keys)))
                pairs.append((eng.insert(k, step * 100 + i),
                              base.insert(k, step * 100 + i)))
            for _ in range(3):
                k = int(rng.choice(keys))
                pairs.append((eng.delete(k), base.delete(k)))
            for _ in range(14):
                k = (int(rng.choice(keys)) if rng.random() < 0.5
                     else int(rng.integers(0, 2**50)))
                pairs.append((eng.get(k), base.get(k)))
            eng.step()
            base.step()
            for a, b in pairs:
                assert a.done and b.done
                assert a.result == b.result, (a.op, a.key)
        s, sb = eng.stats(), base.stats()
        assert s["overlay_merges"] > 0 and sb["overlay_merges"] == 0
        assert s["write_h2d_bytes"] < sb["write_h2d_bytes"]

    def test_online_split_parity(self, monkeypatch):
        """The delta path survives an online split: repartition swaps both
        shards' uids, forcing a reseed, and the stream stays equivalent to
        the full-repack twin throughout."""
        pool = ManualExecutor()
        monkeypatch.setattr(ie_mod, "_COMPACT_POOL", pool)
        keys, eng, base = self._twins(n=600, repartition=True,
                                      split_ratio=1e9, min_split_items=16)
        rng = np.random.default_rng(6)
        for step in range(6):
            pairs = []
            for i in range(10):
                k = int(rng.integers(0, 2**50))
                pairs.append((eng.insert(k, step * 10 + i),
                              base.insert(k, step * 10 + i)))
            for _ in range(10):
                k = (int(rng.choice(keys)) if rng.random() < 0.5
                     else int(rng.integers(0, 2**50)))
                pairs.append((eng.get(k), base.get(k)))
            eng.step()
            base.step()
            pool.pump()
            for a, b in pairs:
                assert a.result == b.result, (a.op, a.key)
            if step == 2:
                sizes = [sh.idx.n_items for sh in eng.shards]
                hot = max(range(len(sizes)), key=sizes.__getitem__)
                assert eng.request_split(hot)
                base.request_split(hot)
        pool.pump()
        eng.drain_compactions()
        base.drain_compactions()
        pairs = [(eng.get(int(k)), base.get(int(k))) for k in keys[::5]]
        eng.step()
        base.step()
        for a, b in pairs:
            assert a.result == b.result, a.key
        assert eng.stats()["num_shards"] > 3
        assert eng.stats()["overlay_merges"] > 0

    @pytest.mark.parametrize("engine", ["one_shard", "three_shards"])
    def test_merge_window_below_capacity(self, engine):
        """Write steps far below the compaction threshold: every answer
        matches the dict oracle, every step merges on device, and the mean
        merge window stays below the pack's capacity."""
        keys = make_dataset("covid", 1_200, seed=1)
        num_shards = {"one_shard": 1, "three_shards": 3}[engine]
        part = partition_bulkload(keys, payloads_for(keys), num_shards,
                                  cfg=AulidConfig(**SMALL_GEOM))
        eng = ShardedIndexEngine(part, gamma=0.5, backend="jnp",
                                 overlay_merge=True)
        oracle = {int(k): int(k) + 1 for k in keys}
        rng = np.random.default_rng(11)
        for step in range(8):
            reqs = []
            for i in range(12):
                k = (int(rng.integers(0, 2**50)) if rng.random() < 0.6
                     else int(rng.choice(keys)))
                eng.insert(k, step * 100 + i)
                oracle[k] = step * 100 + i
            for _ in range(3):
                k = int(rng.choice(keys))
                eng.delete(k)
                oracle.pop(k, None)
            for _ in range(14):
                k = (int(rng.choice(keys)) if rng.random() < 0.7
                     else int(rng.integers(0, 2**50)))
                reqs.append((k, eng.get(k)))
            eng.step()
            for k, r in reqs:
                assert r.done and r.result == oracle.get(k), (step, k)
        s = eng.stats()
        cap = int(eng.ov_arrs["ov_pack"].shape[1])
        assert s["compactions"] == 0 and s["overlay_merges"] == 8
        assert s["overlay_merge_slots"] / s["overlay_merges"] < cap

    def test_backend_fn_resolution(self):
        fn = overlay_merge_backend_fn("jnp")
        assert fn is merge_overlay_pack_jnp
        fn = overlay_merge_backend_fn("fused_interpret")
        a = dict_pack({1: (10, 0), 5: (50, 1)}, 8)
        b = dict_pack({3: (30, 0)}, 8)
        got = np.asarray(fn(jnp.asarray(a), jnp.asarray(b), 16))
        np.testing.assert_array_equal(
            got, dict_pack({1: (10, 0), 3: (30, 0), 5: (50, 1)}, 16))


class TestMeshWriteMerge:
    def test_wmerge_driver(self, device_count):
        """Mesh engine vs single-device full-repack oracle on a write-heavy
        stream + shard_map stacked-merge kernel parity (subprocess, 8
        forced devices)."""
        out = device_count(8, "mesh_equiv_driver.py", "wmerge", "4")
        assert "ALL OK" in out
