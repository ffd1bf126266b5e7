"""Delta overlay + incremental mirror refresh + mixed serving engine.

The update oracle: arbitrary interleavings of insert/delete/lookup/scan
through the overlay-merged device read path must match a host-side ``Aulid``
queried directly (the host index is the paper's ground truth; the frozen
snapshot + overlay is our device-side reconstruction of it).
"""
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import (Aulid, AulidConfig, BlockDevice, DeltaOverlay,
                        RangePartition)
from repro.core.device_index import build_device_index, refresh_device_index
from repro.core.lookup import (device_arrays, lookup_batch_overlay,
                               overlay_arrays, scan_batch_overlay)
from repro.core.workloads import make_dataset, payloads_for
from repro.serving import ShardedIndexEngine

import jax.numpy as jnp

DATASET_NAMES = ("covid", "planet", "genome", "osm")
SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)


def small_build(keys):
    idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
    idx.bulkload(keys, payloads_for(keys))
    return idx


# Pristine per-dataset mirrors, shared across examples: ops only touch the
# host copy + overlay, never the frozen snapshot — so one jit trace per shape.
_MIRROR_CACHE: dict[str, tuple] = {}


def pristine_mirror(name: str, n: int = 2_500):
    if name not in _MIRROR_CACHE:
        keys = make_dataset(name, n, seed=1)
        idx = small_build(keys)
        di = build_device_index(idx)
        _MIRROR_CACHE[name] = (keys, di, device_arrays(di),
                               max(di.max_inner_height, 3))
    return _MIRROR_CACHE[name]


def apply_ops(idx: Aulid, ov: DeltaOverlay, ops):
    """Upsert/delete interleaving applied to host + overlay (engine twin)."""
    touched = []
    for kind, key in ops:
        if kind == 0:
            if not idx.update(key, key + 9):
                idx.insert(key, key + 9)
            ov.record_insert(key, key + 9)
        else:
            idx.delete(key)
            ov.record_delete(key)
        touched.append(key)
    return touched


def assert_device_matches_host(idx, arrs, ovr, height, queries, scan_starts,
                               scan_count=10):
    q = np.asarray(queries, dtype=np.uint64)
    pay, found, _ = lookup_batch_overlay(arrs, ovr, jnp.asarray(q),
                                         height=height)
    pay, found = np.asarray(pay), np.asarray(found)
    for i, k in enumerate(q):
        exp = idx.lookup(int(k))
        assert (exp is None) == (not found[i]), int(k)
        if exp is not None:
            assert int(pay[i]) == exp, int(k)
    s = np.asarray(scan_starts, dtype=np.uint64)
    ks, ps, valid = scan_batch_overlay(arrs, ovr, jnp.asarray(s),
                                       count=scan_count, height=height)
    ks, ps, valid = map(np.asarray, (ks, ps, valid))
    for i, start in enumerate(s):
        exp = idx.scan(int(start), scan_count)
        n = int(valid[i].sum())
        got = list(zip(ks[i][:n].tolist(), ps[i][:n].tolist()))
        assert got == exp, int(start)


class TestOverlayOracle:
    @pytest.mark.parametrize("name", DATASET_NAMES)
    def test_seeded_interleaving_all_datasets(self, name):
        """Deterministic randomized oracle run (works without hypothesis)."""
        keys, di, arrs, height = pristine_mirror(name)
        idx = small_build(keys)
        ov = DeltaOverlay()
        rng = np.random.default_rng(hash(name) % 2**32)
        ops = []
        for _ in range(200):
            if rng.random() < 0.6:
                ops.append((0, int(rng.integers(0, 2**50))
                            if rng.random() < 0.5 else int(rng.choice(keys))))
            else:
                ops.append((1, int(rng.choice(keys))
                            if rng.random() < 0.7
                            else int(rng.integers(0, 2**50))))
        touched = apply_ops(idx, ov, ops)
        ovr = overlay_arrays(ov)
        misses = rng.integers(0, 2**50, 64)
        queries = np.concatenate([np.array(touched, dtype=np.uint64),
                                  rng.choice(keys, 64).astype(np.uint64),
                                  misses.astype(np.uint64)])
        starts = np.array(touched[:6] + [int(keys[0]), int(keys[-1])],
                          dtype=np.uint64)
        assert_device_matches_host(idx, arrs, ovr, height, queries, starts)

    def test_tombstone_hides_snapshot_key(self):
        keys, di, arrs, height = pristine_mirror("covid")
        idx = small_build(keys)
        ov = DeltaOverlay()
        dead = int(keys[37])
        idx.delete(dead)
        ov.record_delete(dead)
        pay, found, _ = lookup_batch_overlay(
            arrs, overlay_arrays(ov),
            jnp.asarray(np.array([dead, int(keys[38])], dtype=np.uint64)),
            height=height)
        assert not bool(np.asarray(found)[0])
        assert bool(np.asarray(found)[1])

    def test_overlay_update_wins_over_snapshot(self):
        keys, di, arrs, height = pristine_mirror("covid")
        idx = small_build(keys)
        ov = DeltaOverlay()
        k = int(keys[11])
        assert idx.update(k, 424242)
        ov.record_update(k, 424242)
        pay, found, _ = lookup_batch_overlay(
            arrs, overlay_arrays(ov),
            jnp.asarray(np.array([k], dtype=np.uint64)), height=height)
        assert bool(np.asarray(found)[0])
        assert int(np.asarray(pay)[0]) == 424242

    def test_reinsert_after_tombstone(self):
        keys, di, arrs, height = pristine_mirror("covid")
        idx = small_build(keys)
        ov = DeltaOverlay()
        k = int(keys[5])
        idx.delete(k)
        ov.record_delete(k)
        idx.insert(k, 777)
        ov.record_insert(k, 777)
        pay, found, _ = lookup_batch_overlay(
            arrs, overlay_arrays(ov),
            jnp.asarray(np.array([k], dtype=np.uint64)), height=height)
        assert bool(np.asarray(found)[0]) and int(np.asarray(pay)[0]) == 777


@given(st.lists(st.tuples(st.integers(0, 1), st.integers(0, 2**48)),
                min_size=1, max_size=40),
       st.integers(0, 3))
@settings(max_examples=40, deadline=None)
def test_overlay_vs_aulid_oracle_property(ops, name_idx):
    """Property: overlay-merged device reads == host AULID under arbitrary
    upsert/delete interleavings, across all four datasets."""
    name = DATASET_NAMES[name_idx]
    keys, di, arrs, height = pristine_mirror(name)
    idx = small_build(keys)
    ov = DeltaOverlay()
    # mix in real dataset keys so deletes/updates of snapshot keys happen
    ops = [(kind, int(keys[key % len(keys)]) if key % 3 == 0 else key)
           for kind, key in ops]
    touched = apply_ops(idx, ov, ops)
    pad = 64 - len(touched)
    queries = np.array(touched + [touched[0]] * pad, dtype=np.uint64)
    starts = np.array((touched + [int(keys[0])] * 8)[:8], dtype=np.uint64)
    assert_device_matches_host(idx, arrs, overlay_arrays(ov), height,
                               queries, starts)


class TestRefresh:
    def test_fast_path_bit_identical(self):
        """Journal fast path == full rebuild, array for array."""
        keys = make_dataset("genome", 6_000, seed=1)
        idx = small_build(keys)
        di = build_device_index(idx)
        rng = np.random.default_rng(3)
        for k in rng.choice(keys, 300, replace=False):
            assert idx.update(int(k), int(k) + 123)
        # deletes that keep every leaf non-empty (no SMO)
        for k in keys[10:40:3]:
            assert idx.delete(int(k))
        di = refresh_device_index(idx, di)
        assert di.refreshes == 1 and di.full_builds == 1
        fresh = build_device_index(idx)
        for f in ("slot_tag", "slot_key", "slot_ptr", "next_occ", "succ_slot",
                  "node_base", "node_fanout", "node_slope", "node_intercept",
                  "node_overflow_slot", "pa_keys", "pa_ptrs", "bt_keys",
                  "bt_ptrs", "leaf_keys", "leaf_pay", "leaf_count",
                  "leaf_next"):
            assert np.array_equal(getattr(di, f), getattr(fresh, f)), f
        assert di.last_leaf_min == fresh.last_leaf_min
        assert di.root_node == fresh.root_node
        assert di.last_leaf_row == fresh.last_leaf_row

    def test_smo_falls_back_to_full_build(self):
        keys = make_dataset("covid", 3_000, seed=1)
        idx = small_build(keys)
        di = build_device_index(idx)
        splits_before = idx.smo_leaf_splits
        rng = np.random.default_rng(4)
        for k in rng.integers(0, 2**50, 400):  # forces leaf splits
            idx.insert(int(k), 1)
        assert idx.smo_leaf_splits > splits_before
        di = refresh_device_index(idx, di)
        assert di.full_builds == 2 and di.refreshes == 0
        # and the rebuilt mirror serves the new keys
        arrs = device_arrays(di)
        from repro.core.lookup import lookup_batch
        q = np.unique(rng.integers(0, 2**50, 400))[:64].astype(np.uint64)
        pay, found, _ = lookup_batch(arrs, jnp.asarray(q),
                                     height=max(di.max_inner_height, 3))
        for i, k in enumerate(np.asarray(q)):
            exp = idx.lookup(int(k))
            assert (exp is None) == (not bool(np.asarray(found)[i]))

    def test_refresh_epoch_advances_and_truncates(self):
        keys = make_dataset("covid", 2_000, seed=1)
        idx = small_build(keys)
        di = build_device_index(idx)
        e0 = di.journal_epoch
        idx.update(int(keys[0]), 5)
        di = refresh_device_index(idx, di)
        assert di.journal_epoch == e0 + 1 == idx.journal_end
        assert len(idx.journal) == 0, "consumed prefix must be truncated"
        # idempotent: nothing new to fold
        di2 = refresh_device_index(idx, di)
        assert di2.refreshes == di.refreshes == 1

    def test_noop_refresh_records_empty_touched_rows(self):
        """Refresh with nothing journaled is a no-op: same mirror object,
        ``last_touched_rows`` empty (consumers patch zero device rows)."""
        keys = make_dataset("covid", 1_000, seed=1)
        idx = small_build(keys)
        di = build_device_index(idx)
        di2 = refresh_device_index(idx, di)
        assert di2 is di
        assert di2.last_touched_rows is not None
        assert len(di2.last_touched_rows) == 0
        assert di2.refreshes == 0 and di2.full_builds == 1

    def test_truncated_journal_under_older_mirror_full_builds(self):
        """journal_epoch < journal_base (entries truncated away beneath this
        mirror) must force a full build, never a silent skip."""
        keys = make_dataset("covid", 1_000, seed=1)
        idx = small_build(keys)
        di_old = build_device_index(idx)
        di_other = build_device_index(idx)
        idx.update(int(keys[0]), 1)
        # the other mirror consumes and truncates the journal prefix
        refresh_device_index(idx, di_other)
        assert idx.journal_base > di_old.journal_epoch, "precondition"
        idx.update(int(keys[1]), 2)
        di_old = refresh_device_index(idx, di_old)
        assert di_old.full_builds == 2 and di_old.refreshes == 0

    def test_second_mirror_not_stranded_by_truncation(self):
        """A mirror snapshotted before another mirror consumed (and
        truncated) the journal must full-rebuild, not skip those writes."""
        keys = make_dataset("covid", 2_000, seed=1)
        idx = small_build(keys)
        di_a = build_device_index(idx)
        di_b = build_device_index(idx)
        idx.update(int(keys[0]), 111)
        di_a = refresh_device_index(idx, di_a)      # consumes + truncates
        idx.update(int(keys[1]), 222)
        di_b = refresh_device_index(idx, di_b)
        assert di_b.full_builds == 2, "must detect truncated-away entries"
        assert di_a.refreshes == 1
        arrs = device_arrays(di_b)
        from repro.core.lookup import lookup_batch
        q = np.array([int(keys[0]), int(keys[1])], dtype=np.uint64)
        pay, found, _ = lookup_batch(arrs, jnp.asarray(q),
                                     height=max(di_b.max_inner_height, 3))
        assert bool(np.asarray(found).all())
        assert np.asarray(pay).tolist() == [111, 222]


class TestEmptyMirror:
    """Empty-index mirrors (ISSUE 5 satellite): ``build_device_index`` on an
    empty index produces an all-padding leaf pool with ``last_row == L - 1``,
    and ``refresh_device_index`` survives the empty -> nonempty transition."""

    def _assert_serves_nothing(self, di):
        arrs = device_arrays(di)
        from repro.core.lookup import lookup_batch, scan_batch
        q = jnp.asarray(np.array([0, 5, 2**50], dtype=np.uint64))
        pay, found, leaf = lookup_batch(arrs, q, height=3)
        assert not bool(np.asarray(found).any())
        ks, ps, valid = scan_batch(arrs, q, count=8, height=3)
        assert not bool(np.asarray(valid).any())

    def test_never_bulkloaded(self):
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        di = build_device_index(idx)
        L = di.leaf_keys.shape[0]
        assert L == 1 and di.last_leaf_row == L - 1
        assert (di.leaf_keys == np.uint64(0xFFFFFFFFFFFFFFFF)).all()
        assert int(di.leaf_count.sum()) == 0 and di.root_node == -1
        self._assert_serves_nothing(di)
        # empty -> nonempty: first insert changes the leaf set (SMO
        # fingerprint), so the refresh full-builds rather than asserting
        idx.insert(42, 7)
        di = refresh_device_index(idx, di)
        assert di.full_builds == 2
        assert idx.lookup(42) == 7
        arrs = device_arrays(di)
        from repro.core.lookup import lookup_batch
        pay, found, _ = lookup_batch(
            arrs, jnp.asarray(np.array([42, 43], dtype=np.uint64)), height=3)
        assert bool(np.asarray(found)[0]) and int(np.asarray(pay)[0]) == 7
        assert not bool(np.asarray(found)[1])

    def test_bulkloaded_empty_takes_fast_path(self):
        """bulkload([]) leaves one empty leaf; the first insert is content-
        only (no SMO), so the refresh may take the journal fast path."""
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(np.empty(0, dtype=np.uint64),
                     np.empty(0, dtype=np.uint64))
        di = build_device_index(idx)
        assert len(di.leaf_rows) == 1 and int(di.leaf_count.sum()) == 0
        self._assert_serves_nothing(di)
        idx.insert(42, 7)
        di = refresh_device_index(idx, di)
        assert di.refreshes == 1 and di.full_builds == 1
        arrs = device_arrays(di)
        from repro.core.lookup import lookup_batch
        pay, found, _ = lookup_batch(
            arrs, jnp.asarray(np.array([42], dtype=np.uint64)), height=3)
        assert bool(np.asarray(found)[0]) and int(np.asarray(pay)[0]) == 7

    def test_refresh_noop_on_empty(self):
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        di = build_device_index(idx)
        di2 = refresh_device_index(idx, di)
        assert di2 is di and len(di2.last_touched_rows) == 0


class TestIndexEngine:
    def _mk(self, n=3_000, **kw):
        """The engine over one host index (a one-shard partition),
        compacting synchronously."""
        keys = make_dataset("covid", n, seed=1)
        self.idx = small_build(keys)
        part = RangePartition(np.empty(0, dtype=np.uint64), [self.idx])
        return keys, ShardedIndexEngine(part, async_compact=False, **kw)

    def test_mixed_interleaving_vs_dict_oracle(self):
        keys, eng = self._mk(gamma=0.02)
        oracle = {int(k): int(k) + 1 for k in keys}
        rng = np.random.default_rng(9)
        pending = []
        for i in range(1200):
            r = rng.random()
            if r < 0.45:
                k = (int(rng.choice(keys)) if rng.random() < 0.6
                     else int(rng.integers(0, 2**50)))
                pending.append(("get", eng.get(k), k))
            elif r < 0.7:
                k, p = int(rng.integers(0, 2**50)), i
                eng.insert(k, p)
                oracle[k] = p
            elif r < 0.85:
                k = int(rng.choice(sorted(oracle))) if rng.random() < 0.5 \
                    else int(rng.integers(0, 2**50))
                eng.delete(k)
                oracle.pop(k, None)
            else:
                pending.append(("scan", eng.scan(int(rng.choice(keys)), 15),
                                None))
            if (i + 1) % 300 == 0:
                eng.step()
                import bisect
                srt = sorted(oracle)
                for kind, req, k in pending:
                    assert req.done
                    if kind == "get":
                        assert req.result == oracle.get(k), k
                    else:
                        j = bisect.bisect_left(srt, req.key)
                        assert req.result == [(kk, oracle[kk])
                                              for kk in srt[j: j + 15]]
                pending = []
        eng.run()
        stats = eng.stats()
        assert stats["compactions"] >= 1, "gamma policy never fired"
        assert stats["writes_applied"] > 0 and stats["reads_served"] > 0
        self.idx.check_invariants()

    def test_step_level_consistency(self):
        """A get queued before a write in the same batch still sees it."""
        keys, eng = self._mk(n=1_000)
        k = int(keys[3])
        r1 = eng.get(k)
        eng.insert(k, 999)       # upsert queued after the get, same step
        r2 = eng.get(k)
        eng.step()
        assert r1.result == 999 and r2.result == 999

    def test_compaction_resets_overlay_and_serves(self):
        keys, eng = self._mk(n=1_000, gamma=0.001)  # compact on every write
        k = int(keys[10])
        eng.delete(k)
        eng.get(k)
        eng.step()
        assert len(eng.shards[0].overlay) == 0 and eng.compactions >= 1
        r = eng.get(k)
        eng.step()
        assert r.result is None
        r2 = eng.get(int(keys[11]))
        eng.step()
        assert r2.result == int(keys[11]) + 1

    def test_scan_sees_step_writes(self):
        keys, eng = self._mk(n=1_000)
        lo = int(keys[0])
        eng.insert(lo - 3, 111)   # below the whole snapshot range
        r = eng.scan(lo - 5, 4)
        eng.step()
        assert r.result[0] == (lo - 3, 111)
        assert r.result[1] == (lo, lo + 1)
