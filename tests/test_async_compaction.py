"""Double-buffered (epoch-mirror) compaction == synchronous compaction,
request for request — the acceptance oracle of DESIGN.md §11.

The storm tests force EVERY shard past its gamma threshold in one step and
then stream gets/scans/deletes across the freeze -> build -> upload -> swap
-> retire lifecycle, comparing the async engine's results against a
synchronous twin serving the same trace.  A manually-pumped executor stands
in for the background pool so the in-flight window deterministically spans
whole steps: reads and writes are provably served from the old epoch + frozen
overlay (and the deferred-write pending log) before the swap is allowed to
land.  Shard-level tests pin down the deferred-write semantics (results
computed overlay-first, pending replay at ``finish_swap``) without an engine.
Fault-scenario tests inject build failures and require the abort path
(``abort_swap``, DESIGN.md §12) to keep the old epoch live with no lost
writes; the split/merge fault twins live in ``test_repartition.py``.
"""
import concurrent.futures

import numpy as np
import pytest

from repro.core import Aulid, AulidConfig, BlockDevice, partition_bulkload
from repro.core.device_index import build_device_index, refresh_device_index
from repro.core.workloads import make_dataset, payloads_for
from repro.serving import ShardedIndexEngine
from repro.serving import index_engine as ie_mod
from repro.serving.index_engine import IndexShard

SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)


class ManualExecutor:
    """submit() parks jobs until pump() — the in-flight window of the
    double-buffered lifecycle becomes a test-controlled clock edge."""

    def __init__(self):
        self.jobs = []

    def submit(self, fn, *args):
        fut = concurrent.futures.Future()
        self.jobs.append((fut, fn, args))
        return fut

    def pump(self):
        jobs, self.jobs = self.jobs, []
        for fut, fn, args in jobs:
            try:
                fut.set_result(fn(*args))
            except Exception as exc:   # fault injection: deliver the failure
                fut.set_exception(exc)
        return len(jobs)


@pytest.fixture
def manual_pool(monkeypatch):
    pool = ManualExecutor()
    monkeypatch.setattr(ie_mod, "_COMPACT_POOL", pool)
    return pool


def _dataset(n=1_500):
    keys = make_dataset("covid", n, seed=1)
    return keys, payloads_for(keys)


def _sharded(gamma, async_compact, num_shards=3, n=1_500):
    keys, pay = _dataset(n)
    part = partition_bulkload(keys, pay, num_shards,
                              cfg=AulidConfig(**SMALL_GEOM))
    return keys, ShardedIndexEngine(part, gamma=gamma, backend="jnp",
                                    async_compact=async_compact)


def _one_shard(gamma, async_compact, n=1_500):
    """The single-index engine: one shard, one mirror, one build in flight."""
    return _sharded(gamma, async_compact, num_shards=1, n=n)


def _storm_writes(eng: ShardedIndexEngine, keys, rng):
    """Per shard: enough inserts to cross gamma, plus deletes of existing
    keys — so every shard freezes in one step WITH tombstones in the frozen
    overlay."""
    by_shard = {s: [] for s in range(eng.num_shards)}
    for k in keys:
        by_shard[eng.part.shard_of(int(k))].append(int(k))
    ins, dels = [], []
    for s, sh in enumerate(eng.shards):
        need = int(eng.gamma * max(sh.idx.n_items, 1)) + 2
        pool = by_shard[s]
        dels.extend(rng.choice(pool, size=3, replace=False).tolist())
        lo = 0 if s == 0 else int(eng.part.bounds[s - 1]) + 1
        hi = (int(eng.part.bounds[s]) if s < eng.num_shards - 1
              else 2**48)
        ins.extend(int(k) for k in
                   rng.integers(lo, hi, size=need, dtype=np.uint64))
    return ins, dels


def _result(r):
    return tuple(r.result) if isinstance(r.result, list) else r.result


def _drive(eng, trace):
    """Apply a list of per-step request lists; returns flat results."""
    out = []
    for step in trace:
        reqs = [eng.submit(*args) for args in step]
        eng.step()
        out.extend((r.op, r.key, _result(r)) for r in reqs)
    return out


class TestShardedStormEquivalence:
    def _trace(self, eng, keys, seed):
        """One all-shards storm step, an in-flight mixed step, a post-swap
        read step — gets/scans straddle the swap, deletes freeze in the old
        overlay."""
        rng = np.random.default_rng(seed)
        ins, dels = _storm_writes(eng, keys, rng)
        storm = ([("insert", k, 7 * k) for k in ins]
                 + [("delete", k) for k in dels]
                 + [("get", k) for k in dels]          # tombstone visibility
                 + [("get", int(k)) for k in rng.choice(keys, 12)]
                 + [("scan", int(k), 0, 16) for k in rng.choice(keys, 4)])
        inflight = ([("insert", int(k), 9) for k in rng.choice(keys, 8)]
                    + [("delete", int(k)) for k in rng.choice(keys, 4)]
                    + [("delete", k) for k in dels[:2]]  # already-dead keys
                    + [("get", int(k)) for k in rng.choice(keys, 12)]
                    + [("get", k) for k in ins[:6]]
                    + [("scan", int(k), 0, 16) for k in rng.choice(keys, 4)])
        post = ([("get", int(k)) for k in rng.choice(keys, 12)]
                + [("get", k) for k in dels]
                + [("scan", int(k), 0, 16) for k in rng.choice(keys, 4)])
        return [storm, inflight, post]

    @pytest.mark.parametrize("seed", [5, 23])
    def test_storm_request_for_request(self, manual_pool, seed):
        keys, sync = _sharded(0.02, async_compact=False)
        _, dbuf = _sharded(0.02, async_compact=True)
        trace = self._trace(sync, keys, seed)

        out_sync = _drive(sync, trace[:2])
        # async: storm step freezes every shard; builds stay parked, so the
        # second step's reads AND writes provably run inside the window
        out_async = _drive(dbuf, trace[:2])
        assert dbuf.stats()["inflight"] == dbuf.num_shards
        assert all(sh.frozen_overlay is not None for sh in dbuf.shards)
        assert out_sync == out_async

        # release the builds: the next step's _begin_step swaps epochs
        manual_pool.pump()
        out_sync = _drive(sync, trace[2:])
        out_async = _drive(dbuf, trace[2:])
        assert out_sync == out_async
        st = dbuf.stats()
        assert st["swaps"] == dbuf.num_shards and st["inflight"] == 0
        assert all(sh.frozen_overlay is None and not sh.pending
                   for sh in dbuf.shards)

    def test_storm_with_real_pool(self):
        """Same storm against the real background pool (arbitrary build
        timing): equivalence must hold under ANY interleaving."""
        keys, sync = _sharded(0.02, async_compact=False)
        _, dbuf = _sharded(0.02, async_compact=True)
        trace = self._trace(sync, keys, seed=31)
        out_sync = _drive(sync, trace)
        out_async = _drive(dbuf, trace)
        dbuf.drain_compactions()
        assert out_sync == out_async
        assert dbuf.stats()["swaps"] == dbuf.num_shards

    def test_compaction_counters_match_at_freeze(self, manual_pool):
        """compactions counts the DECISION (freeze), so sync and async agree
        on the storm step even though async hasn't swapped yet."""
        keys, sync = _sharded(0.02, async_compact=False)
        _, dbuf = _sharded(0.02, async_compact=True)
        rng = np.random.default_rng(3)
        ins, dels = _storm_writes(sync, keys, rng)
        step = [("insert", k, 5 * k) for k in ins] + \
               [("delete", k) for k in dels]
        _drive(sync, [step])
        _drive(dbuf, [step])
        assert sync.stats()["compactions"] == dbuf.stats()["compactions"] \
            == dbuf.num_shards
        assert dbuf.stats()["swaps"] == 0          # not installed yet


class TestMonolithicAsync:
    def test_async_equivalence_and_lifecycle(self, manual_pool):
        keys, sync = _one_shard(0.02, async_compact=False)
        _, dbuf = _one_shard(0.02, async_compact=True)
        rng = np.random.default_rng(11)
        need = int(0.02 * len(keys)) + 2
        dels = rng.choice(keys, 4, replace=False).tolist()
        storm = ([("insert", int(k), 3) for k in
                  rng.integers(1, 2**48, need, dtype=np.uint64)]
                 + [("delete", int(k)) for k in dels]
                 + [("get", int(k)) for k in dels]
                 + [("scan", int(rng.choice(keys)), 0, 16)])
        inflight = ([("insert", int(rng.choice(keys)), 42)]
                    + [("delete", int(dels[0]))]       # delete of a dead key
                    + [("get", int(k)) for k in rng.choice(keys, 8)]
                    + [("scan", int(rng.choice(keys)), 0, 16)])
        assert _drive(sync, [storm, inflight]) == \
            _drive(dbuf, [storm, inflight])
        assert dbuf.stats()["inflight"] == 1 and dbuf.shards[0].pending
        manual_pool.pump()
        post = [("get", int(k)) for k in rng.choice(keys, 8)]
        assert _drive(sync, [post]) == _drive(dbuf, [post])
        assert dbuf.stats()["swaps"] == 1
        assert (dbuf.shards[0].frozen_overlay is None
                and not dbuf.shards[0].pending)


class TestDeferredWrites:
    """IndexShard-level semantics of the in-flight window: writes defer to
    the pending log, results are computed overlay-first, and ``finish_swap``
    replays into the host index exactly once."""

    def _shard(self):
        keys, pay = _dataset(600)
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, pay)
        return keys, IndexShard.wrap(idx, gamma=0.05)

    def test_deferred_results_match_sync_semantics(self):
        keys, sh = self._shard()
        k_dead = int(keys[10])
        sh.apply_write("delete", k_dead)          # tombstone, pre-freeze
        frozen = sh.freeze()
        assert frozen.get(k_dead) == (0, True)
        n_before = sh.idx.n_items
        # deferred: a delete of a key only the FROZEN overlay killed
        assert sh.apply_write("delete", k_dead) is False
        # deferred: a delete of a key only the host index knows
        assert sh.apply_write("delete", int(keys[20])) is True
        # deferred: insert-then-delete inside the window (live overlay wins)
        assert sh.apply_write("insert", 123456789, 7) is True
        assert sh.apply_write("delete", 123456789) is True
        assert sh.apply_write("delete", 123456789) is False
        # the host index was NOT touched while frozen
        assert sh.idx.n_items == n_before
        assert len(sh.pending) == 5

    def test_finish_swap_replays_pending(self):
        keys, sh = self._shard()
        sh.freeze()
        sh.apply_write("insert", 424242, 99)
        sh.apply_write("delete", int(keys[5]))
        di = refresh_device_index(sh.idx, sh.di)
        sh.finish_swap(di)
        assert sh.frozen_overlay is None and not sh.pending
        assert sh.idx.lookup(424242) == 99        # replayed upsert
        assert sh.idx.lookup(int(keys[5])) is None  # replayed delete
        assert sh.di is di

    def test_sync_compact_guarded_while_frozen(self):
        _, sh = self._shard()
        sh.freeze()
        with pytest.raises(AssertionError):
            sh.compact()
        with pytest.raises(AssertionError):
            sh.freeze()                            # one build in flight


class TestFailedBuilds:
    """Fault scenarios (DESIGN.md §12): a background build that RAISES must
    leave the served view on the old epoch with no lost writes — the frozen
    overlay folds back under the live one (``abort_swap``), the pending log
    replays into the host index — and a later successful build must fully
    recover.  The oracle is the same sync twin as the storm suite."""

    def test_sharded_failed_build_keeps_writes(self, manual_pool):
        keys, sync = _sharded(0.02, async_compact=False)
        _, dbuf = _sharded(0.02, async_compact=True)
        rng = np.random.default_rng(7)
        ins, dels = _storm_writes(sync, keys, rng)
        storm = ([("insert", k, 7 * k) for k in ins]
                 + [("delete", k) for k in dels])
        inflight = ([("insert", int(k), 9) for k in rng.choice(keys, 8)]
                    + [("delete", int(k)) for k in rng.choice(keys, 4)]
                    + [("get", k) for k in dels]
                    + [("get", k) for k in ins[:6]]
                    + [("scan", int(k), 0, 16) for k in rng.choice(keys, 4)])
        post = ([("get", int(k)) for k in rng.choice(keys, 16)]
                + [("get", k) for k in ins[:6]]
                + [("get", k) for k in dels]
                + [("scan", int(k), 0, 16) for k in rng.choice(keys, 4)])

        def boom(s, sdi):
            raise RuntimeError("injected build failure")
        dbuf._build_job = boom
        assert _drive(sync, [storm, inflight]) == \
            _drive(dbuf, [storm, inflight])
        assert dbuf.stats()["inflight"] == dbuf.num_shards
        del dbuf._build_job                    # restore the real build
        manual_pool.pump()                     # delivers the injected failures
        epoch0 = dbuf.sdi.epoch
        # next step aborts every swap: old epoch stays live, pending replays
        assert _drive(sync, [post]) == _drive(dbuf, [post])
        st = dbuf.stats()
        assert st["failed_swaps"] == dbuf.num_shards and st["swaps"] == 0
        assert dbuf.sdi.epoch == epoch0        # served view never moved
        assert all(not sh.pending for sh in dbuf.shards)
        # one write step: the merged-back overlays still exceed gamma, so
        # every shard re-freezes with the REAL build job — recovery must land
        kick = [("insert", int(keys[0]), 4242)]
        assert _drive(sync, [kick]) == _drive(dbuf, [kick])
        manual_pool.pump()                     # recovery builds succeed
        assert _drive(sync, [post]) == _drive(dbuf, [post])
        st = dbuf.stats()
        assert st["swaps"] == dbuf.num_shards
        assert all(sh.frozen_overlay is None and not sh.pending
                   for sh in dbuf.shards)

    def test_monolithic_failed_build_keeps_writes(self, manual_pool):
        keys, sync = _one_shard(0.02, async_compact=False)
        _, dbuf = _one_shard(0.02, async_compact=True)
        rng = np.random.default_rng(13)
        need = int(0.02 * len(keys)) + 2
        news = [int(k) for k in rng.integers(1, 2**48, need, dtype=np.uint64)]
        dels = [int(k) for k in rng.choice(keys, 3, replace=False)]
        storm = [("insert", k, 3 * k) for k in news] + \
                [("delete", k) for k in dels]
        inflight = ([("insert", news[0], 777), ("delete", news[1])]
                    + [("get", k) for k in news[:4]]
                    + [("get", k) for k in dels])
        post = ([("get", k) for k in news[:4]] + [("get", k) for k in dels]
                + [("scan", int(rng.choice(keys)), 0, 16)])

        def boom(s, sdi):
            raise RuntimeError("injected build failure")
        dbuf._build_job = boom
        assert _drive(sync, [storm, inflight]) == \
            _drive(dbuf, [storm, inflight])
        del dbuf._build_job
        manual_pool.pump()
        assert _drive(sync, [post]) == _drive(dbuf, [post])
        st = dbuf.stats()
        assert st["failed_swaps"] == 1 and st["swaps"] == 0
        assert not dbuf.shards[0].pending      # replayed, not lost
        kick = [("insert", int(keys[0]), 4242)]   # re-freeze via write step
        assert _drive(sync, [kick]) == _drive(dbuf, [kick])
        manual_pool.pump()                     # recovery build
        assert _drive(sync, [post]) == _drive(dbuf, [post])
        assert dbuf.stats()["swaps"] == 1
        assert dbuf.shards[0].frozen_overlay is None


class TestEpochInvariants:
    def test_install_bumps_epoch_and_token(self, manual_pool):
        """Every swap advances the stacked epoch and issues a fresh operand
        snapshot token — the fused kernel's cache can never serve a pack
        from a retired epoch (reads-never-observe-mixed-epoch, §11).

        The storm is upsert-only (existing keys, new payloads): content-only
        journal entries take the fast refresh path and grow no pool, so the
        prepared slices are guaranteed to fit and the install deterministically
        exercises the pre-uploaded-slice scatter (not the re-stack
        fallback)."""
        keys, eng = _sharded(0.02, async_compact=True)
        rng = np.random.default_rng(2)
        epoch0, tok0 = eng.sdi.epoch, eng.stk["snap_token"]
        by_shard = {s: [] for s in range(eng.num_shards)}
        for k in keys:
            by_shard[eng.part.shard_of(int(k))].append(int(k))
        ups = []
        for s, sh in enumerate(eng.shards):
            need = int(eng.gamma * max(sh.idx.n_items, 1)) + 2
            ups.extend(rng.choice(by_shard[s], size=need,
                                  replace=False).tolist())
        _drive(eng, [[("insert", k, 17 * k + 1) for k in ups]])
        assert eng.stats()["inflight"] == eng.num_shards
        # the old epoch keeps serving while the builds are parked
        assert eng.sdi.epoch == epoch0 and eng.stk["snap_token"] == tok0
        manual_pool.pump()
        out = _drive(eng, [[("get", k) for k in ups[:8]]])
        assert out == [("get", k, 17 * k + 1) for k in ups[:8]]
        st = eng.stats()
        assert st["swaps"] == eng.num_shards and st["full_restacks"] == 0
        assert eng.sdi.epoch == epoch0 + eng.num_shards  # one bump per install
        assert eng.stk["snap_token"] != tok0             # new operand pack key
