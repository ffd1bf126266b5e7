"""Writes reach the host index behind the step's device work (DESIGN.md §3).

A step records its writes in the overlay and in each shard's host log,
dispatches the overlay merge and the first read program, and only then
applies the logs to the host ``Aulid`` indexes (phase ``write_index``); the
compaction decision follows the drain.  These tests hold both engines,
under sync and async compaction and with repartitioning on, to a sorted-dict
oracle request for request, check the host indexes at every step end, pin
down the order of the phases, and cover the write that would be lost if a
shard froze with an unapplied log.
"""
import bisect
import contextlib

import numpy as np
import pytest

from test_async_compaction import ManualExecutor

from repro.core import Aulid, AulidConfig, BlockDevice, partition_bulkload
from repro.core.workloads import make_dataset, payloads_for
from repro.serving import IndexEngine, ShardedIndexEngine
from repro.serving import index_engine as ie_mod

SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)


@pytest.fixture
def manual_pool(monkeypatch):
    pool = ManualExecutor()
    monkeypatch.setattr(ie_mod, "_COMPACT_POOL", pool)
    return pool


def _data(n):
    keys = make_dataset("covid", n, seed=1)
    return keys, payloads_for(keys)


def _engine(which, keys, pay, gamma, **kw):
    if which == "mono":
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, pay)
        return IndexEngine(idx, gamma=gamma, backend="jnp", **kw)
    part = partition_bulkload(keys, pay, 3, cfg=AulidConfig(**SMALL_GEOM))
    return ShardedIndexEngine(part, gamma=gamma, backend="jnp", **kw)


def _shards(eng):
    return [eng.shard] if isinstance(eng, IndexEngine) else eng.shards


def _host_items(sh) -> dict:
    """A shard's host index with its unapplied log replayed on top."""
    items = dict(sh.idx.scan(0, sh.idx.n_items))
    for op, key, payload in sh.pending:
        if op == "insert":
            items[key] = payload
        else:
            items.pop(key, None)
    return items


class Oracle:
    """Sorted dict with the engine's step semantics: the step's writes in
    request order, then every read of the step sees all of them."""

    def __init__(self, keys, pay):
        self.d = {int(k): int(p) for k, p in zip(keys, pay)}

    def step(self, reqs) -> list:
        out = {}
        for i, (op, key, *rest) in enumerate(reqs):
            if op == "insert":
                self.d[key] = rest[0]
                out[i] = True
            elif op == "delete":
                out[i] = self.d.pop(key, None) is not None
        order = sorted(self.d)
        for i, (op, key, *rest) in enumerate(reqs):
            if op == "get":
                out[i] = self.d.get(key)
            elif op == "scan":
                j = bisect.bisect_left(order, key)
                out[i] = [(k, self.d[k]) for k in order[j:j + rest[1]]]
        return [out[i] for i in range(len(reqs))]


def _step_requests(rng, keys, hot_hi, step):
    """One mixed step: gets, upserts (fresh keys below ``hot_hi`` to drift
    the load, and loaded keys), deletes, scans of one length bucket, a key
    written twice, a delete after an insert and an insert after a delete."""
    lo = int(keys[0])

    def loaded():
        return int(rng.choice(keys))

    def fresh():
        return int(rng.integers(lo, hot_hi))

    reqs = [("get", loaded() if rng.random() < 0.7 else fresh())
            for _ in range(12)]
    reqs += [("insert", fresh() if rng.random() < 0.6 else loaded(),
              1000 * step + i) for i in range(24)]
    reqs += [("delete", loaded()) for _ in range(4)]
    reqs += [("scan", loaded(), 0, int(rng.integers(9, 16))) for _ in range(3)]
    twice, a, b = fresh(), fresh(), loaded()
    reqs += [("insert", twice, 1), ("get", twice), ("insert", twice, 2),
             ("insert", a, 3), ("delete", a), ("get", a),
             ("delete", b), ("insert", b, 4), ("get", b),
             ("scan", a, 0, 12)]
    return reqs


CASES = {
    "mono-sync": ("mono", dict(async_compact=False)),
    "mono-async": ("mono", dict(async_compact=True)),
    "sharded-sync": ("sharded", dict(async_compact=False)),
    "sharded-async": ("sharded", dict(async_compact=True)),
    "sharded-sync-repartition": ("sharded", dict(
        async_compact=False, repartition=True, split_ratio=1.5,
        min_split_items=16)),
    "sharded-async-repartition": ("sharded", dict(
        async_compact=True, repartition=True, split_ratio=1.5,
        min_split_items=16)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_mixed_steps_match_sorted_dict_oracle(manual_pool, case):
    """Every result equals the oracle's; at every step end each shard's
    host index plus its log equals the oracle over the shard's range, and
    an unfrozen shard's log is empty."""
    which, kw = CASES[case]
    keys, pay = _data(600)
    eng = _engine(which, keys, pay, gamma=0.04, **kw)
    oracle = Oracle(keys, pay)
    rng = np.random.default_rng(sum(map(ord, case)))
    hot_hi = int(keys[len(keys) // 6])     # inserts drift to the low range
    for step in range(10):
        trace = _step_requests(rng, keys, hot_hi, step)
        reqs = [eng.submit(*args) for args in trace]
        eng.step()
        want = oracle.step(trace)
        for r, w, args in zip(reqs, want, trace):
            assert r.done and r.result == w, (step, args)
        for s, sh in enumerate(_shards(eng)):
            if sh.frozen_overlay is None:
                assert not sh.pending, (step, s)
            if which == "mono":
                mine = oracle.d
            else:
                mine = {k: v for k, v in oracle.d.items()
                        if eng.part.shard_of(k) == s}
            assert _host_items(sh) == mine, (step, s)
        if step % 2:
            manual_pool.pump()      # builds span a whole step, then land
    st = eng.stats()
    assert st["compactions"] > 0
    if kw.get("repartition"):
        assert st["splits"] + st["merges"] > 0
    eng.drain_compactions()
    for sh in _shards(eng):
        assert sh.frozen_overlay is None and not sh.pending
        sh.idx.check_invariants()


@pytest.mark.parametrize("which", ["mono", "sharded"])
def test_writes_of_the_freezing_step_survive_the_swap(manual_pool, which):
    """A shard crosses gamma·n in step t and freezes: the drain ran first,
    so the build sees step t's writes in the host index, and after the
    swap retires the overlay that held them they are still read back."""
    keys, pay = _data(600)
    eng = _engine(which, keys, pay, gamma=0.02, async_compact=True)
    sh = _shards(eng)[0]
    hi = int(keys[150])
    rng = np.random.default_rng(21)
    fresh = sorted({int(k) for k in rng.integers(int(keys[0]), hi, 60)}
                   - {int(k) for k in keys})
    need = int(0.02 * sh.idx.n_items) + 2
    upd, dead = int(keys[3]), int(keys[7])
    writes = ([("insert", k, 7 * k) for k in fresh[:need]]
              + [("insert", upd, 99), ("delete", dead)])
    for args in writes:
        eng.submit(*args)
    eng.step()
    frozen, log = sh.frozen_overlay is not None, list(sh.pending)
    probe = fresh[:need] + [upd, dead]
    in_host = [sh.idx.lookup(k) for k in probe]    # what the build reads
    assert manual_pool.pump() >= 1
    gets = [eng.get(k) for k in probe]
    scan = eng.scan(int(keys[0]), 16)
    eng.step()                             # installs the build first
    assert eng.stats()["swaps"] >= 1 and sh.frozen_overlay is None
    want = [7 * k for k in fresh[:need]] + [99, None]
    assert [g.result for g in gets] == want
    oracle = Oracle(keys, pay)
    oracle.step(writes)
    assert [scan.result] == oracle.step([("scan", int(keys[0]), 0, 16)])
    assert frozen and not log and in_host == want


def _recording(monkeypatch, eng, in_host):
    """Record the ``aulid.*`` phases entered, and ``in_host()`` at each
    read program's dispatch."""
    seen, at_dispatch = [], []
    monkeypatch.setattr(ie_mod, "phase_span",
                        lambda name: seen.append(name)
                        or contextlib.nullcontext())
    lookup, scan = eng._lookup, eng._scan

    def rec(fn):
        def call(*a, **k):
            at_dispatch.append(in_host())
            return fn(*a, **k)
        return call
    eng._lookup, eng._scan = rec(lookup), rec(scan)
    return seen, at_dispatch


@pytest.mark.parametrize("which", ["mono", "sharded"])
def test_drain_runs_behind_the_first_read_dispatch(monkeypatch, which):
    keys, pay = _data(600)
    eng = _engine(which, keys, pay, gamma=10.0)     # no compaction
    probe = [int(keys[40]) + 1]

    def in_host():
        return any(sh.idx.lookup(probe[0]) is not None for sh in _shards(eng))
    read = ["read_dispatch", "read_wait", "read_unpack"]
    seen, at_dispatch = _recording(monkeypatch, eng, in_host)
    # writes, gets and a scan bucket: the drain sits between the get
    # batch's dispatch and its wait
    eng.insert(probe[0], 5)
    eng.get(int(keys[9]))
    eng.scan(int(keys[9]), 12)
    eng.step()
    assert seen == (["write_apply", "write_host", "read_dispatch",
                     "write_index", "read_wait", "read_unpack"] + read)
    assert at_dispatch == [False, True]
    assert in_host()
    # writes and two scan buckets: behind the first bucket's dispatch
    seen.clear()
    at_dispatch.clear()
    probe[0] += 1
    eng.insert(probe[0], 6)
    eng.scan(int(keys[9]), 12)
    eng.scan(int(keys[9]), 40)
    eng.step()
    assert seen == (["write_apply", "write_host", "read_dispatch",
                     "write_index", "read_wait", "read_unpack"] + read)
    assert at_dispatch == [False, True]
    # writes alone: right after the merge's dispatch
    seen.clear()
    at_dispatch.clear()
    probe[0] += 1
    eng.insert(probe[0], 7)
    eng.step()
    assert seen == ["write_apply", "write_host", "write_index"]
    assert in_host()
    # reads alone: nothing to drain
    seen.clear()
    eng.get(probe[0])
    eng.step()
    assert seen == read


@pytest.mark.parametrize("which", ["mono", "sharded"])
def test_stats_report_write_index_and_writes_deferred(manual_pool, which):
    keys, pay = _data(600)
    eng = _engine(which, keys, pay, gamma=0.05, async_compact=True)
    st = eng.stats()
    assert st["write_index_s"] == 0 and st["writes_deferred"] == 0
    for i in range(4):
        eng.insert(int(keys[100 + i]), i)
    eng.get(int(keys[1]))
    eng.step()
    st = eng.stats()
    assert st["write_index_s"] > 0
    assert st["writes_deferred"] == st["writes_applied"] == 4
    # a step that freezes the lowest shard: its next writes wait for the
    # swap, so they are applied but not deferred
    hi = int(keys[150])
    need = int(0.05 * _shards(eng)[0].idx.n_items) + 2
    for k in np.linspace(int(keys[0]) + 1, hi - 1, need).astype(np.uint64):
        eng.insert(int(k), 1)
    eng.step()
    assert _shards(eng)[0].frozen_overlay is not None
    before = eng.stats()
    eng.insert(int(keys[2]), 8)
    eng.step()
    st = eng.stats()
    assert st["writes_applied"] - before["writes_applied"] == 1
    assert st["writes_deferred"] == before["writes_deferred"]
