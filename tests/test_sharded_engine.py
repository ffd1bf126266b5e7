"""ShardedIndexEngine over one shard vs over three, request for request.

The acceptance oracle of range sharding (DESIGN.md §9): on any interleaving
of get/insert/delete/scan requests the three-shard engine must return
exactly what the one-shard engine (a single index) returns, while compacting
shard-locally (a hot shard folding its overlay leaves cold shards' mirrors
at their snapshot epoch).  The one-shard engine is in turn held to the host
``Aulid`` and to a dict oracle, also when it wraps an index that took writes
after its bulkload.
"""
import bisect

import numpy as np
import pytest

from repro.core import (Aulid, AulidConfig, BlockDevice, RangePartition,
                        partition_bulkload)
from repro.core.workloads import make_dataset, payloads_for
from repro.serving import ShardedIndexEngine
from repro.serving.index_engine import PHASES, pad_queries, scan_bucket

SMALL_GEOM = dict(leaf_capacity=16, pa_classes=(4, 8), bt_child_capacity=15)


def one_shard(idx: Aulid) -> RangePartition:
    """A single host index as a one-shard partition: no boundaries."""
    return RangePartition(np.empty(0, dtype=np.uint64), [idx])


def mk_engines(n=1_500, num_shards=3, gamma=0.05, **kw):
    """(keys, one-shard engine, ``num_shards``-shard engine) over the same
    keys."""
    keys = make_dataset("covid", n, seed=1)
    pay = payloads_for(keys)
    cfg = AulidConfig(**SMALL_GEOM)
    return (keys,
            ShardedIndexEngine(partition_bulkload(keys, pay, 1, cfg=cfg),
                               gamma=gamma, **kw),
            ShardedIndexEngine(partition_bulkload(keys, pay, num_shards,
                                                  cfg=cfg),
                               gamma=gamma, **kw))


class TestShardedEquivalence:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_randomized_mixed_trace(self, seed):
        """Property: both engines answer a randomized mixed trace with
        identical results (fixed per-step op mix keeps jit shapes shared)."""
        keys, one, shrd = mk_engines()
        rng = np.random.default_rng(seed)
        pairs = []
        for step in range(3):
            for i in range(18):       # 18 gets
                k = (int(rng.choice(keys)) if rng.random() < 0.6
                     else int(rng.integers(0, 2**50)))
                pairs.append((one.get(k), shrd.get(k)))
            for i in range(10):       # 10 upserts (new + existing keys)
                k = (int(rng.integers(0, 2**50)) if rng.random() < 0.7
                     else int(rng.choice(keys)))
                p = step * 100 + i
                pairs.append((one.insert(k, p), shrd.insert(k, p)))
            for i in range(5):        # 5 deletes
                k = (int(rng.choice(keys)) if rng.random() < 0.6
                     else int(rng.integers(0, 2**50)))
                pairs.append((one.delete(k), shrd.delete(k)))
            for i in range(4):        # 4 scans, one shared length bucket
                k = int(rng.choice(keys)) if rng.random() < 0.8 \
                    else int(rng.integers(0, 2**50))
                c = int(rng.integers(9, 16))
                pairs.append((one.scan(k, c), shrd.scan(k, c)))
            one.step()
            shrd.step()
        for m, s in pairs:
            assert m.done and s.done
            assert m.result == s.result, (m.op, m.key, m.count)
        assert one.reads_served == shrd.reads_served
        assert one.writes_applied == shrd.writes_applied
        for sh in shrd.shards:
            sh.idx.check_invariants()

    def test_scan_across_boundary_with_step_writes(self):
        """A scan straddling a shard boundary sees same-step writes on BOTH
        sides of the boundary (overlay merge + successor chain)."""
        keys, one, shrd = mk_engines(gamma=10.0)   # no compaction
        b = int(shrd.part.bounds[0])
        i = int(np.searchsorted(keys, np.uint64(b)))
        start = int(keys[i - 2])
        for eng in (one, shrd):
            eng.insert(b - 1 if b - 1 not in keys else b, 111)
            eng.insert(b + 1, 222)
            eng.delete(int(keys[i - 1]))
        r_m = one.scan(start, 10)
        r_s = shrd.scan(start, 10)
        one.step()
        shrd.step()
        assert r_m.result == r_s.result
        got_keys = [k for k, _ in r_s.result]
        assert b + 1 in got_keys, "must cross into the next shard"
        assert int(keys[i - 1]) not in got_keys


class TestOneShard:
    def test_index_with_writes_after_bulkload_vs_dict_oracle(self):
        """An ``Aulid`` that took inserts and deletes after its bulkload,
        wrapped as a one-shard partition, serves a mixed trace of gets,
        upserts, deletes and scans exactly like a dict oracle — across the
        compactions its gamma forces."""
        keys = make_dataset("covid", 1_000, seed=4)
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, payloads_for(keys))
        oracle = {int(k): int(p) for k, p in zip(keys, payloads_for(keys))}
        rng = np.random.default_rng(17)
        for k in rng.integers(int(keys[0]), int(keys[-1]), 120):
            idx.insert(int(k), int(k) % 991)
            oracle[int(k)] = int(k) % 991
        for k in rng.choice(keys, 40, replace=False):
            assert idx.delete(int(k))
            oracle.pop(int(k))
        assert idx.n_items == len(oracle)
        eng = ShardedIndexEngine(one_shard(idx), gamma=0.03)
        st = eng.stats()
        assert st["num_shards"] == 1 and st["read_backend"] == "jnp"
        for step in range(6):
            srt = sorted(oracle)
            checks = []
            for _ in range(16):
                k = (int(rng.choice(srt)) if rng.random() < 0.7
                     else int(rng.integers(0, 2**50)))
                checks.append((eng.get(k), k))
            for i in range(10):
                k = (int(rng.integers(0, 2**50)) if rng.random() < 0.6
                     else int(rng.choice(srt)))
                eng.insert(k, 1000 * step + i)
                oracle[k] = 1000 * step + i
            for _ in range(4):
                k = (int(rng.choice(srt)) if rng.random() < 0.75
                     else int(rng.integers(0, 2**50)))
                r = eng.delete(k)
                checks.append((r, oracle.pop(k, None) is not None))
            for _ in range(3):
                checks.append((eng.scan(int(rng.choice(srt)), 12), None))
            eng.step()
            if step % 2:
                eng.drain_compactions()
            srt = sorted(oracle)
            for r, want in checks:
                assert r.done
                if r.op == "get":
                    assert r.result == oracle.get(want), (step, r.key)
                elif r.op == "delete":
                    assert r.result is want, (step, r.key)
                else:
                    j = bisect.bisect_left(srt, r.key)
                    assert r.result == [(k, oracle[k])
                                        for k in srt[j:j + 12]], step
        eng.drain_compactions()
        st = eng.stats()
        assert st["num_shards"] == 1 and st["compactions"] >= 1
        assert eng.shards[0].idx is idx
        assert dict(idx.scan(0, idx.n_items)) == oracle
        idx.check_invariants()


class TestShardLocalCompaction:
    def test_cold_shards_keep_snapshot_epoch(self):
        """Writes confined to one shard's range compact that shard only;
        cold shards' mirrors keep their snapshot epoch (the structural
        property the p99 benchmark gate rests on)."""
        keys, one, shrd = mk_engines(num_shards=4, gamma=0.01)
        hot = 1
        lo = int(shrd.part.bounds[0]) + 1
        hi = int(shrd.part.bounds[1])
        cold = [s for s in range(4) if s != hot]
        before = [(shrd.shards[s].di.journal_epoch,
                   shrd.shards[s].di.full_builds,
                   shrd.shards[s].di.refreshes) for s in range(4)]
        rng = np.random.default_rng(0)
        for step in range(3):
            for k in rng.integers(lo, hi, 30):
                shrd.insert(int(k), int(k) % 1000)
            shrd.step()
        assert shrd.shards[hot].compactions >= 1
        for s in cold:
            assert shrd.shards[s].compactions == 0
            assert (shrd.shards[s].di.journal_epoch,
                    shrd.shards[s].di.full_builds,
                    shrd.shards[s].di.refreshes) == before[s], f"shard {s}"
        st = shrd.stats()
        assert st["compactions"] == shrd.shards[hot].compactions
        assert st["compactions_per_shard"][hot] == st["compactions"]

    def test_empty_to_nonempty_engine(self):
        """An engine over an empty partition serves its first writes."""
        part = partition_bulkload(np.empty(0, dtype=np.uint64),
                                  np.empty(0, dtype=np.uint64), 2,
                                  cfg=AulidConfig(**SMALL_GEOM))
        eng = ShardedIndexEngine(part, gamma=0.001)  # compact on every write
        eng.insert(42, 7)
        r0 = eng.get(42)
        eng.step()
        assert r0.result == 7
        r1, r2 = eng.get(42), eng.get(43)
        eng.step()
        assert r1.result == 7 and r2.result is None


class TestScanBucketing:
    def test_bucket_is_pow2_and_floored(self):
        assert scan_bucket(1) == 8 and scan_bucket(8) == 8
        assert scan_bucket(9) == 16 and scan_bucket(100) == 128

    def test_mixed_lengths_share_buckets_and_slice_exact(self):
        keys = make_dataset("covid", 800, seed=1)
        idx = Aulid(BlockDevice(), cfg=AulidConfig(**SMALL_GEOM))
        idx.bulkload(keys, payloads_for(keys))
        eng = ShardedIndexEngine(one_shard(idx), gamma=10.0)
        reqs = [eng.scan(int(keys[40]), c) for c in (3, 5, 7, 8, 12, 16)]
        eng.step()
        for r, c in zip(reqs, (3, 5, 7, 8, 12, 16)):
            assert len(r.result) == c
            assert r.result == idx.scan(int(keys[40]), c)
        # 6 distinct lengths collapse into 2 compile buckets (8 and 16)
        assert len({scan_bucket(c) for c in (3, 5, 7, 8, 12, 16)}) == 2

    def test_pad_queries_pow2(self):
        q = pad_queries([1, 2, 3])
        assert q.shape == (4,) and q[3] == np.uint64(0xFFFFFFFFFFFFFFFF)
        assert pad_queries([1]).shape == (1,)


# the phases a request-path step is made of (``compact_build`` runs on the
# pool thread; ``install`` nests the pack rebuild after a swap)
STEP_PHASES = ("install", "write_apply", "write_host", "read_dispatch",
               "read_wait", "read_unpack", "write_index")


def _phase_delta(eng, drive) -> dict:
    s0 = eng.stats()
    drive()
    s1 = eng.stats()
    return {p: s1[f"{p}_s"] - s0[f"{p}_s"] for p in PHASES}


def _mixed_requests(eng, keys) -> None:
    for k in keys[:24:3]:
        eng.get(int(k))
    eng.insert(int(keys[5]) + 1, 9)
    eng.delete(int(keys[7]))
    eng.scan(int(keys[11]), 12)


class TestPhaseAccounting:
    @pytest.mark.parametrize("which", ["one_shard", "three_shards"])
    def test_step_phases_add_up_within_the_step(self, which):
        """Every phase that ran in a step with writes, gets and scans counts
        above 0, and the request-path phases fit inside the step's own."""
        keys, one, shrd = mk_engines(gamma=10.0)       # no compaction
        eng = one if which == "one_shard" else shrd
        _mixed_requests(eng, keys)
        d = _phase_delta(eng, eng.step)
        for p in ("write_apply", "write_host", "read_dispatch", "read_wait",
                  "read_unpack", "write_index"):
            assert d[p] > 0, p
        assert d["install"] == 0 and d["compact_build"] == 0
        assert sum(d[p] for p in STEP_PHASES) <= eng.step_seconds[-1]
        assert "step_s" not in eng.stats()
        for gone in ("throughput_ops_s", "p99_step_s", "mean_read_batch"):
            assert gone not in eng.stats()

    @pytest.mark.parametrize("which", ["one_shard", "three_shards"])
    def test_background_compaction_times_build_and_install(self, which):
        """A forced background compaction raises ``compact_build_s`` (timed
        on the pool thread, added at install) and ``install_s``."""
        keys, one, shrd = mk_engines(gamma=0.02, async_compact=True)
        eng = one if which == "one_shard" else shrd
        rng = np.random.default_rng(5)
        for k in rng.integers(int(keys[0]), int(keys[-1]), 200):
            eng.insert(int(k), 1)
        eng.step()
        assert eng.stats()["inflight"] >= 1
        steps = eng.steps
        d = _phase_delta(eng, eng.drain_compactions)
        assert eng.stats()["swaps"] >= 1
        assert d["compact_build"] > 0 and d["install"] > 0
        assert eng.steps == steps

    @pytest.mark.parametrize("which", ["one_shard", "three_shards"])
    def test_phases_are_profiler_spans_inside_the_step(self, tmp_path,
                                                       which):
        """With a profiler trace running, each phase is an ``aulid.*`` span
        on the host plane, nested inside the caller's span around
        ``step()``: one span per phase entered, not one per request."""
        import jax
        from jax.profiler import ProfileData
        keys, one, shrd = mk_engines(gamma=10.0)
        eng = one if which == "one_shard" else shrd
        _mixed_requests(eng, keys)
        eng.step()                          # compile outside the trace
        _mixed_requests(eng, keys)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with jax.profiler.TraceAnnotation("caller.step"):
            eng.step()
        jax.profiler.stop_trace()
        xplane = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for plane in ProfileData.from_file(str(xplane)).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "caller.step" or e.name.startswith("aulid.")]
        (_, lo, hi), = [s for s in spans if s[0] == "caller.step"]
        names = sorted(n for n, _, _ in spans if n != "caller.step")
        # the read phases twice: the get batch and the one scan bucket;
        # the host-index drain once, behind the first read dispatch
        want = (["write_apply", "write_host", "write_index"]
                + ["read_dispatch", "read_wait", "read_unpack"] * 2)
        assert names == sorted(f"aulid.{p}" for p in want)
        assert all(lo <= a <= b <= hi for _, a, b in spans)
