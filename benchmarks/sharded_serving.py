"""Shard-parallel serving vs one monolithic shard on a skewed mixed workload.

The failure mode range sharding removes: one host index + one device
mirror (the engine over a single shard, the ``monolithic`` rows) means
EVERY compaction stalls the whole key space behind an O(n) mirror rebuild — a write-hot key range taxes reads of cold ranges it never
touches.  Range sharding (DESIGN.md §9) keeps compaction stalls shard-local.

Workload: all writes are fresh keys drawn from ONE shard's range (the hot
shard — leaf splits force SMO full rebuilds on compaction, the worst case),
while point reads spread uniformly over the whole key space and fixed-length
scans cross shard boundaries.  Both engines serve the identical trace with
identical step shapes; per-step wall latency is recorded and the gate
compares p99 *after* a warmup window (the first steps pay one-time jit
compiles for both engines).

Acceptance gates (asserted inline):

* p99 step latency of the sharded engine is >= 2x lower than monolithic;
* compactions are shard-local: every cold shard's mirror keeps its snapshot
  epoch (journal_epoch / full_builds / refreshes unchanged) for the whole
  run, and only the hot shard compacts;
* both engines return identical results on a final probe batch.

The **compaction-storm scenario** (ISSUE 7, DESIGN.md §11) is the adversarial
complement: fresh keys spread *uniformly*, so every shard crosses its gamma
threshold in the SAME step and folds with leaf splits (SMO full mirror
rebuilds) — the worst case for on-path maintenance.  The synchronous engine
does all S rebuilds + device installs inside that step; the double-buffered
engine freezes the overlays and keeps serving while the builds run in the
background, swapping epochs at later step boundaries.  Gates: storm-window
p99 of the double-buffered engine within ``STORM_P99_FLATNESS`` of its own
steady-state p99, and sync-vs-async request-for-request equivalence across
every step of the trace.  The sync engine's storm ratio is reported
alongside: this PR's in-place (donated) slice install removed the
device-side stall for BOTH modes, so at small scales the sync spike is
host-rebuild-bound and modest; it grows with shard size while the
double-buffered path stays flat by construction.

The **drift scenario** (DESIGN.md §12) attacks the remaining static
assumption: the boundary table itself.  Inserts drift through the
previously empty range above the loaded keys (append + advancing zipf
window), so a frozen partition funnels the whole write stream into its
last shard and the max/min shard-size ratio grows without bound.  Three
engines serve the identical trace — frozen, repartitioning-sync and
repartitioning-async — with request-for-request equivalence asserted
inline.  Gates: the frozen engine's final ratio exceeds
``DRIFT_RATIO_BOUND`` (the scenario is real), both repartitioning engines
hold every post-warmup step's ratio within it via >= 2 online splits, and
the async engine's p99 over steady + repartitioning steps stays within
``DRIFT_P99_FLATNESS`` of the steady remainder alone.  Ordinary
compaction steps and capacity restacks are excluded from BOTH sides of
that comparison and reported instead: both hit ANY engine under append
traffic (a freeze whose merged overlay reaches a new pow2 bucket, or a
pool outgrowing its padding, each pay a one-off read-path compile), and
leaving them in makes compile outliers dominate both percentiles.
"""
from __future__ import annotations

import gc

import numpy as np

from repro.core import partition_bulkload
from repro.core.workloads import (make_dataset, payloads_for,
                                  shifting_hotspot_keys)
from repro.serving import ShardedIndexEngine

from .common import SCALE_N, print_table, save_results, timed

NUM_SHARDS = 8
GAMMA = 0.02
STEPS = 40
WARMUP = 8                 # steps excluded from p99 (jit compiles)
WRITES_PER_STEP = 128
GETS_PER_STEP = 512
SCANS_PER_STEP = 16
SCAN_COUNT = 64

# ---- drift / online-repartitioning scenario knobs (DESIGN.md §12)
DRIFT_STEPS = 72
DRIFT_WARMUP = 12
DRIFT_GETS_PER_STEP = 384
DRIFT_SCANS_PER_STEP = 16
DRIFT_GAMMA = 0.1          # hot shard folds every few steps, not every step:
                           # plain serving steps must exist for a baseline
DRIFT_SPLIT_RATIO = 3.0    # engine splits comfortably before the gate bound
DRIFT_RATIO_BOUND = 4.0    # acceptance: repart engine max/min sizes <= 4
DRIFT_P99_FLATNESS = 1.5   # acceptance: drift p99 <= 1.5x steady-state p99

# ---- compaction-storm scenario knobs
STORM_STEPS = 96
STORM_WARMUP = 12          # covers the first full storm cycle's compiles
STORM_WRITES_PER_STEP = 160   # uniform: ~20/shard/step -> all-shard storms
STORM_GETS_PER_STEP = 1024    # read-heavy serving batch: the p99 the storm
STORM_SCANS_PER_STEP = 32     # must not disturb is dominated by real traffic
STORM_P99_FLATNESS = 1.5   # acceptance: async storm p99 <= 1.5x steady p99


def _trace(keys: np.ndarray, bounds: np.ndarray, rng: np.random.Generator):
    """One step's requests: hot-shard inserts + uniform gets + scans."""
    # derive the hot shard from the bounds actually built: quantile bounds
    # can collapse on duplicate-heavy keys, so NUM_SHARDS is only an upper
    # bound on the effective shard count
    num_shards = len(bounds) + 1
    assert num_shards >= 4, f"workload needs >=4 effective shards, got {num_shards}"
    hot = num_shards // 2
    lo = int(bounds[hot - 1]) + 1
    hi = int(bounds[hot])
    steps = []
    for _ in range(STEPS):
        ins = rng.integers(lo, hi, WRITES_PER_STEP, dtype=np.uint64)
        gets = rng.choice(keys, GETS_PER_STEP).astype(np.uint64)
        scans = rng.choice(keys, SCANS_PER_STEP).astype(np.uint64)
        steps.append((ins, gets, scans))
    return hot, steps


def _drive(eng, steps) -> dict:
    for ins, gets, scans in steps:
        for k in ins:
            eng.insert(int(k), int(k) % 100_000)
        for k in gets:
            eng.get(int(k))
        for k in scans:
            eng.scan(int(k), SCAN_COUNT)
        eng.step()
    st = eng.stats()
    lat = np.array(eng.step_seconds[WARMUP:])
    ops_per_step = WRITES_PER_STEP + GETS_PER_STEP + SCANS_PER_STEP
    return {**st,
            "p99_step_s": float(np.percentile(lat, 99)),
            "mean_step_s": float(lat.mean()),
            "throughput_ops_s": ops_per_step / float(lat.mean())}


def _storm_trace(keys: np.ndarray, rng: np.random.Generator):
    """Fresh-key writes drawn uniformly over the WHOLE key range ->
    synchronized all-shard gamma crossings (compaction storms) whose folds
    split leaves and force SMO mirror rebuilds on every shard at once, plus
    a read-heavy get/scan mix."""
    lo, hi = int(keys.min()), int(keys.max())
    steps = []
    for i in range(STORM_STEPS):
        ins = rng.integers(lo, hi, STORM_WRITES_PER_STEP, dtype=np.uint64)
        gets = rng.choice(keys, STORM_GETS_PER_STEP).astype(np.uint64)
        scans = rng.choice(keys, STORM_SCANS_PER_STEP).astype(np.uint64)
        steps.append((ins, gets, scans, i))
    return steps


def _drive_storm(eng: ShardedIndexEngine, steps):
    """Drive the storm trace, recording every request's result (for the
    request-for-request equivalence gate) and tagging each step that did any
    mirror maintenance (compact / freeze / swap / restack) via counter
    deltas — the untagged remainder is the steady-state baseline.  The
    collector is paused for the timed region: fresh-key storms allocate
    heavily and a gen-2 GC pause is the same order as a whole step, which
    would poison the p99-vs-p99 ratio with scheduling noise."""
    results, active = [], []
    gc.collect()
    gc.disable()
    try:
        for ins, gets, scans, step_i in steps:
            reqs = []
            for k in ins:
                reqs.append(eng.insert(int(k), (int(k) + step_i) % 100_000))
            for k in gets:
                reqs.append(eng.get(int(k)))
            for k in scans:
                reqs.append(eng.scan(int(k), SCAN_COUNT))
            before = (eng.compactions, eng.swaps, eng.restacks)
            eng.step()
            active.append((eng.compactions, eng.swaps, eng.restacks)
                          != before)
            results.append([(r.op, r.key, r.result) for r in reqs])
        eng.drain_compactions()
    finally:
        gc.enable()
    return results, np.asarray(active, dtype=bool)


def _storm_stats(eng: ShardedIndexEngine, active: np.ndarray) -> dict:
    lat = np.asarray(eng.step_seconds)[STORM_WARMUP:]
    act = active[STORM_WARMUP:]
    assert act.any(), "storm trace produced no post-warmup compaction storms"
    # a p99 baseline over a handful of steps is just their max — demand
    # enough steady samples that one noisy step cannot swing the ratio
    assert (~act).sum() >= 8, (
        f"only {int((~act).sum())} steady-state steps post-warmup — "
        "lengthen STORM_STEPS for a usable baseline")
    steady_p99 = float(np.percentile(lat[~act], 99))
    storm_p99 = float(np.percentile(lat, 99))
    return {**eng.stats(),
            "steady_p99_s": steady_p99,
            "storm_p99_s": storm_p99,
            "storm_ratio": storm_p99 / max(steady_p99, 1e-9),
            "storm_steps": int(act.sum())}


def run_storm(scale: str = "small") -> list[dict]:
    """Compaction-storm scenario: sync vs double-buffered sharded engine on
    the identical uniform-write trace (ISSUE 7 acceptance criterion)."""
    n = SCALE_N[scale]
    keys = make_dataset("covid", n)
    pays = payloads_for(keys)
    steps = _storm_trace(keys, np.random.default_rng(7))

    engines = {}
    for mode, async_compact in (("sharded-sync", False),
                                ("sharded-async", True)):
        part = partition_bulkload(keys, pays, NUM_SHARDS)
        eng = ShardedIndexEngine(part, gamma=GAMMA,
                                 async_compact=async_compact)
        wall, (results, active) = timed(
            lambda e=eng: _drive_storm(e, steps), warmup=0, reps=1)
        engines[mode] = (eng, results, active, wall)

    # ---- gate 1: request-for-request equivalence across the whole trace
    res_sync = engines["sharded-sync"][1]
    res_async = engines["sharded-async"][1]
    for step_i, (rs, ra) in enumerate(zip(res_sync, res_async)):
        assert rs == ra, f"sync/async diverged at step {step_i}"

    rows = []
    for mode, (eng, _, active, wall) in engines.items():
        st = _storm_stats(eng, active)
        rows.append({
            "engine": mode,
            "scenario": "storm",
            "shards": eng.num_shards,
            "steady_p99_ms": round(1e3 * st["steady_p99_s"], 2),
            "storm_p99_ms": round(1e3 * st["storm_p99_s"], 2),
            "storm_ratio": round(st["storm_ratio"], 2),
            "storm_steps": st["storm_steps"],
            "compactions": st["compactions"],
            "swaps": st["swaps"],
            "full_restacks": st["full_restacks"],
            "wall_s": round(wall, 1),
        })

    by = {r["engine"]: r for r in rows}
    print_table("Compaction storm: all shards cross gamma in the same step "
                "(p99 vs own steady state)",
                rows, ["engine", "storm_p99_ms", "steady_p99_ms",
                       "storm_ratio", "storm_steps", "compactions", "swaps",
                       "full_restacks"])
    print(f"\nasync storm p99 {by['sharded-async']['storm_ratio']:.2f}x its "
          f"steady p99 (gate: <= {STORM_P99_FLATNESS}x); sync ratio "
          f"{by['sharded-sync']['storm_ratio']:.2f}x for comparison")

    # ---- gate 2: double-buffering flattens the storm
    assert by["sharded-async"]["storm_ratio"] <= STORM_P99_FLATNESS, (
        "acceptance criterion: double-buffered storm p99 within "
        f"{STORM_P99_FLATNESS}x of steady-state p99")
    # sanity: storms actually compacted every shard at least once
    eng_async = engines["sharded-async"][0]
    assert all(sh.compactions >= 1 for sh in eng_async.shards), \
        "storm trace failed to compact every shard"
    assert by["sharded-async"]["swaps"] >= eng_async.num_shards
    return rows


def _drift_trace(keys: np.ndarray, rng: np.random.Generator):
    """Append/zipf drift: every insert is a fresh key drawn from a bounded
    zipf window whose center advances through the previously EMPTY range
    above the loaded keys (``shifting_hotspot_keys``), so a frozen boundary
    table funnels the entire write stream into its last shard while reads
    stay global (uniform gets + scans over loaded and already-drifted keys)."""
    lo = int(keys.max()) + 1
    hi = lo + (int(keys.max()) - int(keys.min())) // 2
    writes = max(160, len(keys) // 150)   # scales so frozen ratio exceeds 4x
    drift = shifting_hotspot_keys(DRIFT_STEPS * writes, lo, hi,
                                  window_frac=0.04, sweeps=1.0, rng=rng)
    steps = []
    for i in range(DRIFT_STEPS):
        ins = drift[i * writes:(i + 1) * writes]
        seen = drift[:i * writes]
        n_new = min(len(seen), DRIFT_GETS_PER_STEP // 4)
        gets = rng.choice(keys, DRIFT_GETS_PER_STEP - n_new).astype(np.uint64)
        if n_new:
            gets = np.concatenate(
                [gets, rng.choice(seen, n_new).astype(np.uint64)])
        scans = rng.choice(keys, DRIFT_SCANS_PER_STEP).astype(np.uint64)
        steps.append((ins, gets, scans, i))
    return writes, steps


def _drive_drift(eng: ShardedIndexEngine, steps):
    """Drive the drift trace recording per-request results (equivalence
    gate), the per-step max/min shard-size ratio (balance gate), and three
    maintenance tags: repartitioning work (split/merge/failure counters or
    an in-flight boundary build), ordinary compaction work
    (compaction/swap deltas — the hot shard crosses gamma every couple of
    steps on ANY engine, and a freeze step whose merged overlay reaches a
    new pow2 bucket pays a one-off read-path compile), and capacity
    restacks plus first-seen read specializations (pool growth and new
    static-arg/operand-shape combos both hit any engine under append
    traffic, and each jit-compiles a fresh read variant).  The flatness
    gate compares repartitioning steps against the steady remainder with
    the latter two excluded from BOTH sides — otherwise compile outliers
    dominate both percentiles and the comparison is vacuous."""
    results, ratios = [], []
    repart_act, compact_act, compile_act = [], [], []
    gc.collect()
    gc.disable()
    try:
        for ins, gets, scans, step_i in steps:
            reqs = []
            for k in ins:
                reqs.append(eng.insert(int(k), (int(k) + step_i) % 100_000))
            for k in gets:
                reqs.append(eng.get(int(k)))
            for k in scans:
                reqs.append(eng.scan(int(k), SCAN_COUNT))
            st0 = eng.stats()
            before = (st0["splits"], st0["merges"], st0["repart_failures"])
            inflight0, restacks0 = st0["repart_inflight"], eng.restacks
            compact0 = (eng.compactions, eng.swaps)
            misses0 = eng.read_shape_misses
            eng.step()
            st1 = eng.stats()
            repart_act.append(
                (st1["splits"], st1["merges"], st1["repart_failures"])
                != before or bool(inflight0) or bool(st1["repart_inflight"]))
            compact_act.append((eng.compactions, eng.swaps) != compact0)
            compile_act.append(eng.restacks != restacks0
                               or eng.read_shape_misses != misses0)
            sizes = [sh.idx.n_items for sh in eng.shards]
            ratios.append(max(sizes) / max(min(sizes), 1))
            results.append([(r.op, r.key, r.result) for r in reqs])
        eng.drain_compactions()
    finally:
        gc.enable()
    return (results, np.asarray(ratios),
            np.asarray(repart_act, dtype=bool),
            np.asarray(compact_act, dtype=bool),
            np.asarray(compile_act, dtype=bool))


def _drift_stats(eng: ShardedIndexEngine, ratios, repart_act, compact_act,
                 compile_act) -> dict:
    lat = np.asarray(eng.step_seconds)[DRIFT_WARMUP:]
    rep = repart_act[DRIFT_WARMUP:]
    cmp_ = compact_act[DRIFT_WARMUP:]
    rst = compile_act[DRIFT_WARMUP:]
    keep = ~rst & ~cmp_              # steady + repartitioning steps
    steady = keep & ~rep
    steady_p99 = float(np.percentile(lat[steady], 99)) if steady.any() else 0.0
    drift_p99 = float(np.percentile(lat[keep], 99)) if keep.any() else 0.0
    return {**eng.stats(),
            "final_ratio": float(ratios[-1]),
            "max_ratio": float(ratios[DRIFT_WARMUP:].max()),
            "steady_p99_s": steady_p99,
            "drift_p99_s": drift_p99,
            "drift_p99_ratio": drift_p99 / max(steady_p99, 1e-9),
            "repart_steps": int(rep.sum()),
            "repart_kept": int((rep & keep).sum()),
            "compact_steps": int(cmp_.sum()),
            "compile_steps": int(compile_act.sum()),
            "steady_samples": int(steady.sum())}


def run_drift(scale: str = "small") -> list[dict]:
    """Drift scenario (DESIGN.md §12): frozen-partition vs online-
    repartitioning engines on an identical append/zipf-drift trace.  Gates:
    request-for-request equivalence across frozen/sync-repart/async-repart;
    the frozen engine demonstrably violates the max/min size bound; both
    repartitioning engines hold it; async-repart p99 over steady +
    repartitioning steps stays within DRIFT_P99_FLATNESS of the steady
    remainder alone (compaction and compile steps excluded from BOTH
    sides and reported — see _drive_drift)."""
    n = SCALE_N[scale] * 2 // 5   # leave >2x headroom for drifted inserts
    keys = make_dataset("covid", n)
    pays = payloads_for(keys)
    writes, steps = _drift_trace(keys, np.random.default_rng(11))

    engines = {}
    for mode, repart, async_c in (("frozen", False, True),
                                  ("repart-sync", True, False),
                                  ("repart-async", True, True)):
        part = partition_bulkload(keys, pays, NUM_SHARDS)
        eng = ShardedIndexEngine(
            part, gamma=DRIFT_GAMMA, async_compact=async_c,
            repartition=repart, split_ratio=DRIFT_SPLIT_RATIO,
            min_split_items=max(n // NUM_SHARDS // 4, 64))
        wall, out = timed(lambda e=eng: _drive_drift(e, steps),
                          warmup=0, reps=1)
        engines[mode] = (eng, *out, wall)

    # ---- gate 1: request-for-request equivalence, all three engines
    res_frozen = engines["frozen"][1]
    res_sync = engines["repart-sync"][1]
    res_async = engines["repart-async"][1]
    for step_i, (rf, rs, ra) in enumerate(
            zip(res_frozen, res_sync, res_async)):
        assert rf == rs == ra, f"engines diverged at drift step {step_i}"

    rows = []
    for mode, (eng, _, ratios, rep, cmp_, rst, wall) in engines.items():
        st = _drift_stats(eng, ratios, rep, cmp_, rst)
        rows.append({
            "engine": mode,
            "scenario": "drift",
            "shards": eng.num_shards,
            "final_ratio": round(st["final_ratio"], 2),
            "max_ratio": round(st["max_ratio"], 2),
            "splits": st["splits"],
            "merges": st["merges"],
            "drift_p99_ms": round(1e3 * st["drift_p99_s"], 2),
            "steady_p99_ms": round(1e3 * st["steady_p99_s"], 2),
            "drift_p99_ratio": round(st["drift_p99_ratio"], 2),
            "repart_steps": st["repart_steps"],
            "compact_steps": st["compact_steps"],
            "compile_steps": st["compile_steps"],
            "full_restacks": st["full_restacks"],
            "boundary_version": st["boundary_version"],
            "wall_s": round(wall, 1),
        })

    by = {r["engine"]: r for r in rows}
    print_table("Append/zipf drift: frozen vs online-repartitioning "
                "boundary table (max/min shard-size ratio, p99 flatness)",
                rows, ["engine", "shards", "final_ratio", "max_ratio",
                       "splits", "merges", "drift_p99_ms", "steady_p99_ms",
                       "drift_p99_ratio", "compact_steps", "compile_steps"])
    print(f"\nfrozen final ratio {by['frozen']['final_ratio']:.2f}x "
          f"(violates <= {DRIFT_RATIO_BOUND}); repart-async max ratio "
          f"{by['repart-async']['max_ratio']:.2f}x, p99 "
          f"{by['repart-async']['drift_p99_ratio']:.2f}x steady "
          f"(gates: <= {DRIFT_RATIO_BOUND}, <= {DRIFT_P99_FLATNESS}x)")

    # ---- gate 2: frozen partition demonstrably violates the size bound
    assert by["frozen"]["final_ratio"] > DRIFT_RATIO_BOUND, (
        "drift trace too mild: frozen engine stayed within the ratio bound")
    assert by["frozen"]["splits"] == 0 and by["frozen"]["merges"] == 0

    # ---- gate 3: repartitioning engines hold the bound, via real splits
    for mode in ("repart-sync", "repart-async"):
        assert by[mode]["max_ratio"] <= DRIFT_RATIO_BOUND, (
            f"{mode} exceeded max/min ratio {DRIFT_RATIO_BOUND}")
        assert by[mode]["splits"] >= 2, f"{mode} split fewer than 2 times"
        assert by[mode]["boundary_version"] >= 2

    # ---- gate 4: repartitioning does not disturb serving p99
    eng_async = engines["repart-async"][0]
    st_async = _drift_stats(eng_async, *engines["repart-async"][2:6])
    assert st_async["repart_kept"] >= 1, (
        "every repartitioning step coincided with compaction/restack "
        "activity — the flatness gate would compare nothing")
    assert st_async["steady_samples"] >= 8, (
        f"only {st_async['steady_samples']} steady drift steps — lengthen "
        "DRIFT_STEPS for a usable baseline")
    assert by["repart-async"]["drift_p99_ratio"] <= DRIFT_P99_FLATNESS, (
        "acceptance criterion: repartitioning p99 within "
        f"{DRIFT_P99_FLATNESS}x of steady-state p99")
    assert eng_async.stats()["repart_failures"] == 0
    return rows


def run(scale: str = "small") -> list[dict]:
    n = SCALE_N[scale]
    keys = make_dataset("covid", n)
    pays = payloads_for(keys)
    part = partition_bulkload(keys, pays, NUM_SHARDS)
    hot, steps = _trace(keys, part.bounds, np.random.default_rng(0))

    # the monolithic baseline: one shard, compacting on the request path
    mono = ShardedIndexEngine(partition_bulkload(keys, pays, 1), gamma=GAMMA,
                              async_compact=False)
    shrd = ShardedIndexEngine(part, gamma=GAMMA)

    cold = [s for s in range(shrd.num_shards) if s != hot]
    epochs_before = [(shrd.shards[s].di.journal_epoch,
                      shrd.shards[s].di.full_builds,
                      shrd.shards[s].di.refreshes) for s in range(
                          shrd.num_shards)]

    # stateful drives: one measured pass each (see common.timed)
    t_mono, r_mono = timed(lambda: _drive(mono, steps), warmup=0, reps=1)
    t_shrd, r_shrd = timed(lambda: _drive(shrd, steps), warmup=0, reps=1)

    # ---- gate 1: compactions stayed shard-local (cold mirrors keep epoch)
    assert shrd.shards[hot].compactions >= 1, "hot shard never compacted"
    for s in cold:
        assert shrd.shards[s].compactions == 0, f"cold shard {s} compacted"
        assert (shrd.shards[s].di.journal_epoch,
                shrd.shards[s].di.full_builds,
                shrd.shards[s].di.refreshes) == epochs_before[s], \
            f"cold shard {s} lost its snapshot epoch"

    # ---- gate 2: both engines answer a probe batch identically
    rng = np.random.default_rng(1)
    probes = [(mono.get(int(k)), shrd.get(int(k)))
              for k in rng.choice(keys, 256)]
    probes += [(mono.scan(int(k), SCAN_COUNT), shrd.scan(int(k), SCAN_COUNT))
               for k in rng.choice(keys, 8)]
    mono.step()
    shrd.step()
    for m, s in probes:
        assert m.result == s.result, (m.op, m.key)

    speedup = r_mono["p99_step_s"] / max(r_shrd["p99_step_s"], 1e-9)
    rows = []
    for name, r, wall in (("monolithic", r_mono, t_mono),
                          ("sharded", r_shrd, t_shrd)):
        rows.append({
            "engine": name,
            "scenario": "hot_shard",
            "shards": 1 if name == "monolithic" else shrd.num_shards,
            "p99_step_ms": round(1e3 * r["p99_step_s"], 2),
            "mean_step_ms": round(1e3 * r["mean_step_s"], 2),
            "throughput_ops_s": round(r["throughput_ops_s"], 0),
            "compactions": r["compactions"],
            "mirror_full_builds": r["mirror_full_builds"],
            "mirror_refreshes": r["mirror_refreshes"],
            "wall_s": round(wall, 1),
            "p99_speedup": round(speedup, 2) if name == "sharded" else 1.0,
        })
    print_table("Skewed mixed serving: shard-local vs whole-keyspace "
                "compaction stalls (p99 step latency)",
                rows, ["engine", "shards", "p99_step_ms", "mean_step_ms",
                       "throughput_ops_s", "compactions",
                       "mirror_full_builds", "p99_speedup"])
    print(f"\nsharded p99 speedup {speedup:.2f}x "
          f"(acceptance gate: >= 2x, compaction stalls shard-local)")
    assert speedup >= 2.0, \
        "acceptance criterion: >=2x lower p99 step latency under skew"

    rows += run_storm(scale)
    rows += run_drift(scale)
    save_results("sharded_serving", rows,
                 {"scale": scale, "num_shards": NUM_SHARDS, "gamma": GAMMA,
                  "steps": STEPS, "warmup": WARMUP,
                  "writes_per_step": WRITES_PER_STEP,
                  "gets_per_step": GETS_PER_STEP,
                  "scans_per_step": SCANS_PER_STEP,
                  "scan_count": SCAN_COUNT, "hot_shard": hot,
                  "storm_steps": STORM_STEPS, "storm_warmup": STORM_WARMUP,
                  "storm_writes_per_step": STORM_WRITES_PER_STEP,
                  "storm_p99_flatness": STORM_P99_FLATNESS,
                  "drift_steps": DRIFT_STEPS, "drift_warmup": DRIFT_WARMUP,
                  "drift_split_ratio": DRIFT_SPLIT_RATIO,
                  "drift_ratio_bound": DRIFT_RATIO_BOUND,
                  "drift_p99_flatness": DRIFT_P99_FLATNESS})
    return rows


if __name__ == "__main__":
    run()
