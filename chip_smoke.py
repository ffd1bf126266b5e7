#!/usr/bin/env python3
"""Bring-up smoke run of the sharded AULID serving engine on a TPU.

Drives the engine's main path once through its public API, at a
deployment's size, and checks every answer:

1. load — the paper's OSM-like key distribution (``make_dataset("osm")``,
   200M keys by default) from ``--seed``, bulkloaded into 16 range shards
   (``partition_bulkload``, default ``AulidConfig``) behind a
   ``ShardedIndexEngine`` with its defaults;
2. traffic — a few dozen steps of seeded get (hits and misses) / upsert /
   insert / delete / scan (1-100 keys) traffic, with a write burst on one
   shard that crosses its gamma*n threshold, so a compaction builds and
   swaps in under load; after every step each answer is compared with a
   plain reference (the sorted key and payload arrays plus a dict of the
   acknowledged writes);
3. kernels — both Pallas kernels compiled, at the largest geometry the
   tuner admits, compared bit for bit with the jnp path on the same
   operands.

    python chip_smoke.py               # one chip: phases 1-3
    python chip_smoke.py --chips 4     # the mesh engine over four chips:
                                       # phases 1-2 only

Earlier lines report each phase; the last line of standard output is one
JSON object naming the device.  The run exits nonzero, printing no result,
when JAX finds no TPU or the repository is not next to this file, and when
any answer differed from the reference, no compaction swapped in, or a
compaction build failed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

NUM_SHARDS = 16
DEFAULT_KEYS = 200_000_000      # the paper's smallest dataset (DESIGN.md §8)
MIN_KEYS = 100_000_000
# host bytes per loaded key: the peak RSS of a 20M-key load on the CPU
# backend (dataset + payloads, host index leaves and blocks, shard mirrors,
# the padded stack) was 99 B per key; 10% headroom
HOST_BYTES_PER_KEY = 110
GETS_PER_STEP = 2048
SCANS_PER_BUCKET = 8
# scan lengths 1-100 in the engine's pow2 buckets (one compile per bucket)
SCAN_BUCKETS = ((1, 8), (9, 16), (17, 32), (33, 64), (65, 100))
BURST_WRITES = 1 << 17
UMAX = (1 << 64) - 1


def report(phase: str, **kw) -> None:
    print(f"{phase}: {json.dumps(kw, sort_keys=True, default=str)}",
          flush=True)


def host_rss_gb() -> dict:
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS:", "VmHWM:")):
                k, v = line.split(":")
                out[k] = round(int(v.split()[0]) / 2 ** 20, 2)
    return out


def mem_available_bytes() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("no MemAvailable in /proc/meminfo")


def device_memory(devices) -> list:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append({"device": d.id,
                    "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


# ------------------------------------------------------------------ reference
class Reference:
    """The plain reference: the loaded sorted key/payload arrays plus a dict
    of acknowledged writes (payload, or None for a delete)."""

    def __init__(self, keys: np.ndarray, pays: np.ndarray):
        self.keys = keys
        self.pays = pays
        self.writes: dict[int, int | None] = {}
        self._wkeys: np.ndarray | None = None

    def _base(self, k: int) -> int | None:
        i = int(np.searchsorted(self.keys, np.uint64(k)))
        if i < len(self.keys) and int(self.keys[i]) == k:
            return int(self.pays[i])
        return None

    def get(self, k: int) -> int | None:
        return self.writes[k] if k in self.writes else self._base(k)

    def insert(self, k: int, p: int) -> bool:
        self.writes[k] = p
        self._wkeys = None
        return True

    def delete(self, k: int) -> bool:
        existed = self.get(k) is not None
        self.writes[k] = None
        self._wkeys = None
        return existed

    def scan(self, k: int, count: int) -> list[tuple[int, int]]:
        """Two-way merge of base keys >= k (unless overwritten) with the
        written keys >= k (unless deleted)."""
        if self._wkeys is None:
            self._wkeys = np.sort(np.fromiter(self.writes, dtype=np.uint64,
                                              count=len(self.writes)))
        keys, wk = self.keys, self._wkeys
        i = int(np.searchsorted(keys, np.uint64(k)))
        j = int(np.searchsorted(wk, np.uint64(k)))
        out: list[tuple[int, int]] = []
        while len(out) < count and (i < len(keys) or j < len(wk)):
            kb = int(keys[i]) if i < len(keys) else None
            kw = int(wk[j]) if j < len(wk) else None
            if kw is not None and (kb is None or kw <= kb):
                i += kb == kw
                j += 1
                if self.writes[kw] is not None:
                    out.append((kw, self.writes[kw]))
            else:
                out.append((kb, int(self.pays[i])))
                i += 1
        return out


# ----------------------------------------------------------------------- load
def load(n_keys: int, seed: int, mesh=None):
    """Phase 1: dataset, partition bulkload, engine build (timed)."""
    from repro.core import partition_bulkload
    from repro.core.workloads import make_dataset, payloads_for
    from repro.serving import ShardedIndexEngine
    t0 = time.perf_counter()
    keys = make_dataset("osm", n_keys, seed=seed).copy()
    pays = payloads_for(keys)
    t1 = time.perf_counter()
    part = partition_bulkload(keys, pays, NUM_SHARDS)
    t2 = time.perf_counter()
    eng = ShardedIndexEngine(part, mesh=mesh)
    t3 = time.perf_counter()
    timing = {"make_dataset_s": t1 - t0, "partition_bulkload_s": t2 - t1,
              "engine_build_s": t3 - t2}
    return keys, pays, part, eng, timing


# -------------------------------------------------------------------- traffic
class Traffic:
    """Seeded request mix over the loaded keys and the keys it wrote."""

    def __init__(self, ref: Reference, bounds: np.ndarray, seed: int):
        self.ref = ref
        self.bounds = np.asarray(bounds, dtype=np.uint64)
        self.rng = np.random.default_rng(seed + 1)
        self.fresh: list[int] = []

    def base_key(self) -> int:
        return int(self.ref.keys[self.rng.integers(len(self.ref.keys))])

    def fresh_key(self) -> int:
        while True:
            k = int(self.rng.integers(0, 1 << 62, dtype=np.uint64))
            if self.ref.get(k) is None and k != UMAX:
                self.fresh.append(k)
                return k

    def written_key(self) -> int:
        if self.fresh and self.rng.random() < 0.5:
            return self.fresh[int(self.rng.integers(len(self.fresh)))]
        return self.base_key()

    def gets(self, n: int) -> list:
        ops = []
        for i in range(n):
            r = i % 4
            k = (self.base_key() if r == 0 else self.written_key() if r == 1
                 else int(self.rng.integers(0, 1 << 62, dtype=np.uint64)))
            ops.append(("get", k, 0))
        return ops

    def writes(self, n_upsert: int, n_insert: int, n_delete: int) -> list:
        ops = [("upsert", self.base_key(), int(self.rng.integers(1 << 62)))
               for _ in range(n_upsert)]
        ops += [("insert", self.fresh_key(), int(self.rng.integers(1 << 62)))
                for _ in range(n_insert)]
        ops += [("delete", self.written_key(), 0) for _ in range(n_delete)]
        return ops

    def scans(self) -> list:
        ops = []
        for lo, hi in SCAN_BUCKETS:
            for _ in range(SCANS_PER_BUCKET):
                k = (self.base_key() if self.rng.random() < 0.5 else
                     int(self.rng.integers(0, 1 << 62, dtype=np.uint64)))
                ops.append(("scan", k, int(self.rng.integers(lo, hi + 1))))
        return ops

    def burst(self, shard: int, n: int) -> list:
        """Fresh inserts inside one shard's key range."""
        lo = int(self.bounds[shard - 1]) + 1 if shard > 0 else 0
        hi = int(self.bounds[shard]) if shard < len(self.bounds) else 1 << 62
        keys = self.ref.keys
        ks = np.unique(self.rng.integers(lo, hi, 2 * n, dtype=np.uint64))
        i = np.minimum(np.searchsorted(keys, ks), len(keys) - 1)
        ks = ks[keys[i] != ks]
        ks = [int(k) for k in self.rng.permutation(ks)
              if int(k) not in self.ref.writes][:n]
        self.fresh.extend(ks)
        return [("insert", k, k ^ 0x5DEECE66D) for k in ks]


def serve_step(eng, ref: Reference, ops: list, stats: dict) -> int:
    """Submit ``ops`` as one engine step, then replay them on the reference
    (writes in order, then reads: the engine's step-level consistency) and
    count answers that differ."""
    reqs = []
    for op, k, x in ops:
        if op == "get":
            reqs.append(eng.get(k))
        elif op in ("insert", "upsert"):
            reqs.append(eng.insert(k, x))
        elif op == "delete":
            reqs.append(eng.delete(k))
        else:
            reqs.append(eng.scan(k, x))
    eng.step()
    want = [None] * len(ops)
    for i, (op, k, x) in enumerate(ops):
        if op in ("insert", "upsert"):
            want[i] = ref.insert(k, x)
        elif op == "delete":
            want[i] = ref.delete(k)
    for i, (op, k, x) in enumerate(ops):
        if op == "get":
            want[i] = ref.get(k)
        elif op == "scan":
            want[i] = ref.scan(k, x)
    bad = 0
    for (op, _, _), r, w in zip(ops, reqs, want):
        stats[op] = stats.get(op, 0) + 1
        if not r.done or r.result != w:
            bad += 1
    return bad


def run_traffic(eng, ref: Reference, seed: int, *, mixed_steps: int = 12,
                after_steps: int = 8, burst_writes: int = BURST_WRITES,
                max_burst_steps: int = 12, gets_per_step: int = GETS_PER_STEP,
                log=report) -> dict:
    """Phase 2.  Scans run only while the overlay is small (mixed phases):
    the scan merge widens its window by the live overlay bound, so the
    burst steps carry gets and writes only."""
    tr = Traffic(ref, eng.part.bounds, seed)
    ops_count: dict[str, int] = {}
    mism = 0
    step_s = []

    def step(ops):
        nonlocal mism
        t = time.perf_counter()
        mism += serve_step(eng, ref, ops, ops_count)
        step_s.append(time.perf_counter() - t)

    # mixed: one larger write batch, then small ones; the live overlay stays
    # under one pow2 bound, so the scans compile once per bucket
    for i in range(mixed_steps):
        w = tr.writes(288, 288, 96) if i == 0 else tr.writes(4, 4, 4)
        step(w + tr.gets(gets_per_step) + (tr.scans() if i else []))
    first_step_s = step_s[0]
    log("mixed", steps=mixed_steps, mismatches=mism,
        first_step_s=first_step_s,
        median_step_s=float(np.median(step_s[1:])) if mixed_steps > 1
        else None)

    # burst: fresh inserts into one shard until it crosses gamma*n
    shard = NUM_SHARDS // 3 if eng.num_shards > 1 else 0
    c0 = eng.compactions
    burst_steps = 0
    while eng.compactions == c0 and burst_steps < max_burst_steps:
        step(tr.burst(shard, burst_writes) + tr.gets(gets_per_step))
        burst_steps += 1
    log("burst", shard=shard, steps=burst_steps, mismatches=mism,
        compactions=eng.compactions, shard_items=eng.part.shards[shard].n_items)

    # after: gets and small writes until the build swaps in, then mixed
    # traffic with scans on the new epoch
    polls = 0
    while eng.swaps + eng.failed_swaps == 0 and polls < 4:
        step(tr.writes(2, 2, 2) + tr.gets(gets_per_step))
        polls += 1
    if eng.swaps + eng.failed_swaps == 0:
        eng.drain_compactions()
    for _ in range(after_steps):
        step(tr.writes(4, 4, 4) + tr.gets(gets_per_step) + tr.scans())
    st = eng.stats()
    out = {"steps": len(step_s), "mismatches": mism, "ops": ops_count,
           "compactions": st["compactions"], "swaps": st["swaps"],
           "failed_swaps": st["failed_swaps"],
           "read_backend": st["read_backend"],
           "first_step_s": first_step_s,
           "median_step_s": float(np.median(step_s)),
           "read_shape_misses": st["read_shape_misses"]}
    log("after", polls=polls, **out)
    return out


# -------------------------------------------------------------------- kernels
KERNEL_SIZES = tuple(65_536 * m for m in (1, 2, 3, 4, 6, 8, 12, 16))
OVERLAY_CHECK_CAP = 1024


def _largest_admitted(seed: int, sizes, vmem_budget: int) -> dict:
    """Per leaf strategy, the largest key count of ``sizes`` whose mirror
    geometry the tuner admits for the compiled kernel with that strategy:
    leaf -> (keys, mirror, geometry, strategy)."""
    from repro.core import Aulid
    from repro.core.device_index import build_device_index
    from repro.core.workloads import make_dataset, payloads_for
    from repro.kernels.fused_lookup import tuning
    best = {}
    for n in sorted(sizes):
        keys = make_dataset("osm", n, seed=seed)
        idx = Aulid()
        idx.bulkload(keys, payloads_for(keys))
        di = build_device_index(idx)
        geom = tuning.PoolGeometry.from_pools(
            di.pool_geometry(), overlay_bucket=OVERLAY_CHECK_CAP)
        try:
            st = tuning.choose_strategy(geom, interpret=False,
                                        vmem_budget=vmem_budget)
        except ValueError:
            continue
        best[st.leaf] = (keys, di, geom, st)
    return best


def fused_kernel_check(seed: int, *, interpret: bool = False,
                       sizes=KERNEL_SIZES, vmem_budget: int | None = None,
                       log=report) -> int:
    """The fused lookup kernel against the jnp path, per leaf strategy at
    the largest admitted geometry; returns the number of differing answers
    (the jnp answers are also checked against the host reference)."""
    import jax.numpy as jnp
    from repro.core import DeltaOverlay
    from repro.core.lookup import (device_arrays, lookup_batch_overlay,
                                   overlay_arrays)
    from repro.kernels.fused_lookup import fused_lookup_batch_overlay
    from repro.kernels.fused_lookup import tuning
    best = _largest_admitted(seed, sizes,
                             vmem_budget or tuning.DEFAULT_VMEM_BUDGET)
    bad_total = 0
    for leaf in ("persistent", "looped"):
        if leaf not in best:
            log("fused_kernel", leaf=leaf, error="no admitted geometry")
            bad_total += 1
            continue
        keys, di, geom, st = best.pop(leaf)
        rng = np.random.default_rng(seed + 2)
        ov = DeltaOverlay()
        writes: dict[int, int | None] = {}
        for k in rng.choice(keys, 200):                 # upserts
            writes[int(k)] = int(k) ^ 0xABC
        for k in rng.integers(0, 1 << 62, 200, dtype=np.uint64):  # fresh
            writes[int(k)] = int(k) ^ 0xDEF
        for k in rng.choice(keys, 100):                 # tombstones
            writes[int(k)] = None
        for k, p in writes.items():
            if p is None:
                ov.record_delete(k)
            else:
                ov.record_insert(k, p)
        arrs = device_arrays(di)
        ovr = overlay_arrays(ov)
        q = np.concatenate([rng.choice(keys, 2048),
                            np.fromiter(writes, dtype=np.uint64),
                            rng.integers(0, 1 << 62, 1024, dtype=np.uint64)])
        h = max(di.max_inner_height, 3)
        qd = jnp.asarray(q)
        t = time.perf_counter()
        kp, kf, kl = map(np.asarray, fused_lookup_batch_overlay(
            arrs, ovr, qd, height=h, interpret=interpret, strategy=st))
        first_s = time.perf_counter() - t
        jp, jf, jl = map(np.asarray,
                         lookup_batch_overlay(arrs, ovr, qd, height=h))
        diff = int(np.sum((kp != jp) | (kf != jf) | (kl != jl)))
        i = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
        want_f = keys[i] == q
        want_p = np.where(want_f, keys[i] + np.uint64(1), np.uint64(0))
        for j, k in enumerate(q.tolist()):
            if k in writes:
                want_f[j] = writes[k] is not None
                want_p[j] = writes[k] if writes[k] is not None else 0
        ref_bad = int(np.sum((jf != want_f) | (jp != want_p)))
        bad_total += diff + ref_bad
        log("fused_kernel", leaf=leaf, strategy=st.describe(),
            keys=int(keys.size), geometry=geom.__dict__,
            overlay_cap=int(ovr["ov_pack"].shape[1]), queries=int(q.size),
            differ_from_jnp=diff, jnp_differ_from_reference=ref_bad,
            found=int(kf.sum()), compile_and_first_call_s=first_s,
            interpret=interpret)
    return bad_total


def _pack(rng, keys: np.ndarray, cap: int) -> np.ndarray:
    """Overlay-layout (3, cap) pack of sorted unique ``keys`` with random
    payloads and tombstones, u64-max padded."""
    pack = np.zeros((3, cap), dtype=np.uint64)
    pack[0] = np.uint64(UMAX)
    m = keys.size
    pack[0, :m] = keys
    pack[1, :m] = rng.integers(0, 1 << 62, m, dtype=np.uint64)
    pack[2, :m] = rng.random(m) < 0.2
    return pack


def merge_kernel_check(seed: int, *, interpret: bool = False,
                       shapes=((1, 4096, 256), (4, 4096, 256)),
                       log=report) -> int:
    """The overlay-merge kernel against ``merge_overlay_pack_jnp`` (the
    oracle) on stacked packs, 3/4 full, with batches that half overwrite
    overlay keys; returns the number of differing packs."""
    import jax.numpy as jnp
    from repro.core.lookup import merge_overlay_pack_jnp
    from repro.kernels.overlay_merge import overlay_merge_pack_stacked
    rng = np.random.default_rng(seed + 3)
    bad = 0
    for S, ca, cb in shapes:
        packs, batches = [], []
        for _ in range(S):
            a = np.unique(rng.integers(0, 1 << 62, (3 * ca) // 4,
                                       dtype=np.uint64))
            b = np.unique(np.concatenate([
                rng.choice(a, cb // 2, replace=False),
                rng.integers(0, 1 << 62, cb // 2, dtype=np.uint64)]))
            packs.append(_pack(rng, a, ca))
            batches.append(_pack(rng, b, cb))
        packs, batches = np.stack(packs), np.stack(batches)
        t = time.perf_counter()
        got = np.asarray(overlay_merge_pack_stacked(
            packs, batches, ca, interpret=interpret))
        first_s = time.perf_counter() - t
        want = np.stack([np.asarray(merge_overlay_pack_jnp(
            jnp.asarray(p), jnp.asarray(b), ca))
            for p, b in zip(packs, batches)])
        diff = int(np.sum(np.any(got != want, axis=(1, 2))))
        bad += diff
        log("merge_kernel", shards=S, overlay_cap=ca, batch_cap=cb,
            cap_out=ca, differing_packs=diff,
            compile_and_first_call_s=first_s, interpret=interpret)
    return bad


# ----------------------------------------------------------------------- main
def choose_keys(requested: int, log=report) -> int:
    """The key count this host can load: ``requested`` unless its estimated
    host footprint exceeds 85% of the memory available now, then the
    largest count that fits, never below ``MIN_KEYS``."""
    avail = mem_available_bytes()
    fit = int(0.85 * avail / HOST_BYTES_PER_KEY)
    if requested <= fit:
        return requested
    n = max(MIN_KEYS, fit)
    log("cut", requested_keys=requested, keys=n,
        reason=f"host memory: {avail / 2**30:.1f} GiB available, about "
               f"{HOST_BYTES_PER_KEY} B per key to load")
    return n


def smoke(devices, *, chips: int, keys: int, seed: int,
          burst_writes: int = BURST_WRITES,
          gets_per_step: int = GETS_PER_STEP, kernel_sizes=KERNEL_SIZES,
          vmem_budget: int | None = None,
          merge_shapes=((1, 4096, 256), (4, 4096, 256)),
          interpret: bool = False) -> bool:
    """Phases 1-3 on ``devices`` (phase 3 only with one chip); True when
    every check held.  The keyword sizes exist for the CPU rehearsal in
    ``tests/test_chip_smoke.py``; a chip run uses the defaults."""
    mesh = None
    if chips > 1:
        from repro.parallel import index_mesh
        mesh = index_mesh(chips, devices=devices)
    used = devices[:chips]
    keys_, pays, part, eng, timing = load(keys, seed, mesh=mesh)
    report("load", keys=int(keys_.size), shards=eng.num_shards,
           mesh_devices=eng.stats()["mesh_devices"], **timing,
           pool_geometry=eng.sdi.pool_geometry(),
           overlay_cap=int(eng.ov_arrs["ov_pack"].shape[1]),
           device_memory=device_memory(used), host_rss_gb=host_rss_gb())
    ref = Reference(keys_, pays)
    res = run_traffic(eng, ref, seed, burst_writes=burst_writes,
                      gets_per_step=gets_per_step)
    ok = (res["mismatches"] == 0 and res["compactions"] >= 1
          and res["swaps"] >= 1 and res["failed_swaps"] == 0)
    report("serving", ok=ok, read_backend=res["read_backend"],
           mismatches=res["mismatches"], ops=res["ops"],
           compactions=res["compactions"], swaps=res["swaps"],
           failed_swaps=res["failed_swaps"],
           device_memory=device_memory(used), host_rss_gb=host_rss_gb())
    if chips == 1:
        del eng, part
        bad = (fused_kernel_check(seed, interpret=interpret,
                                  sizes=kernel_sizes,
                                  vmem_budget=vmem_budget)
               + merge_kernel_check(seed, interpret=interpret,
                                    shapes=merge_shapes))
        report("kernels", ok=bad == 0, differing=bad)
        ok = ok and bad == 0
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository next to {Path(__file__).name} "
              f"({SRC} missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    report("device", platform=devices[0].platform,
           kind=devices[0].device_kind, count=len(devices),
           compile_cache=cache, host_rss_gb=host_rss_gb(),
           host_mem_available_gb=round(mem_available_bytes() / 2 ** 30, 2))
    ok = smoke(devices, chips=args.chips, keys=choose_keys(args.keys),
               seed=args.seed)
    if not ok:
        print("chip_smoke: FAILED (see the phase lines above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
