"""Mixed read/write serving: delta overlay vs full-mirror-rebuild-per-batch.

The failure mode this PR removes (ISSUE 2): the device mirror is an immutable
snapshot, so before the overlay existed ANY insert forced a full O(n)
``build_device_index`` before the next batched read.  Here both strategies
serve the same interleaved workload — per step, a batch of host inserts
followed by a fused device read batch — and we report the *amortized
per-insert mirror-maintenance cost*:

* ``rebuild``  — baseline: full mirror rebuild after every write batch;
* ``overlay``  — writes land in the DeltaOverlay (+ host journal); reads
  merge-consult it; the mirror is only refolded when the overlay passes
  ``gamma * n`` (compaction), via the journal fast path when no SMO occurred.

Correctness gate (the acceptance criterion): after EVERY compaction the
overlay-enabled read path must be bit-identical to a fresh full rebuild on a
probe batch (lookups and scans), which this module asserts inline.

Write-path scenario (DESIGN.md §14): a write-heavy stream drives two
one-shard ``ShardedIndexEngine`` twins — ``full_repack`` re-uploads the whole padded
overlay pack every step (the pre-merge path, ``overlay_merge=False``) while
``delta_merge`` ships only the step's sorted batch and merges it into the
device-resident pack.  Reported per step: write-path H2D bytes and host
(sort + pack) milliseconds.  Gate: at overlay fill >= 8x the write batch,
the delta path must ship >= 5x fewer bytes per step; both engines must stay
request-for-request identical, asserted inline every step.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import Aulid, DeltaOverlay
from repro.core.device_index import build_device_index, refresh_device_index
from repro.core.workloads import make_dataset, payloads_for

from .common import SCALE_N, print_table, save_results

GAMMA = 0.02
STEPS = 64
WRITES_PER_STEP = 32       # small write batches are where rebuild-per-batch
READS_PER_STEP = 2_048     # amortizes worst (the ISSUE's failure mode)
SCAN_PROBES = 64
REPEATS = 5   # best-of-N: this container's CPU timing is noisy and the
              # baseline's O(n) rebuild cost is what the gate divides by

# --- write-path scenario ---------------------------------------------------
WP_STEPS = 48
WP_BATCH = 64              # writes per step (the O(batch) the delta ships)
WP_READS = 256             # mixed traffic: reads also verify equivalence
WP_GAMMA = 0.1             # threshold > total inserts: no compaction, so the
                           # overlay fill climbs monotonically past 8x batch
WP_FILL_GATE = 8           # gate applies at fill >= WP_FILL_GATE * batch
WP_BYTES_GATE = 5.0        # delta path must ship >= 5x fewer bytes/step


def _probe_bit_identical(idx, di, ov, height, probe_q):
    """Overlay path (post-compaction: empty overlay) == fresh full rebuild."""
    import jax.numpy as jnp
    from repro.core.lookup import (device_arrays, lookup_batch,
                                   lookup_batch_overlay, overlay_arrays,
                                   scan_batch, scan_batch_overlay)
    arrs = device_arrays(di)
    ovr = overlay_arrays(ov)
    fresh = device_arrays(build_device_index(idx))
    q = jnp.asarray(probe_q)
    po, fo, lo = lookup_batch_overlay(arrs, ovr, q, height=height)
    pf, ff, lf = lookup_batch(fresh, q, height=height)
    assert (np.asarray(po) == np.asarray(pf)).all()
    assert (np.asarray(fo) == np.asarray(ff)).all()
    s = q[:SCAN_PROBES]
    ko, qo, vo = scan_batch_overlay(arrs, ovr, s, count=32, height=height)
    kf, qf_, vf = scan_batch(fresh, s, count=32, height=height)
    vo, vf = np.asarray(vo), np.asarray(vf)
    assert (vo == vf).all()
    assert (np.asarray(ko)[vo] == np.asarray(kf)[vf]).all()
    assert (np.asarray(qo)[vo] == np.asarray(qf_)[vf]).all()


def _run_mode(mode: str, keys: np.ndarray, inserts: np.ndarray,
              read_pool: np.ndarray) -> dict:
    import jax.numpy as jnp
    from repro.core.lookup import (device_arrays, lookup_batch,
                                   lookup_batch_overlay, overlay_arrays,
                                   update_leaf_rows)
    idx = Aulid()
    idx.bulkload(keys, payloads_for(keys))
    di = build_device_index(idx)
    arrs = device_arrays(di)
    ov = DeltaOverlay.for_threshold(GAMMA * idx.n_items)
    ovr = overlay_arrays(ov)
    height = max(di.max_inner_height, 3)
    rng = np.random.default_rng(0)

    maintain_s = 0.0     # mirror rebuild/refresh + overlay materialization
    read_s = 0.0
    n_inserts = 0
    compactions = 0
    wi = 0
    for _ in range(STEPS):
        # -- write batch (host structure mutation is common to both modes)
        batch = inserts[wi: wi + WRITES_PER_STEP]
        wi += WRITES_PER_STEP
        for k in batch:
            idx.insert(int(k), int(k) + 3)
            if mode == "overlay":
                ov.record_insert(int(k), int(k) + 3)
        n_inserts += len(batch)
        # -- mirror maintenance
        t0 = time.perf_counter()
        if mode == "rebuild":
            di = build_device_index(idx)
            arrs = device_arrays(di)
            height = max(di.max_inner_height, 3)
        else:
            if len(ov) >= GAMMA * idx.n_items:
                old = di
                di = refresh_device_index(idx, di)
                arrs = (update_leaf_rows(arrs, di) if di is old
                        else device_arrays(di))
                height = max(di.max_inner_height, 3)
                ov.clear()
                compactions += 1
                maintain_s += time.perf_counter() - t0
                _probe_bit_identical(idx, di, ov, height,
                                     rng.choice(inserts[:wi], 512)
                                     .astype(np.uint64))
                t0 = time.perf_counter()
            ovr = overlay_arrays(ov)
        maintain_s += time.perf_counter() - t0
        # -- fused read batch
        q = jnp.asarray(np.concatenate(
            [rng.choice(read_pool, READS_PER_STEP - len(batch)),
             batch]).astype(np.uint64))
        t0 = time.perf_counter()
        if mode == "rebuild":
            pay, found, _ = lookup_batch(arrs, q, height=height)
        else:
            pay, found, _ = lookup_batch_overlay(arrs, ovr, q, height=height)
        pay.block_until_ready()
        read_s += time.perf_counter() - t0
        assert bool(np.asarray(found)[-len(batch):].all()), \
            "freshly inserted keys must be visible to the next read batch"
    return {"mode": mode, "maintain_s": maintain_s, "read_s": read_s,
            "inserts": n_inserts, "compactions": compactions,
            "amortized_us_per_insert": 1e6 * maintain_s / n_inserts}


def _write_path_rows(scale: str) -> tuple[list[dict], dict]:
    """Write-heavy twin run: per-step H2D bytes + host ms, full-repack vs
    delta-merge, request-for-request equivalence asserted every step."""
    from repro.core import partition_bulkload
    from repro.serving import ShardedIndexEngine
    n = SCALE_N[scale]
    keys = make_dataset("covid", n)
    rng = np.random.default_rng(2)
    inserts = np.unique(rng.integers(0, 2**50, WP_STEPS * WP_BATCH * 2)
                        .astype(np.uint64))
    rng.shuffle(inserts)
    inserts = inserts[: WP_STEPS * WP_BATCH]
    assert WP_STEPS * WP_BATCH < WP_GAMMA * n, \
        "write-path scenario must not compact (fill must climb past the gate)"

    def build(merge: bool) -> "ShardedIndexEngine":
        part = partition_bulkload(keys, payloads_for(keys), 1)
        return ShardedIndexEngine(part, gamma=WP_GAMMA, backend="jnp",
                                  async_compact=False, overlay_merge=merge)

    engines = {"delta_merge": build(True), "full_repack": build(False)}
    trace = {m: [] for m in engines}     # (fill_before, d_bytes, d_host_s)
    wi = 0
    for step in range(WP_STEPS):
        batch = inserts[wi: wi + WP_BATCH]
        wi += WP_BATCH
        probes = np.concatenate(
            [rng.choice(keys, WP_READS - len(batch)), batch])
        results = {}
        for mode, eng in engines.items():
            fill = eng.shards[0].overlay_live()
            s0 = eng.stats()
            for k in batch:
                eng.insert(int(k), int(k) + 3)
            reqs = [eng.get(int(k)) for k in probes]
            eng.step()
            s1 = eng.stats()
            trace[mode].append((fill,
                                s1["write_h2d_bytes"] - s0["write_h2d_bytes"],
                                s1["write_host_s"] - s0["write_host_s"]))
            results[mode] = [r.result for r in reqs]
        assert results["delta_merge"] == results["full_repack"], \
            f"write-path engines diverged at step {step}"

    # the gate applies where the old path's pain is: overlay fill well past
    # the batch size, so a full re-upload moves >> O(batch) bytes
    gate_steps = [i for i, (fill, _, _) in enumerate(trace["delta_merge"])
                  if fill >= WP_FILL_GATE * WP_BATCH]
    assert gate_steps, "scenario too short to reach the fill gate"
    rows = []
    mean = lambda xs: float(np.mean(xs)) if xs else 0.0
    per_mode = {}
    for mode, eng in engines.items():
        tr = trace[mode]
        s = eng.stats()
        per_mode[mode] = {
            "h2d_bytes_per_step": mean([tr[i][1] for i in gate_steps]),
            "host_ms_per_step": 1e3 * mean([tr[i][2] for i in gate_steps]),
        }
        rows.append({
            "dataset": "covid", "scenario": "write_path", "mode": mode,
            "steps": WP_STEPS, "batch": WP_BATCH,
            "gate_steps": len(gate_steps),
            "h2d_bytes_per_step": round(per_mode[mode]["h2d_bytes_per_step"]),
            "host_ms_per_step": round(per_mode[mode]["host_ms_per_step"], 3),
            "total_h2d_bytes": int(s["write_h2d_bytes"]),
            "overlay_fill_final": int(eng.shards[0].overlay_live()),
            "overlay_merges": s["overlay_merges"],
            "overlay_reseeds": s["overlay_reseeds"],
        })
    ratio = (per_mode["full_repack"]["h2d_bytes_per_step"]
             / max(per_mode["delta_merge"]["h2d_bytes_per_step"], 1.0))
    for r in rows:
        r["bytes_ratio_vs_full_repack"] = (round(ratio, 1)
                                           if r["mode"] == "delta_merge"
                                           else 1.0)
    meta = {"steps": WP_STEPS, "batch": WP_BATCH, "reads": WP_READS,
            "gamma": WP_GAMMA, "fill_gate_x_batch": WP_FILL_GATE,
            "gate_min_ratio": WP_BYTES_GATE,
            "bytes_ratio": round(ratio, 1)}
    return rows, meta


def run(scale: str = "small") -> list[dict]:
    n = SCALE_N[scale]
    rows = []
    for dataset in ("covid", "osm"):
        keys = make_dataset(dataset, n)
        rng = np.random.default_rng(1)
        inserts = np.unique(rng.integers(0, 2**50, STEPS * WRITES_PER_STEP * 2)
                            .astype(np.uint64))
        rng.shuffle(inserts)
        inserts = inserts[: STEPS * WRITES_PER_STEP]
        base = min((_run_mode("rebuild", keys, inserts, keys)
                    for _ in range(REPEATS)),
                   key=lambda r: r["amortized_us_per_insert"])
        ovl = min((_run_mode("overlay", keys, inserts, keys)
                   for _ in range(REPEATS)),
                  key=lambda r: r["amortized_us_per_insert"])
        speedup = (base["amortized_us_per_insert"]
                   / max(ovl["amortized_us_per_insert"], 1e-9))
        for r in (base, ovl):
            rows.append({"dataset": dataset, **{k: (round(v, 2)
                        if isinstance(v, float) else v) for k, v in r.items()},
                        "speedup_vs_rebuild": round(speedup, 1)
                        if r is ovl else 1.0})
    wp_rows, wp_meta = _write_path_rows(scale)
    save_results("mixed_serving", rows + wp_rows,
                 {"scale": scale, "gamma": GAMMA, "steps": STEPS,
                  "writes_per_step": WRITES_PER_STEP,
                  "reads_per_step": READS_PER_STEP,
                  "write_path": wp_meta})
    print_table("Mixed read/write serving: amortized mirror-maintenance cost "
                "per insert (overlay vs full rebuild per write batch)",
                rows, ["dataset", "mode", "inserts", "compactions",
                       "amortized_us_per_insert", "read_s",
                       "speedup_vs_rebuild"])
    print_table("Write path: per-step H2D bytes + host ms at overlay fill "
                f">= {WP_FILL_GATE}x batch (full repack vs delta merge)",
                wp_rows, ["mode", "steps", "batch", "gate_steps",
                          "h2d_bytes_per_step", "host_ms_per_step",
                          "total_h2d_bytes", "overlay_fill_final",
                          "overlay_merges", "overlay_reseeds",
                          "bytes_ratio_vs_full_repack"])
    sp = [r["speedup_vs_rebuild"] for r in rows if r["mode"] == "overlay"]
    geomean = float(np.prod(sp)) ** (1.0 / len(sp))
    print(f"\noverlay speedups {sp}, geometric mean {geomean:.1f}x "
          f"(acceptance gate: >= 5x)")
    assert geomean >= 5.0, \
        "acceptance criterion: >=5x lower amortized per-insert cost"
    ratio = wp_meta["bytes_ratio"]
    print(f"write-path H2D bytes/step ratio (full repack / delta merge) "
          f"{ratio}x (acceptance gate: >= {WP_BYTES_GATE}x)")
    assert ratio >= WP_BYTES_GATE, \
        "acceptance criterion: >=5x lower per-step write-path H2D bytes"
    return rows + wp_rows


if __name__ == "__main__":
    run()
