"""Benchmark orchestrator: one module per paper table/figure + the TPU
adaptation benches + the roofline reader.

  PYTHONPATH=src python -m benchmarks.run [--module NAME] [--scale small|paper]

Scale note: 'small' (60k keys, 512 B blocks) reproduces the paper's
tree-height regime and relative ranks in minutes on one CPU core; 'paper'
(200k keys) tightens the match at ~4x the time. See benchmarks/common.py.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
import traceback

MODULES = ["workloads", "bulkload", "tail_latency", "scalability",
           "design_read_opts", "design_structures", "adjust_study",
           "device_lookup", "mixed_serving", "sharded_serving",
           "multi_device_serving", "roofline"]

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# Registry of `BENCH_serving.json` sections: section name -> the benchmark
# module that emits it.  Every written section carries its own
# {"emitter", "generated"} stamp; a carried-over section is PRUNED when its
# recorded emitter no longer matches this registry or is no longer in
# MODULES — previously, sections from deleted/renamed benchmarks survived
# in the snapshot forever, presenting dead numbers as current.
SERVING_SECTIONS = {
    "engines": "sharded_serving",
    "compaction_storm": "sharded_serving",
    "drift": "sharded_serving",
    "device_lookup": "device_lookup",
    "mixed_serving": "mixed_serving",
    "write_path": "mixed_serving",
    "multi_device": "multi_device_serving",
}


def _check_section(name: str, sec: dict) -> dict:
    """A freshly built section must carry measured results, not just its
    stamp + run parameters: a meta-only section (the bug this guards
    against: `mixed_serving` once emitted {emitter, generated, meta} and
    presented a parameter echo as benchmark output) is a collation bug in
    THIS file and fails loudly rather than shipping."""
    payload = {k: v for k, v in sec.items()
               if k not in ("emitter", "generated", "meta") and v}
    if not payload:
        raise ValueError(
            f"emit_bench_serving: section {name!r} has no result payload "
            f"beyond emitter/generated/meta — the emitter dropped its rows")
    return sec


def emit_bench_serving(fresh: set[str] | None = None) -> pathlib.Path | None:
    """Collate the serving benchmarks' saved rows into one machine-readable
    `BENCH_serving.json` at the repo root: per-engine throughput, p99 step
    latency, compaction counts (monolithic vs sharded), the compaction-storm
    flatness numbers (sync vs double-buffered, DESIGN.md §11), the drift
    scenario (frozen vs online-repartitioning boundary table, DESIGN.md
    §12), the device read path (jnp vs fused Pallas kernel, per-geometry
    tuning choice), the mixed read/write amortized-insert numbers, and the
    multi-device mesh serving scaling (DESIGN.md §13), so the serving perf
    trajectory accumulates across PRs.

    Sections merge, never fork: only the sections whose source module ran
    fresh in THIS invocation (``fresh``) are rebuilt — the others carry over
    with their own per-section `emitter`/`generated` stamps intact, so
    leftover rows from an old run are never re-stamped as current, and
    sections orphaned by a deleted or renamed benchmark (or lacking a stamp
    entirely, e.g. from the pre-stamp file format) are dropped."""
    from .common import RESULTS_DIR
    out = REPO_ROOT / "BENCH_serving.json"
    sections: dict[str, dict] = {}
    if out.exists():
        try:
            prev = json.loads(out.read_text())
        except ValueError:
            prev = {}
        for name, sec in prev.get("sections", {}).items():
            if not isinstance(sec, dict):
                continue
            emitter = sec.get("emitter")
            if SERVING_SECTIONS.get(name) == emitter and emitter in MODULES:
                sections[name] = sec
    if fresh is None:
        fresh = set(SERVING_SECTIONS.values())
    stamp = time.strftime("%Y-%m-%d %H:%M:%S")
    changed = False

    def load(mod: str):
        p = RESULTS_DIR / f"{mod}.json"
        if mod not in fresh or not p.exists():
            return None
        return json.loads(p.read_text())

    data = load("sharded_serving")
    if data is not None:
        rows = data.get("rows", [])
        hot = [r for r in rows if r.get("scenario", "hot_shard") == "hot_shard"]
        storm = [r for r in rows if r.get("scenario") == "storm"]
        drift = [r for r in rows if r.get("scenario") == "drift"]
        sections["engines"] = {
            "emitter": "sharded_serving", "generated": stamp,
            "meta": data.get("meta", {}),
            "engines": {row["engine"]: {
                "shards": row.get("shards", 1),
                "throughput_ops_s": row.get("throughput_ops_s"),
                "p99_step_ms": row.get("p99_step_ms"),
                "mean_step_ms": row.get("mean_step_ms"),
                "compactions": row.get("compactions"),
                "mirror_full_builds": row.get("mirror_full_builds"),
                "mirror_refreshes": row.get("mirror_refreshes"),
                "p99_speedup_vs_monolithic": row.get("p99_speedup"),
            } for row in hot},
        }
        if storm:
            sections["compaction_storm"] = {
                "emitter": "sharded_serving", "generated": stamp,
                "p99_flatness_gate":
                    data.get("meta", {}).get("storm_p99_flatness"),
                "engines": {row["engine"]: {
                    "steady_p99_ms": row.get("steady_p99_ms"),
                    "storm_p99_ms": row.get("storm_p99_ms"),
                    "storm_ratio": row.get("storm_ratio"),
                    "storm_steps": row.get("storm_steps"),
                    "compactions": row.get("compactions"),
                    "swaps": row.get("swaps"),
                    "full_restacks": row.get("full_restacks"),
                } for row in storm},
            }
        if drift:
            meta = data.get("meta", {})
            sections["drift"] = {
                "emitter": "sharded_serving", "generated": stamp,
                "ratio_bound_gate": meta.get("drift_ratio_bound"),
                "p99_flatness_gate": meta.get("drift_p99_flatness"),
                "engines": {row["engine"]: {
                    "shards": row.get("shards"),
                    "final_ratio": row.get("final_ratio"),
                    "max_ratio": row.get("max_ratio"),
                    "splits": row.get("splits"),
                    "merges": row.get("merges"),
                    "drift_p99_ms": row.get("drift_p99_ms"),
                    "steady_p99_ms": row.get("steady_p99_ms"),
                    "drift_p99_ratio": row.get("drift_p99_ratio"),
                    "repart_steps": row.get("repart_steps"),
                    "compact_steps": row.get("compact_steps"),
                    "compile_steps": row.get("compile_steps"),
                    "full_restacks": row.get("full_restacks"),
                    "boundary_version": row.get("boundary_version"),
                } for row in drift},
            }
        changed = True
    data = load("mixed_serving")
    if data is not None:
        rows = data.get("rows", [])
        # the write-path rows are a separate scenario (mode is an engine
        # write-path variant, not an overlay-vs-rebuild strategy) — they get
        # their own section below, not a slot in the per-dataset table
        wp = [r for r in rows if r.get("scenario") == "write_path"]
        rows = [r for r in rows if r.get("scenario") != "write_path"]
        by_ds: dict[str, dict] = {}
        for row in rows:
            ent = by_ds.setdefault(row["dataset"], {})
            ent[row["mode"]] = {
                "amortized_us_per_insert": row.get("amortized_us_per_insert"),
                "maintain_s": row.get("maintain_s"),
                "read_s": row.get("read_s"),
                "inserts": row.get("inserts"),
                "compactions": row.get("compactions"),
            }
            if row["mode"] == "overlay":
                ent["overlay_speedup_vs_rebuild"] = \
                    row.get("speedup_vs_rebuild")
        meta = {k: v for k, v in data.get("meta", {}).items()
                if k != "write_path"}
        sections["mixed_serving"] = {"emitter": "mixed_serving",
                                     "generated": stamp,
                                     "meta": meta,
                                     "datasets": by_ds}
        if wp:
            wp_meta = data.get("meta", {}).get("write_path", {})
            sections["write_path"] = {
                "emitter": "mixed_serving", "generated": stamp,
                "meta": wp_meta,
                "bytes_ratio_gate": wp_meta.get("gate_min_ratio"),
                "bytes_ratio": wp_meta.get("bytes_ratio"),
                "modes": {row["mode"]: {
                    "h2d_bytes_per_step": row.get("h2d_bytes_per_step"),
                    "host_ms_per_step": row.get("host_ms_per_step"),
                    "total_h2d_bytes": row.get("total_h2d_bytes"),
                    "overlay_fill_final": row.get("overlay_fill_final"),
                    "overlay_merges": row.get("overlay_merges"),
                    "overlay_reseeds": row.get("overlay_reseeds"),
                    "bytes_ratio_vs_full_repack":
                        row.get("bytes_ratio_vs_full_repack"),
                } for row in wp},
            }
        changed = True
    data = load("multi_device_serving")
    if data is not None:
        sections["multi_device"] = {
            "emitter": "multi_device_serving", "generated": stamp,
            "meta": data.get("meta", {}),
            "engines": {row["engine"]: {
                "devices": row.get("devices"),
                "shard_slots": row.get("shard_slots"),
                "per_shard_qcap": row.get("per_shard_qcap"),
                "lanes_per_device": row.get("lanes_per_device"),
                "read_throughput_ops_s": row.get("read_throughput_ops_s"),
                "speedup_vs_single_device":
                    row.get("speedup_vs_single_device"),
            } for row in data.get("rows", [])},
        }
        changed = True
    data = load("device_lookup")
    if data is not None:
        sections["device_lookup"] = {
            "emitter": "device_lookup", "generated": stamp,
            "meta": data.get("meta", {}),
            "datasets": {row["dataset"]: {
                "jnp_batch_qps": row.get("device_batch_qps"),
                "fused_kernel_qps": row.get("fused_kernel_qps"),
                "fused_speedup_vs_jnp": row.get("fused_speedup_vs_jnp"),
                "strategy": row.get("strategy"),
                "rows_dma_per_query": row.get("rows_dma_per_query"),
                "kernel_block_rounds": row.get("kernel_block_rounds"),
            } for row in data.get("rows", [])},
        }
        changed = True
    if not changed or not sections:
        return None
    for name, sec in sections.items():
        if sec.get("generated") == stamp:    # rebuilt this invocation
            _check_section(name, sec)
    doc = {"benchmark": "serving", "generated": stamp, "sections": sections}
    out.write_text(json.dumps(doc, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--module", default=None, choices=MODULES)
    ap.add_argument("--scale", default="small",
                    choices=["small", "paper", "large"])
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache(REPO_ROOT)}", flush=True)
    mods = [args.module] if args.module else MODULES
    failures = []
    for name in mods:
        print(f"\n{'=' * 72}\n=== benchmarks.{name} (scale={args.scale})\n"
              f"{'=' * 72}", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(scale=args.scale)
            print(f"[{name}] done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    # rebuild only the sections whose source module ran fresh in THIS
    # invocation — re-stamping leftover rows from an old run would present
    # stale numbers as current (other sections carry over unchanged)
    fresh = {m for m in set(SERVING_SECTIONS.values())
             if m in mods and m not in failures}
    if fresh:
        path = emit_bench_serving(fresh)
        if path is not None:
            print(f"serving perf snapshot written to {path}", flush=True)
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        return 1
    print(f"\nall {len(mods)} benchmarks green; results in experiments/bench/")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
