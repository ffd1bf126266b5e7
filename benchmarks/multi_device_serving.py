"""Multi-device mesh serving: read throughput vs the single-device engine.

The tentpole measurement of the mesh-placement PR (DESIGN.md §13): a
``ShardedIndexEngine`` whose stacked pools live on an N-device index mesh
serves batched reads with per-device LOCAL traversal — each device routes
the replicated query batch against the replicated boundary table, packs
only the queries it owns into an ``(S/N, qcap)`` lane matrix, traverses its
own pool slice, and the ``(B,)`` result planes ``psum`` together.  The
single-device engine traverses an always-safe ``(S, Q)`` lane matrix.

On a TPU host every variant runs in this one process over a prefix of
``jax.devices()`` (a chip belongs to one process; a child could not reach
it), and device counts beyond the host's are skipped.  On the CPU, because
jax pins its device topology at import, every engine variant runs in a
fresh subprocess with ``--xla_force_host_platform_device_count`` set; the
parent collates the children's JSON rows.  Forced host devices time-slice
the CPU, so the speedup that survives there is the WORK reduction of tight
per-device lane packing — total traversal lanes drop from ``S * Q`` to
``S * qcap`` with ``qcap`` the host-routed per-shard occupancy bound — plus
the per-device parallelism headroom the lane counts document for real
multi-chip hosts.

Acceptance gate: mesh at 4 devices >= 2x the single-device engine's read
throughput (uniform batched gets, identical dataset/geometry/batch).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

GATE_SPEEDUP = 2.0
GATE_DEVICES = 4
DEVICE_COUNTS = (1, 2, 4)
NUM_SHARDS = 16    # many-shard regime: the paper's pod serves O(10) shards
BATCH_Q = 4_096
STEPS = 24
WARMUP = 4
REPEATS = 3   # best-of-N: single-core container timing is noisy

_REPO = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------------- variant
def _variant(mode: str, devices: int, n: int) -> dict:
    """One engine variant over the first ``devices`` devices -> its row."""
    import jax

    from repro.core import AulidConfig, partition_bulkload
    from repro.core.workloads import make_dataset, payloads_for
    from repro.serving import ShardedIndexEngine

    assert jax.device_count() >= devices, (jax.device_count(), devices)
    keys = make_dataset("covid", n, seed=1)
    pay = payloads_for(keys)
    part = partition_bulkload(keys, pay, NUM_SHARDS,
                              cfg=AulidConfig(leaf_capacity=16,
                                              pa_classes=(4, 8),
                                              bt_child_capacity=15))
    mesh = None
    if mode == "mesh":
        from repro.parallel import index_mesh
        mesh = index_mesh(devices)
    eng = ShardedIndexEngine(part, gamma=0.05, backend="jnp", mesh=mesh)

    rng = np.random.default_rng(2)
    batches = [rng.choice(keys, BATCH_Q) for _ in range(WARMUP + STEPS)]
    best = None
    for _ in range(REPEATS):
        served = 0
        elapsed = 0.0
        for i, batch in enumerate(batches):
            reqs = [eng.get(int(k)) for k in batch]
            t0 = time.perf_counter()
            eng.step()
            dt = time.perf_counter() - t0
            assert all(r.done and r.result is not None for r in reqs)
            if i >= WARMUP:
                served += len(reqs)
                elapsed += dt
        tput = served / elapsed
        best = max(best or 0.0, tput)
    S = int(eng._snap()["meta"].shape[0])
    qcap = eng._mesh_qcap(np.sort(batches[-1]).astype(np.uint64)) \
        if mesh is not None else BATCH_Q
    sl = S // devices if mesh is not None else S
    row = {
        "engine": f"mesh_{devices}dev" if mode == "mesh" else "single_device",
        "mode": mode, "devices": devices if mode == "mesh" else 1,
        "shard_slots": S, "per_shard_qcap": int(qcap),
        "lanes_per_device": sl * int(qcap),
        "total_lanes": S * int(qcap) if mode == "mesh" else S * BATCH_Q,
        "read_throughput_ops_s": round(best, 1),
        "mesh_devices": eng.stats()["mesh_devices"],
    }
    return row


# -------------------------------------------------------------------- parent
def _spawn(mode: str, devices: int, n: int) -> dict:
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_REPO / "src"), str(_REPO)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, __file__, "--child", mode, str(devices), str(n)],
        capture_output=True, text=True, timeout=1800, env=env, cwd=_REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multi_device child {mode}/{devices} failed:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("ROW "):
            return json.loads(line[4:])
    raise RuntimeError(f"child {mode}/{devices} printed no ROW line")


def run(scale: str = "small") -> list[dict]:
    import jax
    from .common import SCALE_N, print_table, save_results
    n = SCALE_N[scale]
    on_tpu = jax.default_backend() == "tpu"
    counts = [d for d in DEVICE_COUNTS
              if not on_tpu or d <= len(jax.devices())]
    variant = _variant if on_tpu else _spawn
    rows = [variant("single", 1, n)]
    for d in counts:
        rows.append(variant("mesh", d, n))
    base = rows[0]["read_throughput_ops_s"]
    for r in rows:
        r["speedup_vs_single_device"] = round(
            r["read_throughput_ops_s"] / base, 2)
    save_results("multi_device_serving", rows,
                 {"scale": scale, "num_shards": NUM_SHARDS,
                  "batch_q": BATCH_Q, "steps": STEPS, "repeats": REPEATS,
                  "gate_speedup": GATE_SPEEDUP,
                  "gate_devices": GATE_DEVICES,
                  "platform": jax.devices()[0].platform,
                  "device_kind": jax.devices()[0].device_kind,
                  "note": ("one process over the host's chips" if on_tpu
                           else "forced host devices time-slice the CPU: "
                           "the measured speedup is the lane-packing work "
                           "reduction; lanes_per_device documents the "
                           "per-chip parallel headroom")})
    print_table(
        "Mesh-placed sharded serving: batched read throughput vs the "
        f"single-device engine ({jax.devices()[0].device_kind})",
        rows, ["engine", "devices", "shard_slots", "per_shard_qcap",
               "lanes_per_device", "read_throughput_ops_s",
               "speedup_vs_single_device"])
    gate = next((r for r in rows
                 if r["engine"] == f"mesh_{GATE_DEVICES}dev"), None)
    if gate is None:
        print(f"\nno {GATE_DEVICES}-device mesh on this host: gate not run")
        return rows
    sp = gate["speedup_vs_single_device"]
    print(f"\nmesh@{GATE_DEVICES} read-throughput speedup {sp:.2f}x "
          f"(acceptance gate: >= {GATE_SPEEDUP}x)")
    assert sp >= GATE_SPEEDUP, \
        f"acceptance criterion: >= {GATE_SPEEDUP}x at {GATE_DEVICES} devices"
    return rows


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        print("ROW " + json.dumps(
            _variant(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))))
    else:
        run(sys.argv[1] if len(sys.argv) > 1 else "small")
