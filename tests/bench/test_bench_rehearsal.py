"""The cell rehearsed on the CPU at tiny sizes through the harness's own
functions (everything of a run but its look for a chip), under its own mix,
a reads-only mix and the fixed-rate open loop; the correctness check failing
under each fault a cell can have, and the control failing."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import control, harness  # noqa: E402

CELL_A = "osm200m.ycsb_a.sat"
TINY = {"keys": 40_000, "shards": 4, "mix": {"ops_per_step": 256}}
# reads only, as YCSB-C sends them: the engine's get-only step
READS = {"keys": 40_000, "shards": 4,
         "mix": {"ops_per_step": 512, "shares": {"read": 1.0}}}
# the open loop of the fixed-rate mix (bench/traffic/ycsb_c_rate.json)
OPEN = {"keys": 40_000, "shards": 4,
        "mix": {"loop": "open", "rate": 3000.0, "max_ops_per_step": 128,
                "shares": {"read": 1.0}}}
MIXES = {"closed": TINY, "reads": READS, "open": OPEN}


def run(cell, seed=2 ** 31 + 7, seconds=0.6, traced=False, overrides=None):
    return harness.run_cell(cell, seed, seconds, traced,
                            overrides=overrides or TINY,
                            require_peaks=False, compile_cache=False)


@pytest.mark.parametrize("mix", ["closed", "reads", "open"])
def test_bench_rehearsal_correct(mix):
    r = run(CELL_A, overrides=MIXES[mix])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"ops_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "compared"
    assert r["compared"] == {"mismatches": {"value": 0, "limit": 0}}
    assert r["device"]["platform"] == "cpu"


def test_bench_rehearsal_traced():
    r = run(CELL_A, traced=True)
    assert r["correct"] is True
    # no device plane on the CPU: the device readers find nothing to read
    assert set(r["metrics"]) == {"step_ms.sat", "write_host_ms.sat"}
    assert r["metrics"]["step_ms.sat"]["value"] > 0


def _fault(monkeypatch, kind):
    from repro.serving import ShardedIndexEngine
    from repro.serving.index_engine import BaseIndexEngine
    serve = BaseIndexEngine._serve_gets
    if kind == "state_unchanged":
        def apply(self, req):
            req.result, req.done = True, True
        monkeypatch.setattr(ShardedIndexEngine, "_apply_write", apply)
    elif kind == "half_batch":
        def half(self, gets):
            serve(self, gets[:len(gets) // 2])
        monkeypatch.setattr(BaseIndexEngine, "_serve_gets", half)
    elif kind == "answer_altered":
        def altered(self, gets):
            serve(self, gets)
            g = gets[len(gets) // 2]
            g.result = (g.result or 0) + 1
        monkeypatch.setattr(BaseIndexEngine, "_serve_gets", altered)


@pytest.mark.parametrize("mix,kind", [
    ("closed", "state_unchanged"), ("closed", "half_batch"),
    ("closed", "answer_altered"), ("reads", "half_batch"),
    ("reads", "answer_altered")])
def test_bench_fault_fails_check(monkeypatch, mix, kind):
    _fault(monkeypatch, kind)
    r = run(CELL_A, seconds=0.3, overrides=MIXES[mix])
    assert r["correct"] is False
    assert r["failed"] > 0
    assert r["compared"]["mismatches"]["value"] == r["failed"]


@pytest.mark.parametrize("mix", ["closed", "reads"])
def test_bench_control_fails(mix):
    c = harness.load_cell(CELL_A, ROOT)
    out = control.control_reading(c, 11, steps=3, seconds=0.5,
                                  overrides=MIXES[mix])
    assert out["key_dtype"] == "float32"
    assert out["answers"] > 0 and out["differ"] > 0
    assert out["correct"] is False


def test_bench_reference_semantics():
    from bench.generator import DELETE, INSERT, READ, SCAN, UPDATE
    from bench.reference import MISSING, Reference, answers
    keys = np.array([10, 20, 30, 40], dtype=np.uint64)
    ref = Reference(keys)
    op = np.array([UPDATE, INSERT, DELETE, READ, READ, READ, READ, SCAN],
                  dtype=np.uint8)
    key = np.array([20, 25, 30, 20, 25, 30, 35, 15], dtype=np.uint64)
    arg = np.array([7, 8, 0, 0, 0, 0, 0, 3], dtype=np.uint64)
    a = answers(ref, op, key, arg)
    assert a["writes"].tolist() == [True, True, True]
    assert a["reads"].tolist() == [7, 8, int(MISSING), int(MISSING)]
    assert a["scans"] == [[(20, 7), (25, 8), (40, 41)]]


def test_bench_host_probe_sees_collections():
    import gc
    probe = harness.HostProbe()
    try:
        a = probe.read()
        gc.collect()
        d = probe.delta(a, probe.read())
    finally:
        probe.close()
    assert [g for g, _ in d["gc"]] == [2]
    assert d["cpu_s"] >= 0 and d["minflt"] >= 0 and d["nivcsw"] >= 0
    assert probe._gc not in gc.callbacks
