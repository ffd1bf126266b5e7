"""A configuration, a traffic mix and a per-layer metric join the benchmark as
new files plus new entries in BENCHMARK.json, with no existing file edited;
and the command refuses to run without a TPU or without the program."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_bench_new_files_are_found_by_name(checkout):
    before = digest(checkout)
    cfg = json.loads((checkout / "bench/configs/osm_200m.json").read_text())
    cfg.update(name="genome_tiny", dataset={"generator": "genome",
                                            "keys": 30_000}, shards=2)
    (checkout / "bench/configs/genome_tiny.json").write_text(json.dumps(cfg))
    (checkout / "bench/traffic/ycsb_b_tiny.json").write_text(json.dumps({
        "name": "ycsb_b_tiny", "loop": "closed", "ops_per_step": 200,
        "shares": {"read": 0.95, "update": 0.05},
        "request": {"dist": "zipfian", "constant": 0.99}}))
    (checkout / "bench/metrics/updates_per_step.tiny.py").write_text(
        "def read(ctx):\n"
        "    return ctx.ops['update'] / ctx.steps if ctx.steps else None\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "genome_tiny", "source": "test",
                            "file": "bench/configs/genome_tiny.json",
                            "reduced": ["keys"], "why": "test"})
    spec["workloads"].append({"name": "genome_tiny.ycsb_b", "chips": 1,
                              "config": "genome_tiny",
                              "traffic": "ycsb_b_tiny", "why": "test"})
    spec["per_layer"].append({"name": "updates_per_step.tiny", "unit": "ops",
                              "better": "higher",
                              "source": "program_counter", "layer": "engine",
                              "moves": "ops_per_s",
                              "workloads": ["genome_tiny.ycsb_b"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    after = digest(checkout)
    assert {k: after[k] for k in before} == before   # nothing edited

    cell = harness.load_cell("genome_tiny.ycsb_b", checkout)
    assert cell.config["dataset"]["keys"] == 30_000
    assert cell.mix.shares == {"read": 0.95, "update": 0.05}
    assert [m["name"] for m in cell.end_to_end] == ["ops_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["updates_per_step.tiny"]
    r = harness.run_cell("genome_tiny.ycsb_b", 3, 0.4, True, root=checkout,
                         require_peaks=False, compile_cache=False)
    assert r["correct"] is True
    assert r["metrics"]["updates_per_step.tiny"]["value"] == 10.0


def test_bench_cell_metrics_follow_benchmark_json():
    a = harness.load_cell("osm200m.ycsb_a.sat", ROOT)
    assert [m["name"] for m in a.end_to_end] == ["ops_per_s", "setup_s"]
    assert {m["name"] for m in a.per_layer} == {
        "step_ms.sat", "write_host_ms.sat", "merge_dev_ms.sat",
        "device_idle_share.sat"}
    for m in a.per_layer:
        assert callable(harness.metric_reader(m["name"], ROOT))


def test_bench_unknown_device_kind_is_an_error():
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v99")


def _run(cwd: Path, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "osm200m.ycsb_a.sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return proc.returncode != 0 and not last.startswith("{")


def test_bench_run_refuses_without_tpu():
    proc = _run(ROOT)
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no TPU" in proc.stderr


def test_bench_run_refuses_without_program(checkout):
    proc = _run(checkout)
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no program" in proc.stderr
