"""The trace reduction on a small recorded trace: busy union, idle share,
time per program, the top device ops and the idle gaps by host span."""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

EVENTS = json.loads((Path(__file__).parent / "data" /
                     "trace_events.json").read_text())


def test_bench_trace_busy_union_and_idle_share():
    red = trace.reduce(EVENTS)
    assert red.window_s == pytest.approx(1000e-9)
    # [100, 250] (two ops end to end) + [500, 700] + [900, 1000] (clipped)
    assert red.busy_s == pytest.approx(450e-9)
    assert red.idle_share == pytest.approx(0.55)
    assert red.devices == 1


def test_bench_trace_program_time():
    red = trace.reduce(EVENTS)
    assert red.program_s == pytest.approx({
        "jit_merge_overlay_pack_jnp": 170e-9,
        "jit_lookup_batch_sharded_overlay": 240e-9, "jit_other": 110e-9})
    assert red.program_seconds(("merge_overlay_pack", "overlay_merge")) \
        == pytest.approx(170e-9)
    assert red.program_seconds(("no_such_program",)) is None


def test_bench_trace_breakdown():
    red = trace.reduce(EVENTS)
    ops = dict(red.top_ops)
    assert ops == pytest.approx({
        "jit_merge_overlay_pack_jnp/fusion.1": 50e-9,
        "jit_merge_overlay_pack_jnp/scatter.2": 100e-9,
        "jit_lookup_batch_sharded_overlay/sort.3": 200e-9,
        "jit_other/copy.4": 100e-9})
    assert red.top_ops[0][0] == "jit_lookup_batch_sharded_overlay/sort.3"
    assert dict(red.idle_gaps) == pytest.approx(
        {"bench.admit": 100e-9, "bench.step": 250e-9, "bench.wait": 200e-9})
    assert red.idle_gaps[0][0] == "bench.step"


def test_bench_trace_counts_leaf_ops_by_short_name():
    ev = copy.deepcopy(EVENTS)
    ev["devices"]["/device:TPU:0"]["ops"] = [
        ["%while.5 = (u32[]) while(%tuple.1)", 100.0, 300.0],
        ["%fusion.7 = u32[8] fusion(%a)", 120.0, 50.0],
        ["%fusion.8 = u32[8] fusion(%b)", 200.0, 100.0]]
    red = trace.reduce(ev)
    assert red.busy_s == pytest.approx(300e-9)
    assert dict(red.top_ops) == pytest.approx({
        "jit_merge_overlay_pack_jnp/fusion.7": 50e-9,
        "jit_merge_overlay_pack_jnp/fusion.8": 100e-9})


def test_bench_trace_averages_devices():
    ev = copy.deepcopy(EVENTS)
    ev["devices"]["/device:TPU:1"] = {
        "ops": [["fusion.9", -50.0, 2000.0]],
        "programs": [["jit_other(3)", -50.0, 2000.0]]}
    red = trace.reduce(ev)
    assert red.devices == 2
    assert red.busy_s == pytest.approx((450e-9 + 1000e-9) / 2)


def test_bench_trace_without_device_or_spans():
    ev = copy.deepcopy(EVENTS)
    ev["devices"] = {}
    red = trace.reduce(ev)
    assert red.busy_s is None and red.idle_share is None
    ev["spans"] = []
    with pytest.raises(ValueError):
        trace.reduce(ev)


def test_bench_trace_collect_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.collect(trace.find_xplane(tmp_path))
    names = [s[0] for s in ev["spans"]]
    assert names.count("bench.step") == 3 and "bench.window" in names
    red = trace.reduce(ev)
    assert red.window_s > 0
