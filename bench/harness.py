"""One benchmark run of one cell: load, warm up, measure, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``BENCHMARK.json``'s ``configs[*].file``: the deployment (key generator and
  count, shards, gamma, the ``AulidConfig`` fields, the engine's options,
  chips, the guarantees it gives);
* ``bench/traffic/<traffic>.json``: the mix, read by ``generator.Mix``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  ``read(ctx)`` that returns a number or None when it finds nothing.

The system under test is driven only through its public API:
``partition_bulkload``, ``ShardedIndexEngine``, ``get`` / ``insert`` /
``delete`` / ``scan``, ``step()`` and ``stats()``. The window is a whole
number of engine steps: steps start until ``seconds`` have passed, and
rates are taken over the steps' own elapsed time. After the window every
answer of every step, warm-up included, is compared with the plain
reference (``reference.py``) in step order.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import resource
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

from . import datasets, reference, trace
from .generator import DELETE, OPS, READ, Generator, Mix, Ops

ROOT = Path(__file__).resolve().parent.parent
CLOSED_CHUNK_STEPS = 64      # closed-loop steps generated at a time
TRACE_SECONDS = 5.0          # traced stretch: at least this long ...
TRACE_MIN_STEPS = 2          # ... and at least this many whole steps
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def log(phase: str, **kw) -> None:
    print(f"{phase}: {json.dumps(kw, sort_keys=True, default=str)}",
          flush=True)


# ------------------------------------------------------------------ the spec
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: Mix
    chips: int
    end_to_end: list        # metric entries this cell reports
    per_layer: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, its configuration and mix files, and
    the metrics ``BENCHMARK.json`` has it report."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = Mix.from_file(root / "bench" / "traffic" / f"{wl['traffic']}.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (mine(m) if "workloads" in m else m["moves"] in names)]
    return Cell(workload, config, mix, int(wl["chips"]), e2e, layer)


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else the fixed, git-ignored
    ``<checkout>/.jax_cache``; every program is cached, however quick its
    compile, so a warm run compiles nothing."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(root.resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, *_args, **_kw) -> None:
        if self.on:
            self.traces += event == self.TRACE
            self.compiles += event == self.COMPILE


# ---------------------------------------------------------------------- load
def build_engine(config: dict, keys: np.ndarray, devices):
    """The configuration's engine over ``keys`` (payload key + 1)."""
    from repro.core import AulidConfig, partition_bulkload
    from repro.serving import ShardedIndexEngine
    aulid = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config.get("aulid", {}).items()}
    t0 = time.perf_counter()
    pays = keys + np.uint64(1)
    part = partition_bulkload(keys, pays, int(config["shards"]),
                              cfg=AulidConfig(**aulid))
    del pays
    t1 = time.perf_counter()
    mesh = None
    if int(config.get("chips", 1)) > 1:
        from repro.parallel import index_mesh
        mesh = index_mesh(int(config["chips"]), devices=devices)
    eng = ShardedIndexEngine(part, gamma=float(config["gamma"]), mesh=mesh,
                             **config.get("engine", {}))
    t2 = time.perf_counter()
    return eng, {"partition_bulkload_s": t1 - t0, "engine_build_s": t2 - t1}


# ------------------------------------------------------------------- serving
@dataclasses.dataclass
class Step:
    ops: Ops
    got: dict                # the engine's answers (reference.engine_answers)
    undone: int              # requests the step left unfinished
    phase: str               # warmup | window | drain
    t_admit: float
    t_start: float
    t_done: float
    host: dict               # HostProbe.delta over the step
    first: int = 0           # open loop: index of the first arrival served


def submit(eng, ops: Ops) -> list:
    """Queue ``ops`` on the engine in order; the requests it returns."""
    keys = ops.key.tolist()
    if not ops.op.any():                         # reads only
        get = eng.get
        return [get(k) for k in keys]
    sub = (eng.get, eng.insert, eng.insert, eng.delete, eng.scan)
    out = []
    for o, k, a in zip(ops.op.tolist(), keys, ops.arg.tolist()):
        if o == READ or o == DELETE:
            out.append(sub[o](k))
        else:
            out.append(sub[o](k, a))
    return out


class HostProbe:
    """What the host did during a step, to name where a slow step's time
    went without a trace: the process's CPU seconds (all threads), page
    faults, involuntary context switches (CPU taken by others), and
    Python's garbage collections with their generation and seconds. It
    reads and changes nothing of the collector's policy."""

    def __init__(self):
        self.collections: list = []      # (generation, seconds)
        self._t = 0.0
        gc.callbacks.append(self._gc)

    def close(self) -> None:
        gc.callbacks.remove(self._gc)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.collections.append((info["generation"],
                                     time.perf_counter() - self._t))

    def read(self) -> tuple:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (time.process_time(), ru.ru_minflt, ru.ru_majflt,
                ru.ru_nivcsw, len(self.collections))

    def delta(self, a: tuple, b: tuple) -> dict:
        gcs = self.collections[a[4]:b[4]]
        return {"cpu_s": b[0] - a[0], "minflt": b[1] - a[1],
                "majflt": b[2] - a[2], "nivcsw": b[3] - a[3],
                "gc": [[g, s] for g, s in gcs]}


class Session:
    """One engine under one mix: warm-up, the window, the drain."""

    def __init__(self, eng, mix: Mix, gen: Generator, annotate: bool,
                 probe: HostProbe | None = None):
        self.eng = eng
        self.mix = mix
        self.gen = gen
        self.steps: list[Step] = []
        self.gen_in_window_s = 0.0
        self._annotate = annotate
        self.probe = probe

    def span(self, name: str):
        if self._annotate:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def run_step(self, ops: Ops, phase: str, first: int = 0) -> Step:
        h0 = self.probe.read() if self.probe else None
        t_admit = time.perf_counter()
        with self.span("bench.admit"):
            reqs = submit(self.eng, ops)
        t_start = time.perf_counter()
        with self.span("bench.step"):
            self.eng.step()
        t_done = time.perf_counter()
        host = self.probe.delta(h0, self.probe.read()) if self.probe else {}
        # the client reads its answers, as one would; the run keeps them
        # as arrays for the check after the window, not the request objects
        got, undone = reference.engine_answers(ops.op, reqs)
        st = Step(ops, got, undone, phase, t_admit, t_start, t_done, host,
                  first)
        self.steps.append(st)
        return st

    # ---------------------------------------------------------------- warm-up
    def warm_up(self) -> None:
        """Every shape the window uses: closed, the mix's own warm-up steps;
        open, one step of each power-of-two batch up to the per-step cap."""
        if self.mix.loop == "closed":
            for ops in self.gen.closed_steps(-self.mix.warmup_steps,
                                             self.mix.warmup_steps):
                self.run_step(ops, "warmup")
            self.pending = self.gen.closed_steps(0, CLOSED_CHUNK_STEPS)
            return
        b = 1
        while b <= self.mix.max_ops_per_step:
            self.run_step(self.gen.block(self.mix.counts(b)), "warmup")
            b *= 2

    def prepare_open(self, seconds: float) -> None:
        self.due, self.arrivals = self.gen.open_arrivals(seconds)

    # ----------------------------------------------------------------- window
    def closed_window(self, seconds: float, min_steps: int = 1):
        t0 = time.perf_counter()
        k = 0
        with self.span("bench.window"):
            while k < min_steps or time.perf_counter() - t0 < seconds:
                if k == len(self.pending):
                    g = time.perf_counter()
                    self.pending += self.gen.closed_steps(
                        k, CLOSED_CHUNK_STEPS)
                    self.gen_in_window_s += time.perf_counter() - g
                self.run_step(self.pending[k], "window")
                k += 1
        return t0, self.steps[-1].t_done

    def open_window(self, seconds: float, min_steps: int = 1):
        due, cap = self.due, int(self.mix.max_ops_per_step)
        n = int(due.size)
        i = k = 0
        t0 = time.perf_counter()
        t_last = t0
        with self.span("bench.window"):
            while True:
                now = time.perf_counter()
                if now - t0 >= seconds and (k >= min_steps or i >= n):
                    break
                if i < n and t0 + due[i] <= now:
                    j = min(int(np.searchsorted(due, now - t0,
                                                side="right")), i + cap)
                    t_last = self.run_step(self.arrivals[i:j], "window",
                                           i).t_done
                    i = j
                    k += 1
                    continue
                wake = t0 + due[i] if i < n else t0 + seconds
                if now - t0 < seconds:
                    wake = min(wake, t0 + seconds)
                with self.span("bench.wait"):
                    time.sleep(max(0.0, wake - now))
        self.window_end_index = i
        return t0, max(t_last, t0 + seconds)

    def drain(self, upto: float) -> None:
        """Serve the open loop's requests due before ``upto`` seconds that
        the window left queued, oldest first."""
        cap = int(self.mix.max_ops_per_step)
        i = self.window_end_index
        n = int(np.searchsorted(self.due, upto, side="right"))
        while i < n:
            j = min(n, i + cap)
            self.run_step(self.arrivals[i:j], "drain", i)
            i = j


# -------------------------------------------------------------------- checks
def check(keys: np.ndarray, steps: list[Step]) -> dict:
    """Every answer of every step against the plain reference, in step
    order. Returns counts: answers compared, differing, never finished."""
    ref = reference.Reference(keys)
    attempted = differ = undone = 0
    for st in steps:
        want = reference.answers(ref, st.ops.op, st.ops.key, st.ops.arg)
        attempted += len(st.ops)
        undone += st.undone
        differ += reference.compare(st.got, want)
    return {"attempted": attempted, "differ": differ, "undone": undone}


# ------------------------------------------------------------------ metrics
def percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if values.size else float("nan")


def open_latencies(sess: Session, t0: float) -> np.ndarray:
    """Seconds from each served get's due time to its step's end."""
    out = []
    for st in sess.steps:
        if st.phase == "warmup":
            continue
        due = t0 + sess.due[st.first:st.first + len(st.ops)]
        lat = st.t_done - due
        out.append(lat[st.ops.op == READ])
    return np.concatenate(out) if out else np.zeros(0)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read: the traced window's steps, the
    engine's counters before and after it, and the trace's reduction."""
    cell: str
    step_s: list                 # host seconds of each traced eng.step()
    ops: dict                    # op name -> operations in the window
    stats_before: dict
    stats_after: dict
    trace: trace.Reduction | None

    @property
    def steps(self) -> int:
        return len(self.step_s)

    def median_step_s(self) -> float:
        return statistics.median(self.step_s)

    def counter_delta(self, name: str) -> float:
        return self.stats_after[name] - self.stats_before[name]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, devices=None, t_process: float | None = None,
             overrides: dict | None = None, require_peaks: bool = True,
             compile_cache: bool = True) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``overrides`` shrinks the configuration and mix for the CPU tests:
    {"keys": n, "shards": s, "mix": {field: value}}."""
    import jax
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(workload, root)
    config, mix = cell.config, cell.mix
    if overrides:
        config = {**config, "dataset": {**config["dataset"],
                                        "keys": overrides.get(
                                            "keys", config["dataset"]["keys"])},
                  "shards": overrides.get("shards", config["shards"])}
        mix = dataclasses.replace(mix, **overrides.get("mix", {}))
    devices = list(devices if devices is not None
                   else jax.devices()[:cell.chips])
    dev0 = devices[0]
    peaks = device_peaks(dev0.device_kind, root) if require_peaks else {}
    cache = enable_compile_cache(root) if compile_cache else None
    import repro.serving  # noqa: F401  (the program; enables x64)
    counter = CompileCounter()
    log("device", platform=dev0.platform, kind=dev0.device_kind,
        count=len(devices), compile_cache=cache)

    # ---- load
    ds = config["dataset"]
    t = time.perf_counter()
    keys = datasets.make_keys(ds["generator"], int(ds["keys"]), seed)
    make_s = time.perf_counter() - t
    eng, timing = build_engine(config, keys, devices)
    log("load", keys=int(keys.size), generator=ds["generator"],
        generator_version=datasets.VERSION, make_keys_s=make_s,
        data_cache="none: keys are made from the seed in every run",
        shards=eng.num_shards, overlay_cap=int(eng.ov_arrs["ov_pack"].shape[1]),
        **timing)

    # ---- warm up
    gen = Generator(mix, keys, seed)
    probe = HostProbe()
    sess = Session(eng, mix, gen, annotate=traced, probe=probe)
    t = time.perf_counter()
    sess.warm_up()
    run_seconds = min(seconds, TRACE_SECONDS) if traced else seconds
    if mix.loop == "open":
        sess.prepare_open(run_seconds)
    log("warmup", steps=len(sess.steps), seconds=time.perf_counter() - t,
        read_shape_misses=eng.stats()["read_shape_misses"])

    # ---- window
    tracer = None
    if traced:
        tracer = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tracer, profiler_options=opts)
    stats0 = eng.stats()
    counter.on = True
    setup_s = time.perf_counter() - t_process
    min_steps = TRACE_MIN_STEPS if traced else 1
    if mix.loop == "closed":
        t0, t_end = sess.closed_window(run_seconds, min_steps)
    else:
        t0, t_end = sess.open_window(run_seconds, min_steps)
    counter.on = False
    stats1 = eng.stats()
    if traced:
        jax.profiler.stop_trace()
    window = [s for s in sess.steps if s.phase == "window"]
    if mix.loop == "open":
        sess.drain(run_seconds)
    elapsed = t_end - t0
    w_ops = sum(len(s.ops) for s in window)
    counts = {o: int(sum(int(np.sum(s.ops.op == c)) for s in window))
              for c, o in enumerate(OPS)}
    step_s = [s.t_done - s.t_start for s in window]
    mem = memory_peak(devices)
    shape_misses = stats1["read_shape_misses"] - stats0["read_shape_misses"]
    collections = [c for s in window for c in s.host["gc"]]
    log("host", collections_by_generation={
            g: sum(1 for c in collections if c[0] == g) for g in (0, 1, 2)},
        collection_s=sum(c[1] for c in collections),
        collection_s_max=max((c[1] for c in collections), default=0.0),
        cpu_s=sum(s.host["cpu_s"] for s in window),
        majflt=sum(s.host["majflt"] for s in window),
        nivcsw=sum(s.host["nivcsw"] for s in window),
        engine={k: stats1[k] - stats0[k] for k in (
            "compactions", "swaps", "overlay_merges", "overlay_reseeds",
            "mirror_full_builds", "full_restacks")},
        each_step=([{"s": s.t_done - s.t_start, "cpu_s": s.host["cpu_s"],
                     "minflt": s.host["minflt"], "majflt": s.host["majflt"],
                     "nivcsw": s.host["nivcsw"],
                     "gc_s": sum(c[1] for c in s.host["gc"])}
                    for s in window] if len(window) <= 64 else None))
    probe.close()
    log("window", seconds=elapsed, steps=len(window), ops=w_ops,
        ops_by_type=counts,
        step_s_median=statistics.median(step_s) if step_s else None,
        step_s_max=max(step_s) if step_s else None,
        step_s_quantiles=(np.quantile(step_s, [0, .5, .9, .99, 1]).tolist()
                          if step_s else None),
        traces_in_window=counter.traces,
        compiles_in_window=counter.compiles,
        read_shape_misses_in_window=shape_misses,
        generator_in_window_s=sess.gen_in_window_s,
        memory_peak_bytes=mem,
        memory_peak_share_of_hbm=(mem / peaks["hbm_bytes"]
                                  if peaks else None))
    if shape_misses or counter.compiles or counter.traces:
        log("warning", message="something was traced or compiled inside "
            "the measured window", traces=counter.traces,
            compiles=counter.compiles, read_shape_misses=shape_misses)

    metrics: dict = {}
    dev_out = {"platform": dev0.platform, "kind": dev0.device_kind,
               "count": len(devices), "memory_peak_bytes": mem}
    result: dict = {}
    if not traced:
        lat = None
        if mix.loop == "open":
            lat = open_latencies(sess, t0)
            late = [s.t_admit - (t0 + sess.due[s.first]) for s in window]
            log("latency", gets=int(lat.size),
                get_p50_ms=percentile(lat, 50) * 1e3,
                get_p99_ms=percentile(lat, 99) * 1e3,
                get_max_ms=float(lat.max() * 1e3) if lat.size else None,
                gets_beyond_p99=int(np.sum(lat > percentile(lat, 99))),
                admit_late_ms_median=(statistics.median(late) * 1e3
                                      if late else None),
                left_queued_at_close=int(np.searchsorted(
                    sess.due, run_seconds, side="right")
                    - sess.window_end_index),
                offered_rate=mix.rate)
        values = {"ops_per_s": w_ops / elapsed if elapsed > 0 else None,
                  "setup_s": setup_s,
                  "get_p99_ms": (percentile(lat, 99) * 1e3
                                 if lat is not None and lat.size else None)}
        for m in cell.end_to_end:
            if values.get(m["name"]) is None:
                raise RuntimeError(f"end-to-end metric {m['name']} has no "
                                   f"value in {workload}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ev = trace.collect(trace.find_xplane(tracer))
        red = trace.reduce(ev)
        shutil.rmtree(tracer, ignore_errors=True)
        log("trace", lines=ev["lines"], spans=len(ev["spans"]),
            window_s=red.window_s, busy_s=red.busy_s, devices=red.devices,
            programs=dict(sorted(red.program_s.items(),
                                 key=lambda kv: -kv[1])[:12]))
        ctx = LayerContext(workload, step_s, counts, stats0, stats1, red)
        for m in cell.per_layer:
            v = metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red.busy_s is not None:
            dev_out["busy_s"] = red.busy_s
            dev_out["window_s"] = red.window_s
            result["breakdown"] = {"device_ops": red.top_ops,
                                   "idle_gaps": red.idle_gaps}

    # ---- correctness, after the window, outside the timed part
    t = time.perf_counter()
    chk = check(keys, sess.steps)
    failed = chk["differ"] + chk["undone"]
    log("check", seconds=time.perf_counter() - t, steps=len(sess.steps),
        **chk)
    return {"correct": failed == 0, "attempted": chk["attempted"],
            "failed": failed, "metrics": metrics, "device": dev_out,
            **result,
            "compared": {"mismatches": {"value": failed, "limit": 0}}}
